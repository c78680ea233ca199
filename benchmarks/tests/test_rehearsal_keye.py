"""``run.py`` rehearsed on the CPU for the kind ``serve_closed_sparse``
at a tiny size (d=32, 2 layers, 16 experts top-4, an indexer of topk 16
under contexts of 40-116, float32 compute so that the program and the
reference agree to rounding), then with the timed path broken
underneath: ``correct`` must come out false for each fault.  And the
load loop's repair of ``client.py``'s race."""

import io
import json
import os
import threading

import pytest

from benchmarks import client_closed, run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny_keye")
PER_LAYER = {"prefill_share_pct", "gap_ms_p50", "gap_ms_p95",
             "idle_pct.serve", "tick_host_ms_p50", "engine_host_ms_p50",
             "slot_occupancy_pct", "sparse_serve_mfu",
             "dsa_selected_share_pct", "experts_touched_p50"}


@pytest.fixture(autouse=True)
def f32_compute():
    from veles_tpu.config import root
    prev = root.common.engine.get("precision_level", 0)
    root.common.engine.precision_level = 1
    try:
        yield
    finally:
        root.common.engine.precision_level = prev


def drive(trace=0, seed=3000000019, seconds=2):
    out = io.StringIO()
    rc = run.main(["--workload", "tiny.serve_long", "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  require_tpu=False, root=TINY, out=out)
    assert rc == 0
    return json.loads(out.getvalue().splitlines()[-1])


@pytest.mark.parametrize("trace,metrics", [
    (0, {"out_tokens_per_s", "setup_s"}), (1, PER_LAYER)])
def test_the_new_kind_runs_and_is_correct(trace, metrics):
    line = drive(trace)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == metrics
    assert all(m["value"] is not None for m in line["metrics"].values())
    assert line["compared"]["compiles_in_window"]["value"] == 0
    assert line["compared"]["logit_gap"]["value"] < 1e-3
    notes = line["notes"]
    # two finished answers and, if one had begun to stream, a cut one
    assert notes["checked_answers"] in (2, 3) and notes["checked_tokens"] > 0
    assert notes["tick"]["p50_tick_sel_keys"] \
        < notes["tick"]["p50_tick_kv_tokens"]
    # the window opened on the loop's second finished request
    assert notes["window_opened_on_count"] is True
    if trace:
        assert line["metrics"]["dsa_selected_share_pct"]["value"] < 60
        assert 1 <= line["metrics"]["experts_touched_p50"]["value"] <= 8


def test_the_selection_switched_off_is_not_correct(monkeypatch):
    """Every key attended, in prefill and in decode alike: the indexer
    told to keep more keys than any context holds."""
    from veles_tpu.models import layers
    real = layers.TransformerBlock._attn_kwargs

    def all_keys(self):
        kw = real(self)
        kw["indexer"] = dict(kw["indexer"], topk=10 ** 9)
        return kw

    monkeypatch.setattr(layers.TransformerBlock, "_attn_kwargs", all_keys)
    line = drive()
    assert line["correct"] is False
    c = line["compared"]["logit_gap"]
    assert c["value"] > c["limit"]


def test_an_experts_output_left_out_is_not_correct(monkeypatch):
    """Of each token's four experts the one with the largest gate adds
    nothing.  (Expert 0 alone left out reads 0.03 to 0.9 here from run
    to run: at 16 experts it is a quarter of the tokens' fourth part.)"""
    from veles_tpu.ops import moe
    real = moe.route_topk

    def without_the_first(x2d, router, top_k):
        gates, experts = real(x2d, router, top_k)
        return gates.at[:, 0].set(0.0), experts

    monkeypatch.setattr(moe, "route_topk", without_the_first)
    line = drive()
    assert line["correct"] is False
    c = line["compared"]["logit_gap"]
    assert c["value"] > c["limit"]


def test_a_served_token_altered_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    from veles_tpu.models import generate
    real = generate.LMGenerator._step_paged

    def altered(self, *a, **k):
        logits, pool = real(self, *a, **k)
        return jnp.roll(logits, 1, axis=-1), pool

    monkeypatch.setattr(generate.LMGenerator, "_step_paged", altered)
    line = drive()
    assert line["correct"] is False
    c = line["compared"]["logit_gap"]
    assert c["value"] > c["limit"]


# ------------------------------------------------------------ the load loop
def _record(outcome):
    return {"sent": 0.0, "first": None, "line_times": [],
            "line_tokens": [], "streamed": [], "done": None,
            "result": None, "phases": None, "outcome": outcome}


def _stream():
    while True:
        yield [1, 2, 3], 4


@pytest.mark.parametrize("outcome,stopped,want", [
    ("error", True, "cut"),            # client.py's early return
    ("error", False, "error"),         # bare "error" with no stop: kept
    ("error:OSError", True, "error:OSError"),   # a real failure stays
    ("http_503", True, "http_503"),
    ("ok", True, "ok"),
])
def test_the_load_loop_repairs_only_the_race(outcome, stopped, want):
    stop = threading.Event()

    def request(host, port, path, prompt, max_new, cut=None, live=None):
        if stopped:
            cut.set()       # the stop lands while this one is leaving
        else:
            threading.Timer(0.05, cut.set).start()
        return _record(outcome)

    records = client_closed.closed_loop("h", 0, "/", _stream(), 1, stop,
                                        request=request)
    assert records[0]["outcome"] == want
    assert records[0]["prompt"] == [1, 2, 3]


def test_the_clients_first_requests_leave_in_the_streams_order():
    """Ten threads started at once race for the stream; started
    ``START_GAP_S`` apart they take its first sizes in order."""
    stop, order = threading.Event(), []

    def stream():
        n = 0
        while True:
            yield [n], 1
            n += 1

    def request(host, port, path, prompt, max_new, cut=None, live=None):
        order.append(prompt[0])
        cut.wait()                  # every client holds its first one
        return _record("cut")

    threading.Timer(0.05 * 6 + 0.3, stop.set).start()
    client_closed.closed_loop("h", 0, "/", stream(), 6, stop,
                              request=request)
    assert order == list(range(6))


def test_the_window_waits_for_a_count_of_finished_requests():
    from benchmarks.kinds import serve_closed_sparse as kind

    class Engine:
        def __init__(self):
            self.n = 0

        def metrics(self):
            self.n += 1
            return {"served": self.n}

    assert kind.wait_served(Engine(), 5, 10.0, poll_s=0.001) is True
    assert kind.wait_served(Engine(), 10 ** 9, 0.05, poll_s=0.001) is False


def test_the_expert_dropped_control_leaves_the_first_expert_out():
    import json as _json
    from benchmarks import reference_keye
    with open(os.path.join(TINY, "benchmarks", "configs",
                           "tiny-keye.json")) as f:
        cfg = _json.load(f)
    prompt = list(range(3, 43))
    sample = [{"prompt": prompt, "result": prompt + [5, 9, 2, 7]}]
    whole, n = reference_keye.logit_gaps(cfg, 11, sample)
    dropped, _ = reference_keye.logit_gaps(cfg, 11, sample, drop_expert=0)
    assert n == 4 and whole > 0.0 and dropped >= 0.0
    with pytest.raises(ValueError):
        reference_keye.logit_gaps(cfg, 11, sample, drop_expert=3)
