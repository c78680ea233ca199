"""``run.py`` rehearsed on the CPU for the kind ``serve_closed_mixed``
at a tiny size (d=64, 4 layers: three windows of 8 with rope and a full
one without, 2 of 8 experts held top-2, 2 shared experts, contexts of
6-76 on rings of 5 blocks of 4; float32 compute so that the program and
the reference agree to rounding), then with the timed path broken
underneath, one fault a control of ``logit_gap``: ``correct`` must come
out false for each."""

import io
import json
import os

import pytest

from benchmarks import run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny_cmda")
PER_LAYER = {"prefill_share_pct", "gap_ms_p50", "gap_ms_p95",
             "idle_pct.serve", "tick_host_ms_p50", "engine_host_ms_p50",
             "slot_occupancy_pct", "experts_touched_p50",
             "mixed_serve_mfu", "window_keys_share_pct"}


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
    rc = run.main(["--workload", "tiny.serve_mixed", "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  require_tpu=False, root=TINY, out=out)
    assert rc == 0
    return json.loads(out.getvalue().splitlines()[-1])


def not_correct(line):
    assert line["correct"] is False
    c = line["compared"]["logit_gap"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("trace,metrics", [
    (0, {"out_tokens_per_s", "setup_s"}), (1, PER_LAYER)])
def test_the_new_kind_runs_and_is_correct(trace, metrics):
    line = drive(trace)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    # the two rooflines read a device trace's kernel names: the CPU
    # interprets the kernels, and the line leaves them out
    assert set(line["metrics"]) == metrics
    assert all(m["value"] is not None for m in line["metrics"].values())
    assert line["compared"]["compiles_in_window"]["value"] == 0
    assert line["compared"]["logit_gap"]["value"] < 1e-4
    notes = line["notes"]
    assert notes["checked_answers"] in (2, 3) and notes["checked_tokens"] > 0
    # a checked answer passed window + prefill_segment: the ring wrapped
    assert notes["checked_last_position"] > 16
    assert notes["window_opened_on_count"] is True
    assert notes["parameters"] > 0
    tick = notes["tick"]
    assert 0 < tick["p50_tick_win_keys"] < tick["p50_tick_kv_tokens"]
    assert tick["p50_tick_win_keys"] < tick["p50_tick_sel_keys"] \
        < tick["p50_tick_kv_tokens"]
    assert notes["pool_blocks_window_in_use_max"] <= 2 * 5
    assert notes["pool_blocks_full_in_use_max"] > 0
    dev = line["device"]["memory_peak_bytes"]
    assert dev > notes["weights_bytes"]
    if trace:
        assert 0 < line["metrics"]["window_keys_share_pct"]["value"] < 100
        assert 0 <= line["metrics"]["experts_touched_p50"]["value"] <= 2


def test_a_run_whose_answers_stay_inside_the_ring_is_not_correct(
        monkeypatch):
    """No checked answer past window + prefill_segment: the mechanism
    went unchecked."""
    from benchmarks.kinds import serve_closed_mixed as kind
    monkeypatch.setattr(
        kind, "check_sample", lambda records, n, seed: [
            {"prompt": r["prompt"][:5], "result": r["prompt"][:9]}
            for r in records[:1]])
    line = drive()
    assert line["correct"] is False
    assert line["compared"]["logit_gap"]["value"] == float("inf")


def test_the_window_switched_off_is_not_correct(monkeypatch):
    """The sliding layers attend the whole context (a window past
    ``max_len`` never bites: they join the whole-context group)."""
    from benchmarks import build_cmda
    real = build_cmda.zoo_kwargs
    monkeypatch.setattr(build_cmda, "zoo_kwargs", lambda cfg: dict(
        real(cfg), window=10 ** 6))
    not_correct(drive())


def test_rope_on_the_full_layers_is_not_correct(monkeypatch):
    from benchmarks import build_cmda
    real = build_cmda.zoo_kwargs
    monkeypatch.setattr(build_cmda, "zoo_kwargs", lambda cfg: dict(
        real(cfg), rope=None))
    not_correct(drive())


def test_the_shared_experts_left_out_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    from veles_tpu.ops import moe
    monkeypatch.setattr(moe, "shared_experts_forward",
                        lambda params, x, *a, **k: jnp.zeros_like(x))
    not_correct(drive())


def test_a_softmax_where_the_router_has_a_sigmoid_is_not_correct(
        monkeypatch):
    from veles_tpu.ops import moe
    monkeypatch.setattr(moe, "route_sigmoid_topk", moe.route_topk)
    not_correct(drive())


def test_a_served_token_altered_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    from veles_tpu.models import generate
    real = generate.LMGenerator._step_paged

    def altered(self, *a, **k):
        logits, pool = real(self, *a, **k)
        return jnp.roll(logits, 1, axis=-1), pool

    monkeypatch.setattr(generate.LMGenerator, "_step_paged", altered)
    not_correct(drive())
