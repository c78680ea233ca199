"""``run.py`` rehearsed on the CPU for the kind ``serve_closed_state``
at a tiny size (d=32, 2 retention layers of 4 query / 2 KV heads of 16,
half-lives 2 and 64 tokens, contexts of 6-76 admitted in passes of 8;
float32 compute so that the program and the quadratic reference agree
to rounding), then with the timed path broken underneath, one fault a
control of ``logit_gap``: ``correct`` must come out false for each."""

import io
import json
import os

import pytest

from benchmarks import run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny_brumby")
PER_LAYER = {"prefill_share_pct", "gap_ms_p50", "gap_ms_p95",
             "idle_pct.serve", "tick_host_ms_p50", "engine_host_ms_p50",
             "slot_occupancy_pct", "retention_serve_mfu",
             "retention_state_share_pct"}


@pytest.fixture(autouse=True)
def f32_compute():
    from veles_tpu.config import root
    prev = root.common.engine.get("precision_level", 0)
    root.common.engine.precision_level = 1
    try:
        yield
    finally:
        root.common.engine.precision_level = prev


def drive(trace=0, seed=3000000037, seconds=2):
    out = io.StringIO()
    rc = run.main(["--workload", "tiny.serve_decode", "--seed", str(seed),
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
    # the roofline reads a device trace's kernel name: the CPU runs the
    # step's XLA form, and the line leaves it out
    assert set(line["metrics"]) == metrics
    assert all(m["value"] is not None for m in line["metrics"].values())
    assert line["compared"]["compiles_in_window"]["value"] == 0
    assert line["compared"]["logit_gap"]["value"] < 1e-4
    notes = line["notes"]
    assert notes["checked_answers"] in (2, 3) and notes["checked_tokens"] > 0
    # a checked answer crossed two pass boundaries
    assert notes["checked_last_position"] > 17
    assert notes["window_opened_on_count"] is True
    assert notes["parameters"] > 0
    tick = notes["tick"]
    # both slots' state moved a tick (the XLA form moves every slot),
    # read + written: 2 x 2 rows x the row's bytes
    assert tick["p50_tick_state_rows"] == 2
    assert tick["p50_tick_state_bytes"] == 4 * notes["state_row_bytes"]
    assert notes["state_slots_in_use_max"] == 2
    dev = line["device"]["memory_peak_bytes"]
    assert dev == notes["weights_bytes"] + 2 * notes["state_row_bytes"]
    if trace:
        assert 0 < line["metrics"]["retention_state_share_pct"]["value"] \
            < 100
        assert notes["traced_decode_tokens"] > 0


def test_a_run_whose_answers_cross_no_pass_boundary_is_not_correct(
        monkeypatch):
    from benchmarks.kinds import serve_closed_state as kind
    monkeypatch.setattr(
        kind, "check_sample", lambda records, n, seed: [
            {"prompt": r["prompt"][:5], "result": r["prompt"][:9]}
            for r in records[:1]])
    line = drive()
    assert line["correct"] is False
    assert line["compared"]["logit_gap"]["value"] == float("inf")


def test_the_state_dropped_between_passes_is_not_correct(monkeypatch):
    """The planted ``state_reset``: every staged pass starts from a
    zero state, as if the staging row were not handed on."""
    import jax
    from veles_tpu.ops import retention
    real = retention.mixer_chunk

    def dropped(params, x, state, *args, **kwargs):
        if state is not None:
            state = jax.tree_util.tree_map(lambda a: a * 0, state)
        return real(params, x, state, *args, **kwargs)

    monkeypatch.setattr(retention, "mixer_chunk", dropped)
    not_correct(drive())


def test_the_gate_held_open_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    from veles_tpu.ops import retention
    real = retention._project

    def open_gate(*args, **kwargs):
        q, k, v, logg = real(*args, **kwargs)
        return q, k, v, jnp.zeros_like(logg)

    monkeypatch.setattr(retention, "_project", open_gate)
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
