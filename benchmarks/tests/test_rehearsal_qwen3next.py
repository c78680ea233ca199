"""``run.py`` rehearsed on the CPU for the kind ``serve_closed_hybrid``
at a tiny size (d=32, three gated-DeltaNet layers of 2 key / 4 value
heads of 8 and a gated-attention layer of 4 / 2 heads of 16, half-lives
2 to 64 tokens, contexts of 10-84 admitted in passes of 8; float32
compute so that the program and the token-by-token reference agree to
rounding), then with the timed path broken underneath, one fault a
control of ``logit_gap``: ``correct`` must come out false for each."""

import io
import json
import os

import pytest

from benchmarks import run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny_qwen3next")
PER_LAYER = {"prefill_share_pct", "gap_ms_p50", "gap_ms_p95",
             "idle_pct.serve", "tick_host_ms_p50", "engine_host_ms_p50",
             "slot_occupancy_pct", "hybrid_serve_mfu",
             "experts_touched_p50"}


@pytest.fixture(autouse=True)
def f32_compute():
    from veles_tpu.config import root
    prev = root.common.engine.get("precision_level", 0)
    root.common.engine.precision_level = 1
    try:
        yield
    finally:
        root.common.engine.precision_level = prev


def drive(trace=0, seed=3000000043, seconds=2):
    out = io.StringIO()
    rc = run.main(["--workload", "tiny.serve_long", "--seed", str(seed),
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
    assert line["compared"]["state_gap"]["value"] < 1e-4
    notes = line["notes"]
    assert notes["state_probe_tokens"] == 47
    assert notes["checked_tokens"] > 0
    assert notes["checked_last_position"] > 17
    assert notes["window_opened_on_count"] is True
    tick = notes["tick"]
    # both kinds of serving memory were held, and the device counted
    # both (the XLA form moves every slot's state)
    assert tick["p50_tick_state_rows"] == 2
    assert tick["admit_state_bytes"] > 0 and tick["admit_blocks"] > 0
    assert notes["state_slots_in_use_max"] == 2
    assert notes["blocks_in_use_max"] > 0
    dev = line["device"]["memory_peak_bytes"]
    assert notes["weights_bytes"] < dev <= notes["weights_bytes"] \
        + 2 * notes["state_row_bytes"] \
        + notes["pool_blocks"] * notes["block_bytes"]
    if trace:
        assert notes["traced_decode_tokens"] > 0


def test_a_run_whose_answers_stay_short_is_not_correct(monkeypatch):
    from benchmarks.kinds import serve_closed_hybrid as kind
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
    from veles_tpu.ops import deltanet
    real = deltanet.mixer_chunk

    def dropped(params, x, state, *args, **kwargs):
        if state is not None:
            state = jax.tree_util.tree_map(lambda a: a * 0, state)
        return real(params, x, state, *args, **kwargs)

    monkeypatch.setattr(deltanet, "mixer_chunk", dropped)
    not_correct(drive())


def test_the_state_kept_in_bfloat16_is_not_correct(monkeypatch):
    """The planted ``state_bf16``: S rounded to bfloat16 wherever the
    program hands it on (a pass's end, a decode step's): ``state_gap``
    must see it."""
    import jax.numpy as jnp
    from veles_tpu.ops import deltanet

    def rounded(real):
        def run(*args, **kwargs):
            o, s = real(*args, **kwargs)
            return o, s.astype(jnp.bfloat16).astype(jnp.float32)
        return run

    for name in ("deltanet_step", "delta_chunk"):
        monkeypatch.setattr(deltanet, name,
                            rounded(getattr(deltanet, name)))
    line = drive()
    assert line["correct"] is False
    c = line["compared"]["state_gap"]
    assert c["value"] > c["limit"]


def test_the_attention_gate_left_out_is_not_correct(monkeypatch):
    from veles_tpu.ops import attention
    real = attention._out_proj

    def ungated(params, o, x, policy):
        return real({k: v for k, v in params.items() if k != "wgate"},
                    o, x, policy)

    monkeypatch.setattr(attention, "_out_proj", ungated)
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


def test_the_paged_roofline_counts_the_one_attention_layer():
    """``paged_hybrid_roofline_pct`` from a hand-made trace: the keys of
    the tokens delivered while the profiler ran, in the one attention
    layer alone, over the kernel's own device seconds; None without the
    kernel's name or on another model."""
    from benchmarks import manifest
    from benchmarks.readers import paged_hybrid_roofline_pct as reader
    cell = manifest.Cell(TINY, manifest.load(TINY), "tiny.serve_long")
    cfg = cell.config
    kv = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    c = {"cfg": cfg, "traffic": dict(cell.traffic, cache_dtype="bfloat16"),
         "peaks": {"hbm_bytes_per_s": 1e6},
         "traced_token_ranges": [(0, 40), (40, 42), (10, 11)],
         "trace": {"op_seconds": {
             "%veles_paged_decode.2 custom-call (bf16[4])": 0.5,
             "%fusion.1 fusion (f32[4])": 9.0}}}
    keys = 41 + 42 + 11
    assert reader.read(c) == pytest.approx(100.0 * keys * kv / 0.5 / 1e6)
    c["trace"]["op_seconds"] = {"%fusion.1 fusion (f32[4])": 9.0}
    assert reader.read(c) is None
    c["trace"]["op_seconds"] = {"%veles_paged_decode custom-call (x)": 1}
    assert reader.read(dict(c, cfg=dict(cfg, model_type="other"))) is None
