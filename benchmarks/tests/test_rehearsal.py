"""``run.py`` rehearsed on the CPU, once for each kind of traffic, at a
tiny size (d=64, 2 layers, T=64, Pallas in interpret mode) with the
look for a chip switched off — an argument this test owns.  Then the
same run with the timed path broken underneath: ``correct`` must come
out false for each fault a cell can have."""

import io
import json
import os
import subprocess
import sys

import pytest

from benchmarks import run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny")
ROOT = os.path.dirname(os.path.dirname(HERE))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def drive(cell, trace, seed=3000000019, seconds=2):
    out = io.StringIO()
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)],
                  require_tpu=False, root=TINY, out=out)
    assert rc == 0
    return json.loads(out.getvalue().splitlines()[-1])


@pytest.mark.parametrize("cell,trace,metrics", [
    ("tiny.train", 0, {"train_tokens_per_s", "setup_s"}),
    ("tiny.train", 1, {"loader_ms_p50", "step_ms_p50", "train_mfu",
                       "idle_pct.train"}),
    ("tiny.serve_closed", 0, {"out_tokens_per_s", "setup_s"}),
    ("tiny.serve_closed", 1, {"prefill_share_pct", "gap_ms_p50",
                              "gap_ms_p95", "serve_mfu",
                              "idle_pct.serve"}),
])
def test_last_line_has_the_contracts_keys(cell, trace, metrics):
    line = drive(cell, trace)
    assert KEYS <= set(line)
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == metrics
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert line["device"]["count"] == 1
    assert line["compared"]["compiles_in_window"]["value"] == 0
    phases = line["notes"]["phases_s"]
    assert "window" in phases and "reference" in phases
    assert line["metrics"].get("setup_s", {"value": 0})["value"] <= \
        sum(v for k, v in phases.items())
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10
    else:
        assert "breakdown" not in line


def test_same_seed_same_inputs():
    from benchmarks import build, traffic
    cfg = {"vocab_size": 512}
    a = build.token_rows(cfg, 4, 8, 2 ** 31 + 5)
    assert (a == build.token_rows(cfg, 4, 8, 2 ** 31 + 5)).all()
    assert (a != build.token_rows(cfg, 4, 8, 2 ** 31 + 6)).any()
    with open(os.path.join(TINY, "benchmarks", "traffic",
                           "closed_tiny.json")) as f:
        tf = json.load(f)
    s1, s2 = (traffic.request_stream(tf, 512, s) for s in (1, 2))
    first = [next(s1) for _ in range(tf["n_sizes"] + 3)]
    other = [next(s2) for _ in range(tf["n_sizes"] + 3)]
    # every seed: the same sizes in the same order (the order is the
    # work where requests outlast the window), other token contents
    sizes = traffic.request_sizes(tf)
    assert [(len(p), o) for p, o in first] == \
        [(len(p), o) for p, o in other] == sizes + sizes[:3]
    assert len({len(p) for p, _ in first[:8]}) > 1      # not sorted
    assert [p for p, _ in first] != [p for p, _ in other]


# ---------------------------------------------------------------- faults
def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    from veles_tpu.models import optimizer
    monkeypatch.setattr(optimizer, "update",
                        lambda params, grads, state, *a, **k:
                        (params, state))
    line = drive("tiny.train", 0)
    assert line["correct"] is False
    assert line["compared"]["change_gap"]["value"] >= 0.99


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    from veles_tpu.ops import losses
    real = losses.masked_seq_xent

    def half(logits, labels, valid):
        import jax.numpy as jnp
        keep = (jnp.arange(valid.shape[0]) < valid.shape[0] // 2)
        return real(logits, labels, valid * keep.astype(valid.dtype))

    monkeypatch.setattr(losses, "masked_seq_xent", half)
    line = drive("tiny.train", 0)
    assert line["correct"] is False


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    """The tick takes the argmax of the paged step's logits: rolled by
    one along the vocabulary, every served token is its neighbour."""
    import jax.numpy as jnp
    from veles_tpu.models import generate
    real = generate.LMGenerator._step_paged

    def altered(self, *a, **k):
        logits, pool = real(self, *a, **k)
        return jnp.roll(logits, 1, axis=-1), pool

    monkeypatch.setattr(generate.LMGenerator, "_step_paged", altered)
    line = drive("tiny.serve_closed", 0)
    assert line["correct"] is False
    c = line["compared"]["logit_gap"]
    assert c["value"] > c["limit"]


# --------------------------------------------------------------- control
def test_the_int8_control_reads_wider_than_the_program():
    """The control kept at a size a test run can hold: the reference in
    int8, in the program's place, reads at least three times what the
    program (bf16 operands) reads against the f32 reference, on the
    gradient the optimizer got."""
    from benchmarks import calibrate, manifest
    from benchmarks.kinds import train
    cell = manifest.Cell(TINY, manifest.load(TINY), "tiny.train")
    ctx = run.Context(cell, 11, 1.0, False, {}, {})
    got = calibrate.calibrate_train(ctx, controls=True)
    prog, control = got["program"][0], got["control_int8"][0]
    assert control["moment_gap"] > 3 * prog["moment_gap"]
    limits = cell.limits["limits"]
    assert not all(control[k] <= limits[k] for k in control)
    assert train.IN_FLIGHT >= 1


def test_the_serve_control_is_read_over_the_same_served_tokens():
    """The serving control at a size a test run can hold, each seed one
    whole run of the timed path: the tokens the int8 reference puts
    first, in the served tokens' place, at the same positions of the
    same answers.  At d=64 bfloat16 and a per-tensor int8 grid round
    alike and the two readings do not part (both under a thousandth of
    a logit); at the cell's own width the control reads 3.1 to 4.4
    logits against the program's 0.03 (the chip's readings, in
    benchmarks/limits/).  The sample holds what a cut answer had said."""
    import math
    from benchmarks import calibrate, manifest
    cell = manifest.Cell(TINY, manifest.load(TINY), "tiny.serve_closed")
    for seed in (21, 22):
        ctx = run.Context(cell, seed, 1.0, False, {}, {})
        numbers, notes = calibrate.calibrate_serve(ctx, True)["program"]
        assert notes["checked_tokens"] > 0 and numbers["malformed"] == 0
        assert notes["checked_answers"] == \
            cell.traffic["check_requests"] + 1
        assert numbers["logit_gap"] <= cell.limits["limits"]["logit_gap"]
        control = notes["control_int8_logit_gap"]
        assert math.isfinite(control) and control >= 0.0


def test_the_serving_footprint_is_the_weights_and_the_blocks_in_use():
    line = drive("tiny.serve_closed", 0)
    notes = line["notes"]
    assert 0 < notes["pool_blocks_in_use_max"] <= notes["pool_blocks"]
    assert line["device"]["memory_peak_bytes"] == notes["weights_bytes"] \
        + notes["pool_blocks_in_use_max"] * (
            notes["pool_bytes_reserved"] // notes["pool_blocks"])
    assert sum(notes["delivered_by_second"]) == pytest.approx(
        line["metrics"]["out_tokens_per_s"]["value"] * 2, rel=0.05)


# ------------------------------------------------------------- no device
def test_the_real_entry_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_a_device_without_a_row_in_the_peaks_table_is_an_error():
    from benchmarks import peaks
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
