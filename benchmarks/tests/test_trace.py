"""``benchmarks/trace.py`` on the small trace recorded on the v5e by
``record_trace.py`` (five bursts of four jitted 2048 x 2048 bf16
matmuls under a ``bench.burst`` span, 20 ms of sleep under
``bench.sleep`` between bursts), and on intervals made by hand."""

import os

import pytest

from benchmarks import trace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "tiny_tpu.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(RECORDED)


def test_busy_union_and_idle_share(reduced):
    # 20 matmuls of about 0.1 ms; four sleeps of 20 ms inside the window
    assert reduced["busy_s"] == pytest.approx(2.04e-3, rel=0.02)
    assert 0.080 < reduced["window_s"] < 0.100
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert 0.97 < idle < 0.98


def test_device_time_by_name_is_self_time_and_adds_up_to_busy(reduced):
    ops = reduced["op_seconds"]
    assert sum(ops.values()) == pytest.approx(reduced["busy_s"], rel=1e-6)
    name, seconds = reduced["device_ops"][0]
    assert name == "%convolution_multiply_fusion fusion bf16[2048,2048]"
    assert seconds == pytest.approx(1.82e-3, rel=0.02)
    assert len(reduced["device_ops"]) <= 10
    assert reduced["opcode_seconds"]["fusion"] == pytest.approx(
        seconds, rel=1e-6)


def test_gaps_go_to_the_host_span_that_covers_them(reduced):
    gaps = dict(reduced["idle_gaps"])
    # the four sleeps between the five bursts, and the wake-ups after
    assert 0.080 < gaps["bench.sleep"] < 0.095
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert gaps["bench.sleep"] > 50 * gaps.get("(no span)", 0.0)


def test_merge_unions_nested_and_overlapping_intervals():
    assert trace.merge([(5, 6), (0, 10), (2, 3), (9, 12), (20, 21)]) == \
        [[0, 12], [20, 21]]


def test_self_time_takes_the_nested_operations_out_of_their_parent():
    events = [("while", 0, 100), ("a", 10, 30), ("b", 30, 50),
              ("a", 60, 70), ("inner", 62, 65), ("c", 200, 210)]
    assert trace.self_seconds(events) == {
        "while": 50.0, "a": 27.0, "b": 20.0, "inner": 3.0, "c": 10.0}


def test_enclosing_spans_prefers_the_span_that_started_last():
    spans = sorted([(0, 100, "outer"), (10, 20, "inner"), (50, 60, "x")])
    assert trace.enclosing_spans(spans, [5, 15, 25, 55, 150]) == \
        ["outer", "inner", "outer", "x", "(no span)"]


def test_short_name_keeps_the_instruction_and_its_opcode():
    assert trace.short_name(
        "%f.23 = (bf16[192,1024,64]{2,1,0:T(8,128)(2,1)}, bf16[2]{0}) "
        "custom-call(bf16[192,1024,64]{2,1,0} %bitcast.1), "
        "custom_call_target=\"tpu_custom_call\"") == \
        "%f.23 custom-call (bf16[192,1024,64], bf16[2])"
    assert trace.short_name(
        "%fusion.7 = f32[8,128]{1,0:T(8,128)} fusion(f32[8]{0} %p), "
        "kind=kLoop") == "%fusion.7 fusion f32[8,128]"
    assert trace.short_name("jit_train_sweep(123)") == "jit_train_sweep(123)"
