"""The readers that PR 26 added, each on a ``collected`` made by hand:
the kernels are found by instruction name and opcode, a known
``op_seconds`` gives a known percentage, and a program without the
names or the keys (the parent, the CPU rehearsal) gives None."""

import importlib.util
import json
import os

import pytest

from benchmarks import kernel_work, manifest, peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
V5E = peaks.PEAKS["TPU v5 lite"]


def reader(name):
    path = manifest.reader_path(name)
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def cell(name):
    return manifest.Cell(ROOT, manifest.load(ROOT), name)


def test_kernels_are_found_by_name_and_opcode_only():
    ops = {
        "%veles_flash_fwd.3 custom-call (bf16[192,1024,64], "
        "f32[192,1024,128])": 1.0,
        "%veles_flash_bwd_dq custom-call bf16[192,1024,64]": 2.0,
        "%veles_flash_bwd_dkv.7.1 custom-call (bf16[192,1024,64], "
        "bf16[192,1024,64])": 4.0,
        # a fusion whose name merely contains the words
        "%veles_flash_fwd_fusion.3 fusion bf16[192,1024,64]": 8.0,
        "%flash_add_fusion fusion f32[8]": 16.0,
        # the right name under another opcode, another kernel's name
        "%veles_flash_fwd.9 fusion bf16[8]": 32.0,
        "%veles_flash_fwdx.1 custom-call bf16[8]": 64.0,
        "%veles_paged_decode.24 custom-call bf16[32,16,16,128]": 128.0,
        "%f.33 custom-call bf16[192,1024,64]": 256.0,
        "while": 512.0,
    }
    assert kernel_work.kernel_seconds(ops, kernel_work.FLASH) == 7.0
    assert kernel_work.kernel_seconds(ops, kernel_work.PAGED) == 128.0
    assert kernel_work.kernel_seconds(
        {"%f.33 custom-call bf16[8]": 1.0, "%fusion.2 fusion f32[8]": 2.0},
        kernel_work.FLASH) is None
    assert kernel_work.kernel_seconds(
        {"%veles_paged_decode_q8.2 custom-call bf16[8]": 3.0},
        kernel_work.PAGED) == 3.0


def test_the_readers_names_are_the_programs():
    from veles_tpu.ops.pallas import flash, paged
    assert set(kernel_work.FLASH) == {n for n, _ in
                                      flash.KERNEL_NAMES.values()}
    assert set(kernel_work.PAGED) == {n for n, _ in
                                      paged.KERNEL_NAMES.values()}


@pytest.mark.parametrize("name,layer_flop,bound_ratio", [
    # PERF.md section 5's arithmetic by hand: 77.3 and 206 GFLOP a
    # layer-step
    ("gpt2s.train", 77.3e9, 1.59), ("cgpt13.train", 206.2e9, 3.19)])
def test_flash_least_time_is_the_flops_bound(name, layer_flop, bound_ratio):
    c = cell(name)
    least, bound = kernel_work.flash_least_seconds(c.config, c.traffic, V5E)
    assert bound == "flops"
    assert least == pytest.approx(
        c.config["n_layer"] * layer_flop / 197e12, rel=2e-3)
    b, t = c.traffic["batch"], c.traffic["seq"]
    moved = c.config["n_layer"] * 8 * b * t * c.config["n_embd"] * 2
    assert least / (moved / 819e9) == pytest.approx(bound_ratio, rel=5e-3)


def train_collected(name, kernel_share):
    """A traced training window of 5 s in which the three kernels ran
    ``kernel_share`` of the time, at a step of 160 ms."""
    c = cell(name)
    third = kernel_share * 5.0 / 3
    return {
        "cfg": c.config, "traffic": c.traffic, "peaks": V5E,
        "sweep_ms": [641.0, 640.0, 639.0], "steps_per_dispatch": 4,
        "trace": {"window_s": 5.0, "busy_s": 4.999, "op_seconds": {
            "%veles_flash_fwd.33 custom-call (bf16[192,1024,64], "
            "f32[192,1024,128])": third,
            "%veles_flash_bwd_dq.27 custom-call bf16[192,1024,64]": third,
            "%veles_flash_bwd_dkv.21 custom-call (bf16[192,1024,64], "
            "bf16[192,1024,64])": third,
            "%flash_like_fusion.1 fusion bf16[16,1024,768]": 1.0,
            "%fusion.12 fusion f32[16,1023,50257]": 2.5}}}


def test_flash_roofline_pct_of_a_known_window():
    read = reader("flash_roofline_pct")
    c = train_collected("gpt2s.train", 0.2)
    # 12 layers x 77.3 GFLOP / 197 TFLOP/s = 4.709 ms least; the
    # kernels take a fifth of a 160 ms step = 32 ms
    assert read(c) == pytest.approx(100 * 4.709e-3 / 32e-3, rel=2e-3)
    half = train_collected("gpt2s.train", 0.1)
    assert read(half) == pytest.approx(2 * read(c), rel=1e-9)


@pytest.mark.parametrize("spoil", [
    lambda c: c.pop("trace"),
    lambda c: c.pop("sweep_ms"),
    lambda c: c["trace"].update(op_seconds={
        "%f.33 custom-call bf16[192,1024,64]": 1.0,
        "%flash_fusion fusion bf16[8]": 1.0}),
], ids=["untraced", "no_sweeps", "unnamed_kernels"])
def test_flash_roofline_pct_is_none_with_nothing_to_read(spoil):
    c = train_collected("gpt2s.train", 0.2)
    spoil(c)
    assert reader("flash_roofline_pct")(c) is None


def serve_collected(kernel_share, engine):
    c = cell("cgpt13.serve_closed")
    # one prefill of 512 (left out), then 32 rows decoding 100 tokens
    # from position 1000: each attends its own context
    ranges = [(0, 512)] + [(1000, 1100)] * 32
    return {
        "cfg": c.config, "traffic": c.traffic, "peaks": V5E,
        "window_s": 20.0, "token_ranges": ranges, "engine": engine,
        "trace": {"window_s": 4.0, "busy_s": 3.8, "op_seconds": {
            "%veles_paged_decode.24 custom-call bf16[32,16,16,128]":
                kernel_share * 2.0,
            "%veles_paged_decode.26 custom-call bf16[32,16,16,128]":
                kernel_share * 2.0,
            "%paged_copy_fusion fusion bf16[1537,16,16,128]": 0.3}}}


ENGINE = {"slots": 32, "ticks_total": 130, "p50_tick_host_ms": 6.5,
          "p50_engine_host_ms": 1.25, "tick_rows_mean": 31.2}


def test_paged_roofline_pct_of_a_known_window():
    c = serve_collected(0.8, ENGINE)
    # positions attended: 32 rows x sum(1001..1100) = 32 x 105,050;
    # 2 x 12 layers x 2048 x 2 B = 98,304 B a token
    need = 32 * 105050 * 98304
    assert kernel_work.decode_kv_bytes(
        c["cfg"], c["token_ranges"], 2) == need
    expected = 100 * (need / 20.0 / 819e9) / 0.8
    assert reader("paged_roofline_pct")(c) == pytest.approx(expected,
                                                            rel=1e-9)
    assert 0 < expected < 100


@pytest.mark.parametrize("spoil", [
    lambda c: c.pop("trace"),
    lambda c: c.update(token_ranges=[]),
    lambda c: c["trace"].update(op_seconds={
        "%closed_call.24 custom-call bf16[32,16,16,128]": 3.0}),
], ids=["untraced", "no_tokens", "unnamed_kernel"])
def test_paged_roofline_pct_is_none_with_nothing_to_read(spoil):
    c = serve_collected(0.8, ENGINE)
    spoil(c)
    assert reader("paged_roofline_pct")(c) is None


def test_the_ticks_metrics_read_the_engines_keys():
    c = serve_collected(0.8, ENGINE)
    assert reader("tick_host_ms_p50")(c) == 6.5
    assert reader("engine_host_ms_p50")(c) == 1.25
    assert reader("slot_occupancy_pct")(c) == pytest.approx(97.5)


@pytest.mark.parametrize("engine", [
    None, {},
    # the parent's engine.metrics(): no tick ring
    {"slots": 32, "served": 27, "p50_decode_stall_ms": 156.0},
    # the keys, and no tick yet
    dict(ENGINE, ticks_total=0),
], ids=["none", "empty", "parent", "no_ticks"])
@pytest.mark.parametrize("metric", ["tick_host_ms_p50",
                                    "engine_host_ms_p50",
                                    "slot_occupancy_pct"])
def test_the_ticks_metrics_are_none_without_the_keys(metric, engine):
    assert reader(metric)(serve_collected(0.8, engine)) is None


def test_the_new_metrics_are_appended_and_resolve():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    names = [x["name"] for x in m["per_layer"]]
    new = ["flash_roofline_pct", "paged_roofline_pct", "tick_host_ms_p50",
           "engine_host_ms_p50", "slot_occupancy_pct"]
    assert names[-5:] == new
    assert cell("gpt2s.train").per_layer[-1] == "flash_roofline_pct"
    assert cell("cgpt13.serve_closed").per_layer[-4:] == new[1:]
    for x in m["per_layer"][-5:]:
        assert x["unit"] in ("%", "ms") and "workloads" in x
