"""``kernel_work_keye``: the sparse decode path's least bytes from
shapes, the conditional it runs under found by opcode and result type,
its whole span summed from a profile, and the reader over them."""

import json
import os
import types

from benchmarks import kernel_work_keye as kw
from benchmarks.readers import paged_sparse_roofline_pct as reader

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "configs",
                       "keye-vl-2.0-30b-a3b.json")) as _f:
    CFG = json.load(_f)
TF = {"slots": 8, "cache_dtype": "bfloat16"}

#: as the device trace of the tick names them (compiled for a v5e)
SPARSE = ("%conditional.8 = (bf16[8,32,128]{2,1,0:T(8,128)(2,1)}, "
          "s32[8]{0:T(128)}) conditional(%c, %tuple.133, %tuple.508), "
          "branch_computations={%region_15.25, %region_16.55}")
DENSE = ("%conditional.7 = (bf16[8,32,128]{2,1,0:T(8,128)(2,1)}) "
         "conditional(%c, %tuple.127, %tuple.132), "
         "branch_computations={%region_1, %region_2}")
DRAW = ("%conditional.11 = (s32[8]{0:T(128)}) conditional(%c, %t.1, "
        "%t.2), branch_computations={%region_3, %region_4}")
FUSION = ("%fusion.3 = (f32[8,32,128]{2,1,0}, s32[8]{0}) fusion(%p), "
          "kind=kLoop, calls=%fused")


def test_the_least_bytes_count_index_keys_and_selected_kv():
    layers, topk = CFG["num_hidden_layers"], CFG["sa_config"]["topk"]
    # one token at position 9,999: 10,000 index keys of 64 and K and V
    # of 2,048 keys x 4 heads x 128, bfloat16, six layers
    one = layers * (10000 * 64 * 2 + topk * 2 * 4 * 128 * 2)
    assert kw.sparse_decode_bytes(CFG, [(9999, 10000)], 2) == one
    # a prefill is left out; so are positions under topk
    assert kw.sparse_decode_bytes(CFG, [(0, 30000)], 2) == 0
    assert kw.sparse_decode_bytes(CFG, [(100, topk)], 2) == 0
    assert kw.sparse_decode_bytes(CFG, [(topk - 5, topk + 1)], 2) \
        == kw.sparse_decode_bytes(CFG, [(topk, topk + 1)], 2)


def test_the_conditional_is_found_by_opcode_and_result_type():
    from benchmarks import trace
    wanted = kw.is_sparse_decode(CFG, TF)
    assert wanted(trace.short_name(SPARSE))
    assert wanted(trace.short_name(SPARSE.replace("bf16[", "f32[")))
    for other in (DENSE, DRAW, FUSION):
        assert not wanted(trace.short_name(other))
    assert not kw.is_sparse_decode(CFG, dict(TF, slots=4))(
        trace.short_name(SPARSE))


def _profile(events):
    ev = [types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
          for n, s, d in events]
    line = types.SimpleNamespace(name="XLA Ops", events=ev)
    other = types.SimpleNamespace(name="XLA Modules", events=ev)
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/device:TPU:0", lines=[other, line]),
        types.SimpleNamespace(name="/host:CPU", lines=[line])])


def test_the_whole_span_is_summed_nested_operations_included():
    prof = _profile([(SPARSE, 0, 2_000_000), (FUSION, 100, 1_500_000),
                     (DENSE, 3_000_000, 50_000),
                     (SPARSE, 5_000_000, 1_000_000)])
    got = kw.enclosed_seconds(prof, kw.is_sparse_decode(CFG, TF))
    assert got == {"seconds": 0.003, "events": 2}
    assert kw.enclosed_seconds(_profile([(DENSE, 0, 5)]),
                               kw.is_sparse_decode(CFG, TF)) is None


def test_the_reader_is_needed_share_over_busy_share():
    need = kw.sparse_decode_bytes(CFG, [(9999, 10000)], 2)
    c = {"cfg": CFG, "traffic": TF, "token_ranges": [(9999, 10000)],
         "window_s": 2.0, "peaks": {"hbm_bytes_per_s": 8e11},
         "trace": {"window_s": 4.0},
         "sparse_decode": {"seconds": 0.5, "events": 7}}
    want = 100.0 * (need / 2.0 / 8e11) / (0.5 / 4.0)
    assert abs(reader.read(c) - want) < 1e-12
    assert reader.read(dict(c, sparse_decode=None)) is None
    assert reader.read({"trace": None}) is None
