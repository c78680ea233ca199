"""The block that command-a-plus-05-2026 needs, as configuration of the
one ``TransformerBlock`` — a parallel residual path, a LayerNorm
without shift, a sigmoid top-k router over a SHARE of the experts, shared
experts averaged, sliding-window (rope) and full (no positional
encoding) layers in one stack — against the plain reference
``benchmarks/reference_cmda.py`` at a tiny size on the CPU, seeded
weights; and the paged pool's two groups of state (whole context, and
a ring of blocks a window layer), the window decode kernel and the
blockwise pass attention against their ground truths.

Tolerances: the model is built with float32 parameters that are exact
copies of the bfloat16-representable seeded values and computes in
float32 (``precision_level`` 1), as the reference does; what is left is
the order of float32 sums (online softmax, blocks of keys, experts
sorted by tile), under 1e-5 on logits of magnitude 0.1 — ``atol=3e-5``
on logits; a ``logit_gap`` reads 0.0 wherever no served token's
reference logit lies under the reference's best by more than that."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import build_cmda, reference_cmda  # noqa: E402
from benchmarks.reference import seed_key  # noqa: E402
from veles_tpu.models.generate import (  # noqa: E402
    ContinuousBatcher, LMGenerator, PagedContinuousBatcher)
from veles_tpu.ops import attention, moe  # noqa: E402
from veles_tpu.ops.attention import QuantCache, quantize_kv  # noqa: E402
from veles_tpu.ops.pallas import dsa, paged  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "tests", "data", "tiny_cmda",
                       "benchmarks", "configs", "tiny-cmda.json")) as _f:
    CFG = json.load(_f)
WINDOW = CFG["sliding_window"]              # 8
SEED = 2 ** 31 + 7
MAX_LEN = 96
#: (layers, experts held): one period and two; this chip's share, all
SHAPES = {"one_period": (4, [0, 2]), "two_periods": (8, [0, 2]),
          "all_held": (4, [0, 8]), "second_share": (4, [2, 2])}


def _cfg(shape):
    layers, held = SHAPES[shape]
    return dict(CFG, num_hidden_layers=layers, experts_held=held,
                num_experts=held[1])


def _build(cfg):
    from veles_tpu.config import root
    prev = root.common.engine.get("precision_level", 0)
    root.common.engine.precision_level = 1
    try:
        wf = build_cmda.build_workflow(cfg, MAX_LEN, param="float32")
    finally:
        root.common.engine.precision_level = prev
    build_cmda.install_weights(wf.trainer, cfg, SEED)
    return wf, LMGenerator(wf.trainer, max_len=MAX_LEN)


_MODELS = {}


@pytest.fixture
def model(request):
    """The tiny model of a shape of ``SHAPES`` in float32 compute, its
    configuration and its generator (built once a shape)."""
    shape = getattr(request, "param", "one_period")
    if shape not in _MODELS:
        cfg = _cfg(shape)
        _MODELS[shape] = (cfg,) + _build(cfg)
    return _MODELS[shape]


@pytest.fixture
def key_blocks_of_8(monkeypatch):
    monkeypatch.setattr(attention, "DSA_KEY_BLOCK", 8)


def _prompt(n, stream=0):
    return np.random.default_rng([5, stream]).integers(
        0, CFG["vocab_size"], n).tolist()


def _gap(cfg, prompt, result):
    return reference_cmda.logit_gaps(
        cfg, SEED, [{"prompt": prompt, "result": result}])


# ------------------------------------------------------------ the stack
def test_the_stack_is_the_configurations(model):
    cfg, wf, gen = model
    assert [(layer.window, bool(layer.cfg["rope"]))
            for layer in gen._blocks] == [(8, True)] * 3 + [(None, False)]
    for layer in gen._blocks:
        assert layer.parallel_block and layer.norm_kind == "layer_nobias"
        assert layer.norm_eps == 1e-5 and layer.router == \
            "sigmoid_topk_renorm"
        p = gen.params[layer.name]
        assert sorted(p) == ["ln1", "mha", "moe", "shared"]
        assert sorted(p["ln1"]) == ["gamma"]
        assert sorted(p["mha"]) == ["wk", "wo", "wq", "wv"]
        assert p["moe"]["router"].shape == (64, 8)
        assert p["moe"]["w_gate"].shape == (2, 64, 32)
        assert p["shared"]["w_down"].shape == (2 * 32, 64)
    assert gen._ring_spans() == (8,)
    assert [gen._ring_of(layer) for layer in gen._blocks] == [0, 0, 0, None]


@pytest.mark.parametrize("model", sorted(SHAPES), indirect=True)
def test_the_full_forward_is_the_references(model):
    cfg, wf, _ = model
    prompt = _prompt(70)
    toks = np.zeros((1, MAX_LEN), np.int32)
    toks[0, :70] = prompt
    full = np.asarray(jax.jit(wf.trainer._forward, static_argnums=(2,))(
        wf.trainer.params, jnp.asarray(toks), False, jax.random.key(0)),
        np.float32)[0, :70]
    ref = reference_cmda.forward_logits(cfg, SEED, [prompt],
                                        [list(range(70))])[0]
    assert float(jnp.abs(ref).max()) > 0.05
    np.testing.assert_allclose(full, np.asarray(ref), atol=3e-5)


def test_each_control_parts_from_the_reference(model):
    """A control that read 0 would guard nothing: every one of them
    moves the logits of a sequence past the window."""
    cfg, _, _ = model
    prompt = _prompt(40)
    where = [list(range(20, 40))]
    ref = reference_cmda.forward_logits(cfg, SEED, [prompt], where)[0]
    for control in reference_cmda.CONTROL_KEYS:
        other = reference_cmda.forward_logits(cfg, SEED, [prompt], where,
                                              **{control: True})[0]
        assert float(jnp.abs(other - ref).max()) > 1e-3, control
    low = reference_cmda.forward_logits(cfg, SEED, [prompt], where,
                                        precision="int8")[0]
    assert float(jnp.abs(low - ref).max()) > 1e-4


# ------------------------------------------- prefill, then paged decode
@pytest.mark.parametrize("model", ["one_period", "two_periods"],
                         indirect=True)
@pytest.mark.parametrize("segment", [0, 8, 16])
def test_prefill_then_paged_decode_is_the_references_forward(
        model, segment, key_blocks_of_8):
    """Whole (a prompt that fits the ring) and staged in passes, then
    decode through the ring to positions past 3 x (window + segment):
    every served token is the reference's first choice, in logits."""
    cfg, _, gen = model
    cb = PagedContinuousBatcher(gen, slots=2, block=4, pool_tokens=256,
                                prefill_segment=segment)
    seg = segment or WINDOW
    ring = -(-(WINDOW + seg) // 4) + 1
    assert cb.ring_blocks == (ring,) and cb.prefill_segment == seg
    long, short = _prompt(50, 1), _prompt(9, 2)
    assert cb._will_segment(50) and not cb._will_segment(9)
    rids = [cb.submit(long, 40), cb.submit(short, 60)]
    most = 0
    while not cb.idle():
        cb.tick()
        most = max(most, cb.blocks_in_use()[1])
        for held in cb._slot_ring_blocks.values():
            assert all(len(ids) <= ring for ids in held)
    assert 3 * (WINDOW + seg) < 90
    for rid, prompt in zip(rids, (long, short)):
        gap, n = _gap(cfg, prompt, cb.result(rid))
        assert gap == 0.0 and n == len(cb.result(rid)) - len(prompt)
    # a request longer than the ring held the whole ring, no more; a
    # released slot returned its blocks, both groups'
    assert most == 2 * ring
    assert cb.blocks_in_use() == (0, 0)
    assert sorted(cb._ring_free[0]) == list(range(1, 1 + 2 * ring))


def test_the_paged_streams_are_the_dense_batchers(model):
    """Three requests through the paged pool (ring of blocks) and
    through the dense batcher (rolling caches of ``window`` slots): the
    same token streams."""
    _, _, gen = model

    def streams(cb):
        rids = [cb.submit(_prompt(n, n), m)
                for n, m in ((30, 50), (5, 40), (17, 20))]
        cb.run_all()
        return [cb.result(r) for r in rids]

    paged_cb = PagedContinuousBatcher(gen, slots=2, block=4,
                                      pool_tokens=192, prefill_segment=8)
    assert streams(paged_cb) == streams(ContinuousBatcher(gen, slots=2))


def test_a_short_request_claims_its_own_blocks_only(model):
    _, _, gen = model
    cb = PagedContinuousBatcher(gen, slots=2, block=4, pool_tokens=256,
                                prefill_segment=8)
    cb.submit(_prompt(5), 4)                 # 9 positions: 3 blocks
    cb.tick()
    assert cb.blocks_in_use() == (3, 3)
    cb.run_all()
    assert cb.blocks_in_use() == (0, 0)
    cb.submit(_prompt(20), 30)
    cb.reset_pool()
    assert cb.blocks_in_use() == (0, 0) and cb.idle()


def test_prefix_sharing_over_a_ring_is_refused(model):
    _, _, gen = model
    with pytest.raises(ValueError, match="prefix_cache cannot serve"):
        PagedContinuousBatcher(gen, slots=2, block=4, prefix_cache=True)


def test_the_counters_read_what_ran(model):
    """``win_keys``: the keys the window layers' softmax ranged over;
    ``kv_tokens``: the full layer's; ``expert_pairs``: pairs on the two
    held experts of eight; the gauges: both groups' blocks."""
    cfg, _, gen = model
    cb = PagedContinuousBatcher(gen, slots=2, block=4, pool_tokens=256,
                                prefill_segment=8)
    rid = cb.submit(_prompt(30), 30)
    ticks = []
    while not cb.idle():
        cb.tick()
        ticks.append((cb.last_tick, cb.blocks_in_use()))
    assert cb.result(rid) is not None
    decoding = [(t, b) for t, b in ticks if t["rows"] == 1]
    assert decoding
    for t, _ in decoding:
        assert t["win_keys"] == min(t["kv_tokens"], WINDOW)
        # mean over four layers: three windows and the whole context
        assert t["sel_keys"] == pytest.approx(
            (3 * t["win_keys"] + t["kv_tokens"]) / 4)
        # two rows route (the idle slot's too), two pairs each, a
        # quarter of the experts held
        assert 0 <= t["expert_pairs"] <= 4
        assert 0 <= t["experts_touched"] <= 2
    assert any(t["expert_pairs"] > 0 for t, _ in decoding)
    staged = [t for t, _ in ticks if t["staged_tokens"]]
    assert staged and all(
        0 <= t["staged_expert_pairs"] <= 2 * t["staged_tokens"]
        for t in staged)
    assert max(b[0] for _, b in ticks) == 15        # 60 positions
    assert max(b[1] for _, b in ticks) == 5         # the ring
    # and the engine hands them on (its own thread ticks)
    import time
    from veles_tpu.services.restful import ContinuousEngine
    eng = ContinuousEngine(gen, slots=2, paged_block=4, pool_tokens=256,
                           prefill_segment=8)
    try:
        ContinuousEngine.wait(eng.submit_async(_prompt(30), 12))
        deadline = time.monotonic() + 30
        while not eng.cb.idle() and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.1)
        metrics, ring = eng.metrics(), eng.tick_records()
    finally:
        eng.stop()
    assert metrics["pool_blocks_full_in_use"] == 0
    assert metrics["pool_blocks_window_in_use"] == 0
    assert metrics["p50_tick_win_keys"] > 0
    assert "p50_tick_expert_pairs" in metrics
    assert 0 < metrics["staged_expert_pairs_per_token"] <= 2
    assert all("win_keys" in t and "staged_expert_pairs" in t for t in ring)


# ------------------------------------------------------------- routing
def _moe_params(n_experts, n_held=None, d=16, f=12, seed=3):
    rng = np.random.default_rng(seed)
    n_held = n_experts if n_held is None else n_held

    def w(*shape):
        return jnp.asarray(rng.normal(0, 0.3, shape), jnp.float32)

    return {"router": w(d, n_experts), "w_gate": w(n_held, d, f),
            "w_up": w(n_held, d, f), "w_down": w(n_held, f, d)}


def _loop_moe(params, x, top_k, first=0):
    """Token by token: sigmoid scores, the top_k (ties to the lower
    id) over their sum, the held experts' outputs weighted."""
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    n_held = p["w_gate"].shape[0]
    out, pairs = np.zeros_like(x, np.float64), 0
    for t, h in enumerate(np.asarray(x, np.float64)):
        s = 1.0 / (1.0 + np.exp(-(h @ p["router"])))
        top = np.argsort(-s, kind="stable")[:top_k]
        for e in top:
            if first <= e < first + n_held:
                g = h @ p["w_gate"][e - first]
                u = h @ p["w_up"][e - first]
                out[t] += s[e] / s[top].sum() * (
                    (g / (1.0 + np.exp(-g)) * u) @ p["w_down"][e - first])
                pairs += 1
    return out, pairs


def test_sigmoid_routing_is_the_per_token_loop():
    params = _moe_params(8)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(3, 11, 16)),
                    jnp.float32)
    # two experts tied for a token: the lower id is taken
    params["router"] = params["router"].at[:, 5].set(params["router"][:, 2])
    y, _ = jax.jit(lambda p, a: moe.moe_dropless_forward(
        p, a, top_k=3, router="sigmoid_topk_renorm"))(params, x)
    want, _ = _loop_moe(params, np.asarray(x).reshape(-1, 16), 3)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 16), want,
                               rtol=2e-4, atol=2e-5)
    gates, experts = moe.route_sigmoid_topk(x.reshape(-1, 16),
                                            params["router"], 3)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-6)
    e = np.asarray(experts)
    assert not ((e == 5).any(-1) & ~(e == 2).any(-1)).any()


@pytest.mark.parametrize("case", ["mean", "all_to_held", "none_to_held"])
def test_a_share_of_the_experts_loses_no_pair(case):
    """16 held of 128, top-8: the buffers are sized for twice the
    share's mean, and a call in which every token chooses held experts
    runs at the size of all pairs — dropless stays dropless."""
    whole = _moe_params(128, seed=6)
    held = {k: (v if k == "router" else v[:16]) for k, v in whole.items()}
    x = jnp.asarray(np.random.default_rng(7).normal(size=(2, 40, 16)),
                    jnp.float32)
    if case != "mean":
        # the logits of the absent (the held) experts far below the
        # others': no score saturates, so no two tie
        x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 0.5)
        low = slice(16, None) if case == "all_to_held" else slice(0, 16)
        held["router"] = held["router"].at[0, low].add(-40.0)
    m = 80 * 8
    assert moe.dropless_pair_bound(m, 16, 128) == 2 * 80 + 8 * 16 < m
    assert moe.dropless_pair_bound(m, 128, 128) == m
    assert moe.dropless_tile(moe.dropless_pair_bound(m, 16, 128), 16) == 32
    def forward(p, a):
        counts = {}
        y, touched = moe.moe_dropless_forward(
            p, a, top_k=8, router="sigmoid_topk_renorm", counts=counts)
        return y, touched, counts["expert_pairs"]

    y, touched, got = jax.jit(forward)(held, x)
    want, pairs = _loop_moe(held, np.asarray(x).reshape(-1, 16), 8)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 16), want,
                               rtol=2e-4, atol=2e-5)
    assert int(got) == pairs
    assert pairs == {"all_to_held": m, "none_to_held": 0}.get(case, pairs)
    if case == "all_to_held":
        assert int(touched) == 16
        assert (np.abs(np.asarray(y)).sum(-1) > 0).all()


def test_the_shares_and_the_shared_experts_once_are_the_uncut_layer(model):
    """model-configs guide, section 4: the parts of the FFN that the
    four shares of 2 experts give, with what every share computes alike
    (the two shared experts' average) counted once, add up to what the
    reference gives for the whole layer (all 8 experts held)."""
    cfg, _, gen = model
    key = seed_key(SEED)
    h = jnp.asarray(np.random.default_rng(9).normal(size=(1, 32, 64)),
                    jnp.float32)
    uncut = dict(cfg, experts_held=[0, 8])
    lw = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        reference_cmda.layer_weights(uncut, key, 1))
    want = reference_cmda.make_ffn(uncut, 32)(h[0], lw)
    layer = gen._blocks[1]
    parts, shared, pairs = 0.0, None, 0
    for share in range(4):
        sw = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32),
            reference_cmda.layer_weights(
                dict(cfg, experts_held=[2 * share, 2]), key, 1))
        counts = {}
        routed, _ = moe.moe_dropless_forward(
            sw["moe"], h, top_k=2, first=2 * share,
            router="sigmoid_topk_renorm", counts=counts)
        pairs += int(counts["expert_pairs"])
        parts = parts + routed
        mine = moe.shared_experts_forward(sw["shared"], h,
                                          layer.shared_scale)
        if shared is not None:
            np.testing.assert_array_equal(np.asarray(mine),
                                          np.asarray(shared))
        shared = mine
    assert pairs == 32 * 2                   # every pair on some share
    np.testing.assert_allclose(np.asarray(parts + shared)[0],
                               np.asarray(want), atol=2e-6)
    # and the block's own FFN is its share's part plus the shared ones
    own, _ = layer._ffn(jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        reference_cmda.layer_weights(cfg, key, 1)), h, train=False)
    np.testing.assert_allclose(
        np.asarray(own)[0], np.asarray(reference_cmda.make_ffn(cfg, 32)(
            h[0], jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32),
                reference_cmda.layer_weights(cfg, key, 1)))), atol=2e-6)


# ------------------------------------------------ the window decode kernel
def _ring_pool(rng, b, hkv, bs, hd, ring, pos, dtype):
    """A pool whose rows' rings hold positions 0 .. pos[b] as the
    batcher writes them (entry ``(p // bs) mod ring``), and the dense
    K, V a row really has."""
    t = int(max(pos)) + 1
    k = rng.normal(size=(b, hkv, t, hd)).astype(np.float32)
    v = rng.normal(size=(b, hkv, t, hd)).astype(np.float32)
    pool_k = np.zeros((1 + b * ring, hkv, bs, hd), np.float32)
    pool_v = np.zeros_like(pool_k)
    table = np.zeros((b, ring), np.int32)
    for row in range(b):
        table[row] = 1 + row * ring + np.random.default_rng(row) \
            .permutation(ring)
        for p in range(int(pos[row]) + 1):
            blk = table[row, (p // bs) % ring]
            pool_k[blk, :, p % bs] = k[row, :, p]
            pool_v[blk, :, p % bs] = v[row, :, p]
    if dtype == "int8":
        qk, qv = (QuantCache(*quantize_kv(jnp.asarray(a)))
                  for a in (pool_k, pool_v))
        return qk, qv, jnp.asarray(table), k, v
    return (jnp.asarray(pool_k, dtype), jnp.asarray(pool_v, dtype),
            jnp.asarray(table), k, v)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("hd", [128, 64])
def test_the_window_kernel_is_the_gather_reference(hd, dtype):
    """First live positions aligned to a page and not, rows inside the
    window and far past it (rings wrapped several times), a group of
    two query heads: kernel (interpret mode) == reference == the plain
    softmax over the row's last ``window`` keys."""
    b, hkv, g, bs, window = 5, 2, 2, 4, 10
    ring = -(-(window + 8) // bs) + 1                # 6 pages
    pos = np.asarray([3, 9, 12, 40, 87], np.int32)   # first: 0 0 3 31 78
    rng = np.random.default_rng(hd)
    pool_k, pool_v, table, k, v = _ring_pool(rng, b, hkv, bs, hd, ring,
                                             pos, dtype)
    q = jnp.asarray(rng.normal(size=(b, hkv * g, hd)), jnp.float32)
    if dtype == "bfloat16":
        q = q.astype(jnp.bfloat16)
    ref = paged.paged_attention_reference(q, pool_k, pool_v, table,
                                          jnp.asarray(pos), window=window)
    got = paged.paged_attention_decode(q, pool_k, pool_v, table,
                                       jnp.asarray(pos), window=window)
    tol = {"float32": 2e-6, "bfloat16": 2e-2, "int8": 2e-6}[dtype]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=tol)
    if dtype != "float32":
        return
    for row in range(b):
        lo = max(0, int(pos[row]) - window + 1)
        kk = np.repeat(k[row, :, lo:pos[row] + 1], g, axis=0)
        vv = np.repeat(v[row, :, lo:pos[row] + 1], g, axis=0)
        s = np.einsum("hd,htd->ht", np.asarray(q[row]), kk) * hd ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("ht,htd->hd", p / p.sum(-1, keepdims=True), vv)
        np.testing.assert_allclose(np.asarray(got[row]), want, atol=1e-5)


def test_a_ring_too_short_for_its_window_is_refused():
    q = jnp.zeros((1, 2, 128))
    pool = jnp.zeros((5, 2, 4, 128))
    with pytest.raises(ValueError, match="cannot hold a window"):
        paged.paged_attention_decode(q, pool, pool, jnp.zeros((1, 3),
                                                              jnp.int32),
                                     jnp.zeros((1,), jnp.int32), window=10)


# ---------------------------------------------------- the pass attention
@pytest.mark.parametrize("kind", ["full", "window_linear", "window_ring",
                                  "window_ring_int8"])
@pytest.mark.parametrize("start", [0, 5, 19, 41])
def test_the_pass_attention_is_the_plain_masked_softmax(kind, start,
                                                        key_blocks_of_8):
    """``chunk_attend`` over key blocks against ``attention(...,
    window=)`` over the sequence so far, at starts inside and past the
    window: a full row, a window layer's linear row and its ring
    (20 slots, not a multiple of the key block: padded), int8 too."""
    h, hkv, hd, kk, window = 4, 2, 16, 8, 12
    rng = np.random.default_rng(start)
    total = start + kk
    q = jnp.asarray(rng.normal(size=(1, h, kk, hd)), jnp.float32)
    k = rng.normal(size=(1, hkv, total, hd)).astype(np.float32)
    v = rng.normal(size=(1, hkv, total, hd)).astype(np.float32)
    win = None if kind == "full" else window
    t_cache = 20 if kind.startswith("window_ring") else 56
    row_k = np.full((1, hkv, t_cache, hd), 1e4, np.float32)
    row_v = np.full_like(row_k, 1e4)          # what is never attended
    for p in range(total):
        row_k[:, :, p % t_cache] = k[:, :, p]
        row_v[:, :, p % t_cache] = v[:, :, p]
    cache_k, cache_v = jnp.asarray(row_k), jnp.asarray(row_v)
    if kind.endswith("int8"):
        qk, qv = quantize_kv(cache_k), quantize_kv(cache_v)
        cache_k, cache_v = QuantCache(*qk), QuantCache(*qv)
        deq = attention.dequantize_kv
        for p in range(total):                # the quantized view
            k[:, :, p] = np.asarray(deq(cache_k))[:, :, p % t_cache]
            v[:, :, p] = np.asarray(deq(cache_v))[:, :, p % t_cache]
    got = jax.jit(lambda *a: attention.chunk_attend(*a, window=win))(
        q, cache_k, cache_v, jnp.int32(start))
    kr, vr = (jnp.repeat(jnp.asarray(a), h // hkv, axis=1) for a in (k, v))
    want = attention.attention(q, kr, vr, causal=True, window=win)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5 if "int8" not in kind else 2e-4)


def test_the_pass_attention_takes_the_kernel_where_its_tiles_fit(
        monkeypatch):
    """At head dim 128 and a row of whole key blocks the masked
    attention of a pass runs in ``veles_dsa_prefill`` (interpret mode
    here), a wrapped ring included, and equals the XLA loop."""
    monkeypatch.setattr(attention, "DSA_KEY_BLOCK", 128)
    h, hkv, hd, kk, window, t_cache = 4, 2, 128, 32, 100, 256
    rng = np.random.default_rng(3)

    def a(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    q, ck, cv = a(1, h, kk, hd), a(1, hkv, t_cache, hd), a(1, hkv, t_cache,
                                                          hd)
    for start, win in ((40, None), (40, window), (300, window)):
        assert attention.dsa_prefill_tiles(kk, t_cache, hd)
        got = attention.chunk_attend(q, ck, cv, jnp.int32(start), win)
        text = str(jax.make_jaxpr(lambda s: attention.chunk_attend(
            q, ck, cv, s, win))(jnp.int32(start)))
        assert dsa.KERNEL_NAMES["prefill"][0] in text
        monkeypatch.setattr(attention, "dsa_prefill_tiles",
                            lambda *a: None)
        want = attention.chunk_attend(q, ck, cv, jnp.int32(start), win)
        monkeypatch.undo()
        monkeypatch.setattr(attention, "DSA_KEY_BLOCK", 128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)


def test_no_score_tensor_over_the_row(model, key_blocks_of_8):
    """A staged pass of a model without an indexer holds no array as
    wide as queries x the cache row: its widest score block is queries
    x one key block."""
    _, _, gen = model
    caches = jax.eval_shape(lambda: gen._init_caches(1, jnp.float32,
                                                     (24,)))
    jaxpr = jax.make_jaxpr(gen._prefill_resume_fn(8))(
        gen.params, caches, jnp.zeros((1, 8), jnp.int32), jnp.int32(0))

    def widest(jp, found):
        for eqn in jp.eqns:
            for var in eqn.outvars:
                shape = getattr(var.aval, "shape", ())
                if len(shape) >= 2 and shape[-1] in (24, MAX_LEN) \
                        and shape[-2] >= 8 and "float" in str(
                            var.aval.dtype):
                    found.append(shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                widest(sub, found)
        return found

    # rows of the cache themselves are [.., T, head_dim]: never [.., T]
    assert widest(jaxpr.jaxpr, []) == []
