"""The configured transformer block (RMSNorm, no biases, QK-norm, rope
base, dropless top-k experts, a sparse-attention indexer with its third
cache leaf) against the plain reference ``benchmarks/reference_keye.py``
at a tiny size on the CPU, seeded weights — and the proof that the
defaults still build the blocks the zoo always built."""

import hashlib
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

from benchmarks import build_keye, reference_keye  # noqa: E402
from veles_tpu import prng  # noqa: E402
from veles_tpu.loader.fullbatch import FullBatchLoader  # noqa: E402
from veles_tpu.models import generate, zoo  # noqa: E402
from veles_tpu.models.generate import (  # noqa: E402
    ContinuousBatcher, LMGenerator, PagedContinuousBatcher, SlotState)
from veles_tpu.models.standard_workflow import StandardWorkflow  # noqa: E402
from veles_tpu.ops import attention, moe  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "tests", "data", "tiny_keye",
                       "benchmarks", "configs", "tiny-keye.json")) as _f:
    CFG = json.load(_f)
TOPK = CFG["sa_config"]["topk"]             # 16
SEED = 2 ** 31 + 7
MAX_LEN = 64


@pytest.fixture(scope="module")
def model():
    """The tiny model in float32 compute (parameters float32 copies of
    the bfloat16-representable seeded values), and its generator."""
    from veles_tpu.config import root
    prev = root.common.engine.get("precision_level", 0)
    root.common.engine.precision_level = 1
    try:
        wf = build_keye.build_workflow(CFG, MAX_LEN, param="float32")
    finally:
        root.common.engine.precision_level = prev
    build_keye.install_weights(wf.trainer, CFG, SEED)
    return wf, LMGenerator(wf.trainer, max_len=MAX_LEN)


def _prompt(n, stream=0):
    return np.random.default_rng([5, stream]).integers(
        0, CFG["vocab_size"], n).tolist()


# ------------------------------------------------------- the old blocks
def _old_workflow(**kw):
    prng.seed_all(31)
    toks = ((np.arange(16)[None, :] * 2 + np.arange(8)[:, None]) % 13
            ).astype(np.int32)
    loader = FullBatchLoader(None, data=toks, labels=toks,
                             minibatch_size=4, class_lengths=[0, 4, 4])
    wf = StandardWorkflow(
        layers=zoo.transformer_lm(vocab_size=13, d_model=32, n_heads=4,
                                  n_layers=2, **kw),
        loader=loader, loss="lm", decision_config={"max_epochs": 1},
        name="digest-lm")
    wf.initialize()
    return wf, toks


#: recorded on the parent commit (fe763f2) by this same construction:
#: sha256 over every leaf's path, dtype and bytes; logits[0, :3, :4]
#: and sum(|logits|) of the first two rows' forward pass
OLD_BLOCKS = {
    "gpt2": (
        dict(pos="learned", tie_embeddings=True, impl="flash",
             solver="adamw", d_ff=128),
        "4e1275f9d78b49ee8159f4cdc73e38d176949b23d65286e99342ca8f76f0526c",
        36,
        [[0.8718289136886597, -1.2766587734222412, 0.09879732131958008,
          0.8065736293792725],
         [-0.22729456424713135, -1.457828164100647, 0.8825154304504395,
          1.170915961265564],
         [-1.1605618000030518, -1.4853122234344482, 0.3119027316570282,
          0.6512280702590942]], 345.88397216796875),
    "rope_gqa_moe": (
        dict(pos="rope", n_kv_heads=2, n_experts=4),
        "8cc11e37efd5d5576f4b4b1479067cff55004570f48f5302a5a6c2538e81f828",
        39,
        [[1.0289071798324585, 0.9448534250259399, -1.4099059104919434,
          0.0698540210723877],
         [1.0715088844299316, 1.2834001779556274, -0.8375140428543091,
          0.014671087265014648],
         [0.289899617433548, 1.3237806558609009, -0.5462585687637329,
          0.6944981217384338]], 192.74078369140625),
}


@pytest.mark.parametrize("name", sorted(OLD_BLOCKS))
def test_defaults_build_the_parent_commits_tree_bit_for_bit(name):
    kw, digest, n_leaves, corner, total = OLD_BLOCKS[name]
    wf, toks = _old_workflow(**kw)
    leaves = jax.tree_util.tree_flatten_with_path(wf.trainer.params)[0]
    h = hashlib.sha256()
    for path, a in leaves:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(np.asarray(a).tobytes())
    assert len(leaves) == n_leaves
    assert h.hexdigest() == digest
    out = np.asarray(jax.jit(wf.trainer._forward, static_argnums=(2,))(
        wf.trainer.params, jnp.asarray(toks[:2]), False,
        jax.random.key(0)), np.float32)
    # the same machine gives the same bits; another CPU may fuse
    # otherwise, so the recorded logits are held to rounding
    np.testing.assert_allclose(out[0, :3, :4], corner, rtol=2e-6,
                               atol=2e-6)
    np.testing.assert_allclose(np.abs(out).sum(), total, rtol=1e-5)


# ----------------------------------------------------------- selection
@pytest.mark.parametrize("topk", [1, 4, 16, 100])
def test_dsa_select_is_the_references_rule(topk):
    rng = np.random.default_rng(topk)
    scores = rng.normal(size=(2, 24, 40)).astype(np.float32)
    scores[:, :, 5:30:3] = 0.0                   # ties, and at zero
    scores[0, :, 7] = -0.0
    scores[1, 3] = 1.5                           # a whole row tied
    valid = np.tril(np.ones((24, 40), bool), 16)[None].repeat(2, 0)
    got = attention.dsa_select(
        jnp.where(jnp.asarray(scores) == 0.0, 0.0, jnp.asarray(scores)),
        jnp.asarray(valid), topk)
    for b in range(2):
        want = reference_keye.select_keys(jnp.asarray(scores[b]),
                                          jnp.asarray(valid[b]), topk)
        np.testing.assert_array_equal(np.asarray(got[b]),
                                      np.asarray(want))
    counts = np.asarray(got).sum(-1)
    np.testing.assert_array_equal(
        counts, np.minimum(valid.sum(-1), topk))


@pytest.mark.parametrize("n_sel", [4, 16])
def test_the_decode_positions_are_the_references_selection(n_sel):
    """The decode path's gather indices: ``dsa_select``'s mask turned
    into positions, in order, the slots past a short row's keys dead."""
    rng = np.random.default_rng(n_sel)
    scores = rng.normal(size=(3, 48)).astype(np.float32)
    scores[:, 2:40:5] = 0.0
    pos = np.asarray([47, 20, 2], np.int32)
    valid = np.arange(48)[None] <= pos[:, None]
    chosen = attention.dsa_select(jnp.asarray(scores), jnp.asarray(valid),
                                  n_sel)
    sel, live = attention.dsa_positions(chosen, n_sel)
    sel, live = np.asarray(sel), np.asarray(live)
    for b in range(3):
        want = np.asarray(reference_keye.select_keys(
            jnp.asarray(scores[b:b + 1]), jnp.asarray(valid[b:b + 1]),
            n_sel))[0]
        np.testing.assert_array_equal(sel[b][live[b]], np.nonzero(want)[0])
        assert live[b].sum() == min(pos[b] + 1, n_sel)


def _block_major(a, kb):
    """[b, tq, n * kb] -> [n, b, tq, kb]"""
    return jnp.moveaxis(a.reshape(a.shape[:-1] + (-1, kb)), -2, 0)


def _key_order(blocks):
    """[n, b, tq, kb] -> [b, tq, n * kb]"""
    blocks = np.asarray(blocks)
    return np.moveaxis(blocks, 0, 2).reshape(blocks.shape[1:3] + (-1,))


@pytest.fixture
def key_blocks_of_8(monkeypatch):
    monkeypatch.setattr(attention, "DSA_KEY_BLOCK", 8)


#: a cache row of 40 keys in blocks of 8, topk 6: the live width a
#: prefill pass bounds its selection by
LIVE_WIDTHS = {"under_topk": 5, "one_block": 8, "not_a_multiple": 21,
               "whole_row": 40}


@pytest.mark.parametrize("bits", [1, 2, 4])
@pytest.mark.parametrize("case", sorted(LIVE_WIDTHS))
def test_the_bounded_selection_is_the_whole_rows(case, bits, monkeypatch,
                                                 key_blocks_of_8):
    """``dsa_select_blocks`` over the live blocks of a block-major row
    against ``dsa_select`` over the whole row: the same mask, whatever
    lies past the live blocks, with the ties at the k-th value lying on
    both sides of a block boundary — at every number of bits a read
    settles."""
    monkeypatch.setattr(attention, "DSA_SELECT_BITS", bits)
    live, tk, kb, tq, topk = LIVE_WIDTHS[case], 40, 8, 6, 6
    rng = np.random.default_rng(live)
    scores = rng.uniform(-2, 2, size=(2, tq, tk)).astype(np.float32)
    # three keys above everything, then ties from position 6 to 18
    # (blocks 0, 1 and 2): a query that sees them all needs 3 of them
    scores[:, :, 6:19] = 2.5
    scores[:, :, [1, 9, 17]] = 3.0 + np.arange(3, dtype=np.float32)
    scores[1, 2, :] = -0.0                       # a whole row tied
    qpos = live - tq + np.arange(tq)             # the pass's queries
    valid = np.broadcast_to(np.arange(tk)[None] <= qpos[:, None],
                            scores.shape)
    scores, valid = jnp.asarray(scores), jnp.asarray(valid)
    scores = jnp.where(scores == 0.0, 0.0, scores)
    want = np.asarray(attention.dsa_select(scores, valid, topk))
    n_live, width = attention.dsa_live_blocks(live, tk)
    assert (n_live, width) == (-(-live // kb), min(-(-live // kb) * kb, tk))
    u = _block_major(jnp.where(valid, attention._ordered_bits(scores),
                               jnp.uint32(0)), kb)
    # what lies past the live blocks is never read: huge images there
    u = u.at[n_live:].set(jnp.uint32(0xFFFFFF00))
    got = jax.jit(attention.dsa_select_blocks, static_argnums=2)(
        u, jnp.int32(n_live), topk)              # a traced trip count
    assert not np.asarray(got[n_live:]).any()
    got = _key_order(got)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        got.sum(-1), np.minimum(np.asarray(valid).sum(-1), topk))
    if live > 12:
        # the ties were cut inside block 1, past the boundary at 8
        row = got[0, -1]
        assert row[[1, 9, 17]].all() and row[6:9].all()
        assert not row[10:17].any() and not row[18]


@pytest.mark.parametrize("start", [0, 5, 19, 40])
def test_keys_past_the_live_ones_never_reach_a_chunk_step(
        model, key_blocks_of_8, start):
    """``mha_chunk_step`` bounds everything by ``start + K``: cache rows
    past it filled with huge K, V and index keys leave its output and
    the rows it wrote bit-identical (the block that holds the last live
    key is read to its end, and masked)."""
    _, gen = model
    layer = gen._blocks[0]
    params = gen.params[layer.name]
    kk = 8
    rng = np.random.default_rng(start)
    x = jnp.asarray(rng.normal(size=(1, kk, CFG["hidden_size"])),
                    jnp.float32)

    def cache(beyond):
        """the same rows up to the chunk's end, ``beyond`` past it"""
        fill, c = np.random.default_rng(7), \
            gen._init_caches(1, jnp.float32)[0]
        filled = []
        for leaf in c:
            a = fill.normal(size=leaf.shape).astype(np.float32)
            a[:, :, start + kk:] = beyond
            filled.append(jnp.asarray(a, leaf.dtype))
        return type(c)(*filled)

    outs = []
    for beyond in (0.0, 1e30):
        y, c = jax.jit(layer.chunk_step)(params, x, cache(beyond),
                                         jnp.int32(start))
        outs.append((np.asarray(y), [np.asarray(leaf)[:, :, :start + kk]
                                     for leaf in c]))
    assert np.isfinite(outs[0][0]).all()
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    for a, b in zip(outs[0][1], outs[1][1]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("tk", [24, 20])
def test_without_live_keys_the_attention_still_differentiates(
        key_blocks_of_8, tk):
    """``live_keys=None`` (the training forward, ``mha_prefill``): static
    trip counts, and gradients that are the masked softmax attention's
    over the selected keys (tk 20: the last block overlaps its
    neighbour)."""
    topk, rng = 5, np.random.default_rng(11)

    def a(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    t = dict(q=a(1, 4, tk, 8), k=a(1, 2, tk, 8), v=a(1, 2, tk, 8),
             qi=a(1, 2, tk, 4), ki=a(1, 1, tk, 4), wi=a(1, tk, 2) * 0.3)

    def sparse(q, k, v):
        return jnp.sum(jnp.sin(attention.dsa_attend(
            q, k, v, t["qi"], t["ki"], t["wi"], 0, topk)))

    causal = jnp.tril(jnp.ones((tk, tk), bool))[None]
    chosen = attention.dsa_select(
        attention.index_scores(t["qi"], t["ki"][:, 0], t["wi"]), causal,
        topk)

    def masked(q, k, v):
        kr, vr = (jnp.repeat(a, 2, axis=1) for a in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kr) * 8 ** -0.5
        p = jax.nn.softmax(jnp.where(chosen[:, None], s, -jnp.inf), -1)
        return jnp.sum(jnp.sin(jnp.einsum("bhqk,bhkd->bhqd", p, vr)))

    got = jax.jit(jax.grad(sparse, argnums=(0, 1, 2)))(
        t["q"], t["k"], t["v"])
    want = jax.grad(masked, argnums=(0, 1, 2))(t["q"], t["k"], t["v"])
    for g, w in zip(got, want):
        assert float(jnp.abs(w).max()) > 1e-3
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)
    # no loop of the jaxpr has a trip count that depends on a value
    text = str(jax.make_jaxpr(sparse)(t["q"], t["k"], t["v"]))
    assert "while" not in text and "scan" in text


# ------------------------------------------------- against the reference
def test_the_full_forward_is_the_references(model):
    wf, _ = model
    prompt = _prompt(40)
    toks = np.zeros((1, MAX_LEN), np.int32)
    toks[0, :40] = prompt
    full = np.asarray(jax.jit(wf.trainer._forward, static_argnums=(2,))(
        wf.trainer.params, jnp.asarray(toks), False, jax.random.key(0)),
        np.float32)[0, :40]
    ref, kept = reference_keye.forward_logits(CFG, SEED, [prompt],
                                              [list(range(40))])
    np.testing.assert_allclose(full, np.asarray(ref[0]), atol=2e-5)
    for per_layer in kept[0]:
        np.testing.assert_array_equal(
            per_layer, np.minimum(np.arange(40) + 1, TOPK))


def test_the_selected_sets_are_the_references_everywhere(model,
                                                         monkeypatch):
    """Every query's selected set, in every layer: the chunked prefill
    path over a whole prompt, then the paged decode path token by token
    past it (the positions its gathers read, ``dsa_positions``)."""
    wf, gen = model
    prompt = _prompt(40, 1)
    seen = {"prefill": [], "decode": []}
    real_select, real_positions = attention.dsa_select_blocks, \
        attention.dsa_positions

    def select(u, n_live, topk):
        out = real_select(u, n_live, topk)   # [blocks, b, tq, kb]
        jax.debug.callback(
            lambda m: seen["prefill"].append(_key_order(m)), out,
            ordered=True)
        return out

    def positions(chosen, n_sel):
        sel, live = real_positions(chosen, n_sel)
        jax.debug.callback(
            lambda s, l: seen["decode"].append(
                (np.asarray(s), np.asarray(l))), sel, live, ordered=True)
        return sel, live

    # four key blocks a prompt's bucket, so that ties are counted
    # across blocks inside the model too
    monkeypatch.setattr(attention, "DSA_KEY_BLOCK", 16)
    monkeypatch.setattr(attention, "dsa_select_blocks", select)
    monkeypatch.setattr(attention, "dsa_positions", positions)
    cb = PagedContinuousBatcher(gen, slots=1, block=4, pool_tokens=64)
    rid = cb.submit(prompt, 10)
    result = cb.run_all()[rid]
    jax.effects_barrier()
    _, sets = reference_keye.forward_logits(
        CFG, SEED, [result[:-1]], [[0]], sets=True)
    n_layers = CFG["num_hidden_layers"]
    # prefill: one block-major [tp / 16, 1, tp, 16] mask a layer (tp:
    # the prompt's bucket), put back in key order
    assert len(seen["prefill"]) == n_layers
    for layer, mask in enumerate(seen["prefill"]):
        assert mask.shape == (1, 64, 64)
        np.testing.assert_array_equal(mask[0, :40, :40],
                                      sets[0][layer][:40, :40])
    # decode: one ranking a layer a tick, positions 39 .. 48
    # (and one more dispatch, enqueued before the tenth report was read:
    # the row is frozen in it and its ranking is nobody's)
    assert len(seen["decode"]) == n_layers * (10 + 1)
    for i, (sel, live) in enumerate(seen["decode"][:n_layers * 10]):
        layer, p = i % n_layers, 39 + i // n_layers
        got = np.zeros(len(result) - 1, bool)
        got[sel[0][live[0]]] = True
        np.testing.assert_array_equal(got[:p + 1],
                                      sets[0][layer][p, :p + 1])
        assert not got[p + 1:].any()


@pytest.mark.parametrize("plen,new,segment", [
    (9, 20, 0),        # under topk, decodes across the hand-over at 16
    (40, 12, 0),       # over topk from the start, one prefill pass
    (40, 12, 8),       # the same through segmented (staged) prefill
    (34, 12, 32),      # a tail of 1 token run as a pass of segment // 8
])
def test_prefill_then_paged_decode_is_the_references(model, plen, new,
                                                     segment):
    _, gen = model
    prompt = _prompt(plen, 2)
    cb = PagedContinuousBatcher(gen, slots=2, block=4, pool_tokens=256,
                                prefill_segment=segment)
    rid = cb.submit(prompt, new)
    result = cb.run_all()[rid]
    assert result[:plen] == prompt and len(result) == plen + new
    # the dense-cache generator says the same tokens
    solo = gen.generate(np.asarray([prompt]), max_new=new)[0]
    assert result == [int(t) for t in solo[:plen + new]]
    # and every served token is the reference's first choice
    gap, n = reference_keye.logit_gaps(
        CFG, SEED, [{"prompt": prompt, "result": result}])
    assert n == new and gap < 1e-4, gap
    # with the selection off the reference disagrees: the cell guards
    ctrl, _ = reference_keye.logit_gaps(
        CFG, SEED, [{"prompt": prompt, "result": result}], select=False)
    if plen + new > 2 * TOPK:
        assert ctrl > 0.05, ctrl


# ------------------------------------------------------ dropless routing
def _loop_moe(params, x, top_k):
    """Token by token, expert by expert, in float64."""
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    out = np.zeros_like(x, np.float64)
    for t, h in enumerate(np.asarray(x, np.float64)):
        logits = h @ p["router"]
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        order = np.argsort(-probs, kind="stable")[:top_k]
        gates = probs[order] / probs[order].sum()
        for g, e in zip(gates, order):
            a = h @ p["w_gate"][e]
            out[t] += g * ((a / (1 + np.exp(-a)) * (h @ p["w_up"][e]))
                           @ p["w_down"][e])
    return out


def _moe_params(e, d=16, f=8, seed=3):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.normal(0.0, 0.5, shape), jnp.float32)

    return {"router": w(d, e), "w_gate": w(e, d, f), "w_up": w(e, d, f),
            "w_down": w(e, f, d)}


@pytest.mark.parametrize("case", ["even", "all_to_one", "one_empty"])
def test_dropless_routing_is_the_per_token_loop(case):
    params = _moe_params(8)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(3, 11, 16)),
                    jnp.float32)
    top_k = 2
    if case == "all_to_one":
        # every token to expert 5: the others' logits far below
        top_k = 1
        params["router"] = jnp.zeros((16, 8)).at[0, 5].set(50.0)
        x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 0.5)
    if case == "one_empty":
        params["router"] = jnp.zeros((16, 8)).at[0, 2].set(-50.0) \
            + 0.1 * params["router"].at[:, 2].set(0.0)
        x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 0.5)
    y, touched = jax.jit(lambda p, a: moe.moe_dropless_forward(
        p, a, top_k=top_k))(params, x)
    want = _loop_moe(params, np.asarray(x).reshape(-1, 16), top_k)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 16), want,
                               rtol=2e-4, atol=2e-5)
    # no token dropped: every row got its experts' output
    assert (np.abs(np.asarray(y)).sum(-1) > 0).all()
    _, experts = moe.route_topk(x.reshape(-1, 16), params["router"],
                                top_k)
    assert int(touched) == len(np.unique(np.asarray(experts)))
    if case == "all_to_one":
        assert int(touched) == 1
    if case == "one_empty":
        assert 2 not in np.asarray(experts)


def test_the_shares_of_the_experts_add_up_to_the_layer():
    """128 experts in 8 shares of 16 (the chip's share of an
    expert-parallel deployment): every share routes over all 128 and
    computes its own experts' part; the parts add up to the whole."""
    params = _moe_params(128, seed=6)
    x = jnp.asarray(np.random.default_rng(7).normal(size=(2, 9, 16)),
                    jnp.float32)
    whole, touched = moe.moe_dropless_forward(params, x, top_k=8)
    parts, seen = 0.0, 0
    for share in range(8):
        held = {k: (v if k == "router" else v[16 * share:16 * share + 16])
                for k, v in params.items()}
        part, n = moe.moe_dropless_forward(held, x, top_k=8,
                                           first=16 * share)
        parts, seen = parts + part, seen + int(n)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    assert seen == int(touched)
    np.testing.assert_allclose(
        np.asarray(whole).reshape(-1, 16),
        _loop_moe(params, np.asarray(x).reshape(-1, 16), 8),
        rtol=2e-4, atol=2e-5)


# ------------------------------------------------ the pool's third leaf
def test_the_pool_pages_three_leaves(model):
    """Admit, prefix sharing between two concurrent requests, release,
    block accounting — with the index key beside K and V."""
    _, gen = model
    cb = PagedContinuousBatcher(gen, slots=2, block=4, pool_tokens=128,
                                prefix_cache=True)
    di = CFG["sa_config"]["indexer_head_dim"]
    for layer in cb._pool:
        assert layer._fields == ("k", "v", "idx")
        assert layer.k.shape == (33, CFG["num_key_value_heads"], 4,
                                 CFG["head_dim"])
        assert layer.idx.shape == (33, 1, 4, di)
    shared = _prompt(24, 3)
    a, b = shared + _prompt(6, 4), shared + _prompt(9, 5)
    ra, rb = cb.submit(a, 6), cb.submit(b, 5)
    cb.tick()                       # both admitted, both decoding
    blocks, refs = cb.prefix_stats()
    assert refs > blocks >= 6       # the 24 shared tokens' 6 blocks
    in_use = cb.pool_blocks - cb.free_blocks()
    need = -(-(len(a) + 6) // 4) + -(-(len(b) + 5) // 4)
    assert in_use == need - 6       # 6 whole blocks held once
    out = cb.run_all()
    for rid, prompt, new in ((ra, a, 6), (rb, b, 5)):
        solo = gen.generate(np.asarray([prompt]), max_new=new)[0]
        assert out[rid] == [int(t) for t in solo[:len(prompt) + new]]
    assert cb.free_blocks() == cb.pool_blocks
    assert cb.prefix_stats() == (0, 0)
    assert 1 <= cb.last_tick["experts_touched"] <= 2 * \
        CFG["num_experts_per_tok"]


def test_the_tick_counts_the_keys_where_the_attention_ran(model):
    """``sel_keys`` comes from the device: the selection's own count on
    the sparse path, a row's whole context on the dense one."""
    _, gen = model
    cb = PagedContinuousBatcher(gen, slots=2, block=4, pool_tokens=128)
    cb.submit(_prompt(30, 6), 4)
    cb.submit(_prompt(9, 7), 4)
    cb.tick()
    cb.tick()                       # reads the first dispatch's report
    assert cb.last_tick["kv_tokens"] == 30 + 9
    assert cb.last_tick["sel_keys"] == TOPK + 9


# ------------------------------------- one tick, one state layout
@pytest.fixture(scope="module")
def plain_gen():
    """A model with neither indexer nor dropless experts."""
    wf, _ = _old_workflow(pos="learned", d_ff=64)
    return LMGenerator(wf.trainer, max_len=16)


def _batcher(kind, plain, keye, ticks_per_dispatch):
    kw = dict(slots=2, ticks_per_dispatch=ticks_per_dispatch)
    if kind == "dense":
        return ContinuousBatcher(plain, **kw)
    if kind == "speculative":
        return ContinuousBatcher(plain, speculative_k=4, **kw)
    return PagedContinuousBatcher(plain if kind == "paged" else keye,
                                  block=4, pool_tokens=64, **kw)


@pytest.mark.parametrize("ticks_per_dispatch", [1, 2])
@pytest.mark.parametrize("kind", ["dense", "speculative", "paged",
                                  "paged_counting"])
def test_every_tick_body_returns_state_and_report(
        model, plain_gen, kind, ticks_per_dispatch):
    """A tick body of each kind hands out ``(state, report)``, and
    ``_jit_ticks`` the report packed into ONE int32 array, one row a
    tick of the dispatch: the token(s) each row wrote and how many, its
    cursor and flag after the tick — and what the blocks counted, where
    the model's blocks select keys or route to experts."""
    cb = _batcher(kind, plain_gen, model[1], ticks_per_dispatch)
    cb.submit(_prompt(5, 8) if kind == "paged_counting"
              else [1, 2, 3, 4, 5], 4)
    cb._admit(0)
    before = cb._state()
    st, packed = cb._jit_ticks(cb._tick_body())(
        cb.gen.params, before, cb._aids)
    assert packed.dtype == jnp.int32 and packed.ndim == 2 \
        and packed.shape[0] == ticks_per_dispatch
    report = generate._unpack_report(np.asarray(packed),
                                     cb._report_layout)
    assert isinstance(st, SlotState)
    assert jax.tree_util.tree_structure(st) == \
        jax.tree_util.tree_structure(cb._state())
    assert int(st.pos[0]) > 4 and int(st.pos[1]) == 0
    names = ["active", "n", "pos", "tokens"]
    if kind == "paged_counting":
        names += ["attended", "experts_touched"]
        assert report["attended"].shape == (ticks_per_dispatch, 2)
        assert report["experts_touched"].shape == (ticks_per_dispatch,)
    assert sorted(report) == sorted(names)
    width = 4 if kind == "speculative" else 1
    assert report["tokens"].shape == (ticks_per_dispatch, 2, width)
    for name in ("n", "pos", "active"):
        assert report[name].shape == (ticks_per_dispatch, 2)
    # the occupied row wrote up to its cursor, the free one nothing
    wrote = np.asarray(report["n"])
    assert wrote[:, 1].sum() == 0
    assert 4 + wrote[:, 0].sum() == int(st.pos[0])
    np.testing.assert_array_equal(report["pos"][-1], st.pos)
    np.testing.assert_array_equal(report["active"][-1], st.active)
    if kind == "paged_counting":
        # the float32 counts came through as their bits
        assert report["attended"].dtype == np.float32
        assert 0 < report["attended"][-1, 0] <= int(st.pos[0])
    # and the tick the engine runs reads that one array
    cb._set_state(st)
    cb.tick()
    cb.tick()                       # reads the first dispatch's report
    assert cb._report.shape == packed.shape
    assert cb.last_tick["fetch_bytes"] == packed.nbytes


def test_the_paged_state_flattens_in_the_programs_order(model):
    """tokens, pos, plen, total, active, seeds, inv_temp, the pool's
    leaves, the table: the order of the jitted programs' arguments, on
    which their being the same programs rests — and the tick lowers on
    ``(gen.params, cb._state(), cb._aids)``, as the benchmark's compile
    check and the decode audit call it."""
    _, gen = model
    cb = PagedContinuousBatcher(gen, slots=2, block=4, pool_tokens=64)
    st = cb._state()
    assert st._fields == ("tokens", "pos", "plen", "total", "active",
                          "seeds", "inv_temp", "cache")
    assert st.cache[0] is cb._pool and st.cache[1] is cb._tables
    want = [cb._tokens, cb._pos, cb._plen, cb._total, cb._active,
            cb._seeds, cb._inv_temp] \
        + jax.tree_util.tree_leaves(cb._pool) + [cb._tables]
    got = jax.tree_util.tree_leaves(st)
    assert len(got) == len(want) == 7 + 3 * len(gen._blocks) + 1
    assert all(a is b for a, b in zip(got, want))
    text = cb._jit_ticks(cb._tick_body()).lower(
        gen.params, st, cb._aids).as_text()
    # every leaf of the state is donated into the tick
    assert text.count("tf.aliasing_output") == len(got)
    cb._set_state(st)
    assert cb._pool is st.cache[0] and cb._tables is st.cache[1]


# --------------------------------------------------- weights' dtype
def test_a_bfloat16_build_never_holds_a_float32_copy():
    wf = build_keye.build_workflow(CFG, 32)          # param bfloat16
    wide = [a for a in jax.tree_util.tree_leaves(wf.trainer.params)
            if a.ndim >= 2]
    assert wide and all(a.dtype == jnp.bfloat16 for a in wide)
    slots = jax.tree_util.tree_leaves(wf.trainer.velocity)
    assert sum(a.size for a in slots) < sum(a.size for a in wide) / 4
    gen = LMGenerator(wf.trainer, max_len=32, weights="bf16")
    assert all(a is b for a, b in zip(
        jax.tree_util.tree_leaves(gen.params),
        jax.tree_util.tree_leaves(wf.trainer.params)))
    pool = PagedContinuousBatcher(gen, slots=1, block=4,
                                  pool_tokens=32)._pool
    assert all(a.dtype == jnp.bfloat16
               for a in jax.tree_util.tree_leaves(pool))


@pytest.mark.parametrize("scheme", ["int8", "w4a8"])
def test_quantised_weights_refuse_the_dropless_experts(model, scheme):
    wf, _ = model
    with pytest.raises(ValueError, match="moe.w_gate/w_up/w_down"):
        LMGenerator(wf.trainer, max_len=MAX_LEN, weights=scheme)
