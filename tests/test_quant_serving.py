"""Quantized decode depth: w4a8 serving weights, the fused paged
decode kernel's quantized-pool (int8 QuantCache) variant, per-row
speculative routing, and the stray-dequant jaxpr audit that pins the
whole story — no QuantWeight may dequantize outside a dot on the
decode hot path (ISSUE 14)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from veles_tpu import prng
from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.models import zoo
from veles_tpu.models.generate import (ContinuousBatcher, LMGenerator,
                                       PagedContinuousBatcher)
from veles_tpu.models.standard_workflow import StandardWorkflow
from veles_tpu.ops import quant


def _lm_workflow(max_epochs=0, vocab=13, t=16, seed=31, **zoo_kwargs):
    prng.seed_all(seed)
    r = np.random.RandomState(5)
    toks = ((np.arange(t)[None, :] * 2 + r.randint(0, 4, 192)[:, None])
            % vocab).astype(np.int32)
    loader = FullBatchLoader(None, data=toks, labels=toks,
                             minibatch_size=48,
                             class_lengths=[0, 48, 144])
    wf = StandardWorkflow(
        layers=zoo.transformer_lm(vocab_size=vocab, d_model=32,
                                  n_heads=4, n_layers=2, lr=5e-3,
                                  dropout=0.0, **zoo_kwargs),
        loader=loader, loss="lm",
        decision_config={"max_epochs": max(max_epochs, 1)},
        name="quant-lm")
    wf.initialize()
    if max_epochs > 0:
        wf.run()
    return wf, toks


# --------------------------------------------------------------------------
# w4a8 scheme unit level
# --------------------------------------------------------------------------

class TestW4A8Scheme:
    @pytest.mark.parametrize("n_in", [16, 17])   # even + odd (pad path)
    def test_pack_unpack_roundtrip(self, n_in):
        r = np.random.RandomState(0)
        q = r.randint(-7, 8, (n_in, 12)).astype(np.int8)
        packed = quant._pack_nibbles(jnp.asarray(q), 0)
        assert packed.shape == ((n_in + 1) // 2, 12)
        assert packed.dtype == jnp.int8
        unp = np.asarray(quant._unpack_nibbles(packed, n_in, 0))
        np.testing.assert_array_equal(unp, q)

    def test_quantize_weight4_layout_and_error_bound(self):
        r = np.random.RandomState(1)
        w = r.randn(24, 10).astype(np.float32)
        qw = quant.quantize_weight4(w)
        assert isinstance(qw, quant.QuantWeight4)
        assert qw.q.shape == (12, 10) and qw.scale.shape == (10,)
        assert (qw.n, qw.axis) == (24, 0)
        deq = (np.asarray(quant._unpack_nibbles(qw.q, 24, 0),
                          np.float32) * np.asarray(qw.scale))
        # round-to-nearest symmetric int4: error <= scale/2 per entry
        assert np.all(np.abs(deq - w)
                      <= np.asarray(qw.scale) * 0.5 + 1e-6)

    def test_w4a8_matmul_matches_dequantized_reference(self):
        """The fused w4a8 dot must equal the explicit two-step
        (quantize acts, dequantize weight, float matmul) bit for bit —
        the integer-valued f32 dot is exact, so 'fp accumulation'
        changes nothing but the wire format."""
        r = np.random.RandomState(2)
        w = r.randn(16, 12).astype(np.float32)
        x = r.randn(5, 16).astype(np.float32)
        qw = quant.quantize_weight4(w)
        got = np.asarray(quant.w4a8_matmul(jnp.asarray(x), qw))
        xq, xs = quant.symmetric_int8(jnp.asarray(x))
        deq = (np.asarray(quant._unpack_nibbles(qw.q, 16, 0),
                          np.float32))
        want = ((np.asarray(xq, np.float32) @ deq)
                * np.asarray(xs) * np.asarray(qw.scale))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    def test_table_direction_and_take_rows(self):
        r = np.random.RandomState(3)
        t = r.randn(11, 16).astype(np.float32)     # odd vocab is fine
        qt = quant.quantize_weight4(t, axis=1)
        assert qt.q.shape == (11, 8) and qt.scale.shape == (11,)
        x = r.randn(3, 16).astype(np.float32)
        got = np.asarray(quant.w4a8_matmul_t(jnp.asarray(x), qt))
        deq = (np.asarray(quant._unpack_nibbles(qt.q, 16, 1),
                          np.float32) * np.asarray(qt.scale)[:, None])
        xq, xs = quant.symmetric_int8(jnp.asarray(x))
        want = (np.asarray(xq, np.float32) @ deq.T) * np.asarray(xs)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        rows = np.asarray(quant.take_rows(qt, jnp.asarray([0, 4, 10])))
        np.testing.assert_allclose(rows, deq[[0, 4, 10]], rtol=1e-6)

    def test_quantize_lm_params_scheme_dispatch(self):
        wf, _ = _lm_workflow()
        p8 = quant.quantize_lm_params(wf.trainer.params,
                                      embed_name="l00_embedding")
        p4 = quant.quantize_lm_params(wf.trainer.params,
                                      embed_name="l00_embedding",
                                      scheme="w4a8")
        w8 = p8["l02_transformer_block"]["mha"]["wq"]
        w4 = p4["l02_transformer_block"]["mha"]["wq"]
        assert isinstance(w8, quant.QuantWeight)
        assert isinstance(w4, quant.QuantWeight4)
        # half the payload bytes again
        assert w4.q.size * 2 == w8.q.size
        assert isinstance(p4["l00_embedding"]["table"],
                          quant.QuantWeight4)
        with pytest.raises(ValueError, match="scheme"):
            quant.quantize_lm_params(wf.trainer.params, scheme="int2")

    def test_min_payload_elems_counts_logical_int4(self):
        """Odd packed axis: the threshold must be the LOGICAL n*m
        element count (what a dense dequant converts), never the
        padded-nibble count above it — or the audit's own threshold
        would hide the exact convert it exists to catch."""
        w = np.random.RandomState(0).randn(17, 8).astype(np.float32)
        tree = {"w": quant.quantize_weight4(w)}
        assert quant.min_payload_elems(tree) == 17 * 8
        assert quant.min_payload_elems(
            {"w": quant.quantize_weight(w)}) == 17 * 8
        with pytest.raises(ValueError, match="no quantized"):
            quant.min_payload_elems({"w": w})

    def test_pytree_roundtrip(self):
        qw = quant.quantize_weight4(np.eye(8, dtype=np.float32))
        leaves, treedef = jax.tree_util.tree_flatten(qw)
        assert len(leaves) == 2
        back = jax.tree_util.tree_unflatten(treedef, leaves)
        assert (back.n, back.axis) == (qw.n, qw.axis)
        np.testing.assert_array_equal(np.asarray(back.q),
                                      np.asarray(qw.q))


# --------------------------------------------------------------------------
# w4a8 end-to-end decode: argmax agreement on decided samples
# --------------------------------------------------------------------------

class TestW4A8Decode:
    def test_argmax_agreement_on_decided_samples(self, f32_precision):
        """The PR 10 export-native methodology: int4 quantization
        legitimately flips near-ties, so gate argmax agreement on the
        positions whose FLOAT top-2 margin clears the measured
        quantization error — those must agree exactly."""
        wf, toks = _lm_workflow(max_epochs=10)
        gen_f = LMGenerator(wf.trainer, max_len=16)
        gen_4 = LMGenerator(wf.trainer, max_len=16, weights="w4a8")
        sf = gen_f.score(toks[:8]).reshape(-1, 13)
        s4 = gen_4.score(toks[:8]).reshape(-1, 13)
        err = np.abs(s4 - sf).max(axis=1)
        top2 = np.sort(sf, axis=1)
        margin = top2[:, -1] - top2[:, -2]
        decided = margin > 4 * err
        assert decided.sum() >= 20, (margin.max(), err.max())
        np.testing.assert_array_equal(s4.argmax(1)[decided],
                                      sf.argmax(1)[decided])

    def test_w4a8_through_the_serving_batcher(self, f32_precision):
        """w4a8 weights ride the continuous batcher (the REST engine's
        decode path) — streams must equal the solo w4a8 decode."""
        wf, toks = _lm_workflow(max_epochs=8)
        gen = LMGenerator(wf.trainer, max_len=16, weights="w4a8")
        cb = ContinuousBatcher(gen, slots=2)
        rid = cb.submit(toks[0, :4].tolist(), 8)
        cb.run_all()
        assert cb.pop_result(rid) == \
            gen.generate(toks[:1, :4], 8)[0].tolist()


# --------------------------------------------------------------------------
# Quantized-pool fused paged decode kernel
# --------------------------------------------------------------------------

def _quant_paged_setup(b=3, hkv=2, g=4, bs=16, nbm=4, hd=64, seed=0,
                       pos=None):
    from veles_tpu.ops.attention import QuantCache, quantize_kv
    r = np.random.RandomState(seed)
    if pos is not None:
        b = len(pos)
    pool_blocks = b * nbm + 1
    q = jnp.asarray(r.randn(b, hkv * g, hd), jnp.float32)
    kd = jnp.asarray(r.randn(1 + pool_blocks, hkv, bs, hd), jnp.float32)
    vd = jnp.asarray(r.randn(1 + pool_blocks, hkv, bs, hd), jnp.float32)
    pk = QuantCache(*quantize_kv(kd))
    pv = QuantCache(*quantize_kv(vd))
    ids = r.permutation(pool_blocks)[:b * nbm].reshape(b, nbm) + 1
    table = np.zeros((b, nbm), np.int32)
    if pos is None:
        pos = [0, (nbm // 2) * bs + 3, nbm * bs - 1][:b]
    pos = np.asarray(pos, np.int32)
    for i in range(b):
        live = pos[i] // bs + 1
        table[i, :live] = ids[i, :live]
    return q, pk, pv, jnp.asarray(table), jnp.asarray(pos)


def _schedule_edges(chunk, bs, nbm):
    """The positions the live-page schedule can get wrong (as in
    tests/test_attention.py): the first key, either side of the first
    page boundary and of the first chunk boundary, a tail of live pages
    that does not fill a chunk, the last key of a full table."""
    return [0, bs - 1, bs, chunk * bs - 1, chunk * bs,
            (chunk + 1) * bs + 3, (nbm - 1) * bs - 1, nbm * bs - 1]


def _force_q8_chunk(monkeypatch, chunk, bs, hd):
    """Shrink the kernel's buffer budget so that ``page_schedule``
    itself derives ``chunk`` pages a step over both KV heads."""
    from veles_tpu.ops.pallas import paged
    monkeypatch.setattr(
        paged, "_PAGE_BUFFER_BYTES",
        8 * chunk * paged._head_page_bytes(bs, hd, jnp.int8, True))


class TestQuantPagedKernel:
    @pytest.mark.parametrize("g,qdtype,tol", [
        (1, jnp.float32, 2e-5), (4, jnp.float32, 2e-5),
        (4, jnp.bfloat16, 2e-2)])
    def test_interpret_parity_vs_reference(self, g, qdtype, tol):
        """The acceptance pin: the quantized-pool kernel variant ==
        paged_attention_reference over the same QuantCache pools, in
        interpret mode, at staggered per-row lengths."""
        from veles_tpu.ops.pallas.paged import (paged_attention_decode,
                                                paged_attention_reference)
        q, pk, pv, table, pos = _quant_paged_setup(g=g)
        q = q.astype(qdtype)
        ref = paged_attention_reference(q, pk, pv, table, pos)
        out = paged_attention_decode(q, pk, pv, table, pos,
                                     interpret=True)
        assert out.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol)

    def test_dead_blocks_cannot_leak(self):
        """Poison in the dummy block / beyond-pos blocks (data AND
        scales) must not change the quantized kernel's output."""
        from veles_tpu.ops.attention import QuantCache
        from veles_tpu.ops.pallas.paged import paged_attention_decode
        q, pk, pv, table, pos = _quant_paged_setup()
        base = np.asarray(paged_attention_decode(
            q, pk, pv, table, pos, interpret=True), np.float32)
        poison_d = jnp.full(pk.data.shape[1:], 127, jnp.int8)
        poison_s = jnp.full(pk.scale.shape[1:], 1e4, jnp.float32)
        pk2 = QuantCache(pk.data.at[0].set(poison_d),
                         pk.scale.at[0].set(poison_s))
        pv2 = QuantCache(pv.data.at[0].set(poison_d),
                         pv.scale.at[0].set(poison_s))
        live1 = int(pos[1]) // pk.data.shape[2] + 1
        table2 = table.at[1, live1].set(int(table[2, 0]))
        out = np.asarray(paged_attention_decode(
            q, pk2, pv2, table2, pos, interpret=True), np.float32)
        np.testing.assert_allclose(out, base, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("g", [1, 4])
    @pytest.mark.parametrize("hd", [64, 128])
    @pytest.mark.parametrize("bs", [16, 32])
    def test_live_page_schedule_matches_reference(self, monkeypatch, bs,
                                                  hd, g):
        """The q8 twin of tests/test_attention.py's schedule test: the
        quantized pool walks the same live-page schedule (its pages
        arrive as BlockSpec'd operands — Mosaic cannot slice a [bs, 1]
        scale tile by hand) over the positions it can get wrong."""
        from veles_tpu.ops.pallas import paged
        chunk = 128 // bs
        nbm = 2 * chunk + chunk // 2
        _force_q8_chunk(monkeypatch, chunk, bs, hd)
        assert paged.page_schedule(2, bs, hd, jnp.int8, nbm, True) == \
            (chunk, 2)
        assert not paged._sliceable(hd, True)
        q, pk, pv, table, pos = _quant_paged_setup(
            g=g, bs=bs, nbm=nbm, hd=hd,
            pos=_schedule_edges(chunk, bs, nbm) + [0])
        table = table.at[-1].set(0)              # a free slot
        ref = paged.paged_attention_reference(q, pk, pv, table, pos)
        out = paged.paged_attention_decode(q, pk, pv, table, pos,
                                           interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_visits_live_pages_only(self, monkeypatch):
        """Every page no live prefix names poisoned, data and scales
        (the dummy block too), every table entry past a live prefix
        pointed at one: the quantized kernel's output does not move."""
        from veles_tpu.ops.attention import QuantCache
        from veles_tpu.ops.pallas import paged
        bs, chunk, hd = 32, 4, 64
        nbm = 2 * chunk + chunk // 2
        _force_q8_chunk(monkeypatch, chunk, bs, hd)
        q, pk, pv, table, pos = _quant_paged_setup(
            g=1, bs=bs, nbm=nbm, hd=hd,
            pos=_schedule_edges(chunk, bs, nbm))
        base = np.asarray(paged.paged_attention_decode(
            q, pk, pv, table, pos, interpret=True))
        live = np.asarray(table) > 0
        named = np.zeros(pk.data.shape[0], bool)
        named[np.asarray(table)[live]] = True
        dead = np.nonzero(~named)[0]
        assert 0 in dead and len(dead) > nbm

        def poisoned(pool):
            return QuantCache(pool.data.at[dead].set(127),
                              pool.scale.at[dead].set(1e4))

        table2 = jnp.where(live, table, jnp.asarray(
            np.resize(dead, live.shape), jnp.int32))
        out = np.asarray(paged.paged_attention_decode(
            q, poisoned(pk), poisoned(pv), table2, pos, interpret=True))
        np.testing.assert_allclose(out, base, rtol=1e-6, atol=1e-6)

    def test_vp6xx_registered_and_tuner_resolvable(self, tmp_path,
                                                   monkeypatch):
        """Acceptance: the quantized variant is part of the registered
        VP6xx audit hook (both pool flavors audited) and resolves its
        pool block through tuner.lookup at the int8 dtype key, exactly
        like the bf16 path."""
        from veles_tpu.analysis.numerics_audit import (
            ERROR, audit_pallas_kernels)
        from veles_tpu.ops.pallas import kernel_audit_launches, paged
        launches = [l for l in kernel_audit_launches()
                    if l["kernel"].startswith("paged.decode")]
        kinds = {l["kernel"] for l in launches}
        assert kinds == {"paged.decode", "paged.decode.q8"}, kinds
        q8 = next(l for l in launches
                  if l["kernel"] == "paged.decode.q8")
        block_dtypes = {name: jnp.dtype(dt)
                        for name, _s, dt, *_ in q8["blocks"]}
        assert block_dtypes["k"] == jnp.int8
        assert block_dtypes["k_scale"] == jnp.float32
        # the configured launches audit clean (no ERROR findings)
        findings = audit_pallas_kernels(launches)
        assert not [f for f in findings if f.severity == ERROR], \
            findings

        # tuner resolution at the int8 key
        import veles_tpu.tuner as tuner
        monkeypatch.setenv("VELES_TUNE_CACHE",
                           str(tmp_path / "winners.json"))
        tuner.reset()
        try:
            t = tuner.get_tuner()
            t.record("paged.decode", tuner.paged_shape_key(64, 1),
                     "int8", {"block": 64, "block_g": 32}, 1.0,
                     launches=paged.audit_launch(
                         64, 64, g=32, dtype="int8"))
            assert paged.preferred_pool_block(64, 1, jnp.int8) == 64
            assert paged._resolve_block_g(1, 64, jnp.int8) == 32
            # the bf16 key is untouched -> falls to defaults
            assert paged.preferred_pool_block(
                64, 1, jnp.bfloat16) == 16
        finally:
            tuner.reset()

    def test_quant_sweep_populates_cache(self, tmp_path, monkeypatch):
        """The tune-smoke shape: sweep_paged(dtype='int8') in
        interpret mode must produce a winner at the int8 key with
        zero audit-rejected candidates."""
        import veles_tpu.tuner as tuner
        from veles_tpu.tuner import sweeps
        monkeypatch.setenv("VELES_TUNE_CACHE",
                           str(tmp_path / "winners.json"))
        tuner.reset()
        try:
            res = sweeps.sweep_paged(tuner.get_tuner(), hd=32, g=1,
                                     dtype="int8", iters=1, repeats=1,
                                     warmup=1, interpret=True)
            (_, dtype, _hd), sr = next(iter(res.items()))
            assert dtype == "int8"
            assert sr.winner, sr.candidates
            assert not sr.audit_rejected
            assert "|int8|" in sr.key
            win = tuner.lookup("paged.decode",
                               tuner.paged_shape_key(32, 1), "int8")
            assert win and win["block"] == sr.winner["config"]["block"]
        finally:
            tuner.reset()

    def test_engine_serves_quant_paged_fused(self, f32_precision):
        """End to end: ContinuousEngine + cache_dtype=int8 +
        paged_block runs the fused quantized kernel and serves the
        dense int8 batcher's exact streams."""
        from veles_tpu.services.restful import ContinuousEngine
        wf, toks = _lm_workflow(max_epochs=8)
        gen = LMGenerator(wf.trainer, max_len=16, cache_dtype="int8")
        eng = ContinuousEngine(gen, slots=2, paged_block=4,
                               pool_tokens=48)
        try:
            assert eng.cb.fused
            p = toks[0, :4].tolist()
            got = list(map(int, eng.submit(p, 7)))
            assert got == gen.generate(toks[:1, :4], 7)[0].tolist()
        finally:
            eng.stop()


    def test_unmet_fused_raises_on_a_tpu_backend(self, monkeypatch):
        """What the paged kernel cannot serve is an error naming the
        reason, at construction (which launches nothing): a block
        under Mosaic's sublane minimum ON the TPU (the backend forced
        to report it) and fine off it (interpret mode takes any
        block).  A windowed model is served on an int8 pool too: its
        streams are the dense int8 batcher's rolling-window ones."""
        from veles_tpu.models.generate import ContinuousBatcher
        from veles_tpu.ops import pallas
        wf, _ = _lm_workflow(t=32)
        gen8 = LMGenerator(wf.trainer, max_len=32, cache_dtype="int8")
        wfw, toks = _lm_workflow(t=32, window=8)
        genw = LMGenerator(wfw.trainer, max_len=32, cache_dtype="int8")
        assert PagedContinuousBatcher(gen8, slots=2, block=16).fused

        def streams(cb):
            rids = [cb.submit(toks[i, :5 + 9 * i].tolist(), 12)
                    for i in range(2)]
            cb.run_all()
            return [cb.result(r) for r in rids]

        paged = PagedContinuousBatcher(genw, slots=2, block=4,
                                       prefill_segment=8)
        assert paged.ring_blocks == (5,)
        assert streams(paged) == streams(ContinuousBatcher(genw, slots=2))

        monkeypatch.setattr(pallas, "autodetect_interpret",
                            lambda interpret: False)
        with pytest.raises(ValueError, match="32-row sublane minimum"):
            PagedContinuousBatcher(gen8, slots=2, block=16)
        with pytest.raises(ValueError, match="32-row sublane minimum"):
            PagedContinuousBatcher(genw, slots=2, block=16)
        assert PagedContinuousBatcher(gen8, slots=2, block=32).fused


# --------------------------------------------------------------------------
# Per-row speculative routing: the cliff is gone
# --------------------------------------------------------------------------

class TestPerRowSpecRouting:
    def test_mixed_pool_greedy_rows_byte_identical(self,
                                                   f32_precision):
        """THE acceptance pin: greedy rows in a pool that also holds
        one sampled request produce byte-identical streams to the
        all-greedy pool — one sampled request can no longer perturb
        (or de-speculate) its greedy neighbors."""
        wf, toks = _lm_workflow(max_epochs=8)
        gen = LMGenerator(wf.trainer, max_len=16)

        def greedy_streams(with_sampled):
            cb = ContinuousBatcher(gen, slots=3, speculative_k=4)
            g1 = cb.submit(toks[0, :4].tolist(), 8)
            rids = [g1]
            if with_sampled:
                cb.submit(toks[1, :6].tolist(), 4, temperature=0.7,
                          seed=11)
            g2 = cb.submit(toks[2, :3].tolist(), 7)
            rids.append(g2)
            cb.run_all()
            return [cb.pop_result(r) for r in rids]

        assert greedy_streams(True) == greedy_streams(False)

    def test_sampled_row_still_matches_one_token_pool(self,
                                                      f32_precision):
        """The sampled row itself keeps the 1-token pool's bit-exact
        stream (same (seed, position) keys) through the per-row
        routed core."""
        wf, toks = _lm_workflow(max_epochs=8)
        gen = LMGenerator(wf.trainer, max_len=16)

        def run(cb):
            rid = cb.submit(toks[1, :6].tolist(), 5, temperature=0.7,
                            seed=11)
            cb.submit(toks[0, :4].tolist(), 8)
            cb.run_all()
            return cb.pop_result(rid)

        assert run(ContinuousBatcher(gen, slots=2, speculative_k=4)) \
            == run(ContinuousBatcher(gen, slots=2))

    def test_no_pool_wide_cond_around_verify(self, f32_precision):
        """Structural pin: the speculative core's jaxpr carries at
        most ONE cond (the draw-cost guard), and the K-wide verify
        (the transformer stack) sits OUTSIDE it — so the verify can
        never be switched pool-wide by one row's temperature."""
        wf, toks = _lm_workflow(max_epochs=0)
        gen = LMGenerator(wf.trainer, max_len=16)
        cb = ContinuousBatcher(gen, slots=2, speculative_k=4)
        core = cb._make_core_spec(4)
        st = cb._state()
        abstract = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            (gen.params, st, cb._aids))
        jaxpr = jax.make_jaxpr(core)(*abstract)

        conds = [e for e in jaxpr.jaxpr.eqns
                 if e.primitive.name == "cond"]
        assert len(conds) <= 1, "pool-wide branching is back"
        if conds:
            # the guarded branches must be draw-sized, not
            # transformer-sized: no dot_general inside them (the
            # verify's matmuls all live outside the cond)
            def dots(jx):
                n = sum(1 for e in jx.eqns
                        if e.primitive.name == "dot_general")
                for e in jx.eqns:
                    for key in ("jaxpr", "call_jaxpr"):
                        sub = e.params.get(key)
                        if sub is not None:
                            n += dots(getattr(sub, "jaxpr", sub))
                return n
            for br in conds[0].params["branches"]:
                assert dots(br.jaxpr) == 0, \
                    "model compute inside the sampling cond"


# --------------------------------------------------------------------------
# The stray-dequant audit (acceptance: asserted by a jaxpr scan)
# --------------------------------------------------------------------------

class TestStrayDequantAudit:
    """PR 14's one-off decode-jaxpr assertions, retired into the VD700
    rule (ISSUE 16): the decode path is now audited through
    ``analysis.decode_audit``, which traces the SAME tick body serving
    jits (``ContinuousBatcher._tick_body``) — the rule and this test
    can't drift apart.  The detector-mechanics test below keeps
    pinning ``quant.stray_dequant_sites`` itself, which VD700 wraps."""

    @pytest.mark.parametrize("scheme", ["int8", "w4a8"])
    def test_decode_tick_clean_via_vd700(self, scheme):
        """Acceptance: no QuantWeight dequantizes outside a dot
        anywhere in the decode tick serving would dispatch — and the
        rest of the VD7xx family stays silent on it too."""
        from veles_tpu.analysis import decode_audit
        wf, _ = _lm_workflow()
        gen = LMGenerator(wf.trainer, max_len=16, weights=scheme)
        cb = ContinuousBatcher(gen, slots=2)
        findings = decode_audit.audit_decode_tick(cb)
        assert not [f for f in findings if f.rule == "VD700"], findings
        assert not findings, findings

    def test_prefill_pass_clean_via_vd700(self):
        """The segmented-prefill chunk pass (the other jaxpr serving
        dispatches per admission) is dequant-clean as well."""
        from veles_tpu.analysis import decode_audit
        wf, _ = _lm_workflow()
        gen = LMGenerator(wf.trainer, max_len=16, weights="int8")
        findings = decode_audit.audit_prefill_pass(gen, segment=8)
        assert not findings, findings

    def test_detector_fires_on_naive_dequant(self):
        """The audit must actually detect the bug class it pins: a
        dense dequantize-then-matmul materializes a payload-sized
        float weight outside the dot and must be flagged."""
        r = np.random.RandomState(0)
        qw = quant.quantize_weight(r.randn(32, 16).astype(np.float32))

        def naive(x, q, s):
            w = q.astype(jnp.float32) * s        # dense dequant: BAD
            return x @ w

        jaxpr = jax.make_jaxpr(naive)(
            jax.ShapeDtypeStruct((4, 32), jnp.float32),
            jax.ShapeDtypeStruct(qw.q.shape, jnp.int8),
            jax.ShapeDtypeStruct(qw.scale.shape, jnp.float32))
        sites = quant.stray_dequant_sites(jaxpr, 32 * 16)
        assert sites, "naive dense dequant not detected"
        # while the real funnels pass at the same threshold
        good = jax.make_jaxpr(quant.int8_matmul)(
            jax.ShapeDtypeStruct((4, 32), jnp.float32),
            quant.QuantWeight(
                jax.ShapeDtypeStruct((32, 16), jnp.int8),
                jax.ShapeDtypeStruct((16,), jnp.float32)))
        assert not quant.stray_dequant_sites(good, 32 * 16)


# --------------------------------------------------------------------------
# VN4xx numerics audit over the quantized decode step
# --------------------------------------------------------------------------

class TestQuantStepNumericsAudit:
    @pytest.mark.parametrize("scheme,cache", [("int8", "int8"),
                                              ("w4a8", None)])
    def test_quantized_decode_step_audits_clean(self, scheme, cache):
        """Acceptance: the VN4xx value-range audit over the quantized
        decode step (quantized weights, int8 KV cache for the int8
        leg) reports NOTHING — the quantizers' eps guards and f32
        accumulation keep every log/div/exp provably safe."""
        from veles_tpu.analysis.numerics_audit import audit_numerics_step
        wf, _ = _lm_workflow()
        gen = LMGenerator(wf.trainer, max_len=16, weights=scheme,
                          cache_dtype=cache)
        abstract = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            gen.params, is_leaf=lambda x: hasattr(x, "shape"))
        caches = jax.eval_shape(
            lambda: gen._init_caches(2, gen._model_dtype()))
        findings = audit_numerics_step({
            "fn": gen._step,
            "args": (abstract, caches,
                     jax.ShapeDtypeStruct((2,), jnp.int32), 3),
            "name": "%s-decode" % scheme})
        assert not findings, [str(f) for f in findings]
