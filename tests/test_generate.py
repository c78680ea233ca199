"""LM generation with KV cache: the incremental (cached) decode must
reproduce the full-forward logits exactly, and a trained LM must continue
its learned pattern under greedy decoding."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from veles_tpu import prng
from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.models import zoo
from veles_tpu.models.generate import LMGenerator
from veles_tpu.models.standard_workflow import StandardWorkflow


def _lm_workflow(max_epochs=0, vocab=13, t=16, seed=31, mesh_config=None,
                 **zoo_kwargs):
    prng.seed_all(seed)
    r = np.random.RandomState(5)
    n = 192
    toks = ((np.arange(t)[None, :] * 2 + r.randint(0, 4, n)[:, None])
            % vocab).astype(np.int32)
    loader = FullBatchLoader(None, data=toks, labels=toks,
                             minibatch_size=48,
                             class_lengths=[0, 48, 144])
    wf = StandardWorkflow(
        layers=zoo.transformer_lm(vocab_size=vocab, d_model=32, n_heads=4,
                                  n_layers=2, lr=5e-3, dropout=0.0,
                                  **zoo_kwargs),
        loader=loader, loss="lm",
        decision_config={"max_epochs": max(max_epochs, 1)},
        mesh_config=mesh_config, name="gen-lm")
    wf.initialize()
    if max_epochs > 0:
        wf.run()
    return wf, toks


@pytest.mark.parametrize("zoo_kwargs", [
    {}, {"n_kv_heads": 2}, {"pos": "rope"}])
def test_incremental_matches_full_forward(zoo_kwargs, f32_precision):
    wf, toks = _lm_workflow(max_epochs=0, **zoo_kwargs)
    gen = LMGenerator(wf.trainer, max_len=16)
    sample = toks[:4]
    inc = gen.score(sample)                      # [B, T-1, V]
    full = np.asarray(
        jax.jit(wf.trainer._forward, static_argnums=(2,))(
            wf.trainer.params, jnp.asarray(sample), False,
            jax.random.key(0)), np.float32)[:, :-1]
    np.testing.assert_allclose(inc, full, rtol=2e-4, atol=2e-4)


def test_greedy_generation_continues_pattern():
    wf, toks = _lm_workflow(max_epochs=15)
    gen = LMGenerator(wf.trainer, max_len=16)
    prompt = toks[:8, :8]
    out = gen.generate(prompt, max_new=8)
    assert out.shape == (8, 16)
    np.testing.assert_array_equal(out[:, :8], prompt)  # prompt untouched
    # the learned rule: every token advances by 2 (mod vocab)
    step_ok = ((out[:, 1:] - out[:, :-1]) % 13 == 2).mean()
    assert step_ok > 0.9, (step_ok, out[:2])


def test_temperature_sampling_reproducible():
    wf, toks = _lm_workflow(max_epochs=2)
    gen = LMGenerator(wf.trainer, max_len=16)
    a = gen.generate(toks[:2, :6], max_new=6, temperature=0.7, seed=3)
    b = gen.generate(toks[:2, :6], max_new=6, temperature=0.7, seed=3)
    np.testing.assert_array_equal(a, b)
    assert a.dtype == np.int32 and a.shape == (2, 12)


def test_rejects_overlong_prompt():
    wf, toks = _lm_workflow(max_epochs=0)
    gen = LMGenerator(wf.trainer, max_len=10)
    with pytest.raises(ValueError):
        gen.generate(toks[:2, :8], max_new=8)


def test_one_compile_per_batch_size():
    """Varying prompt lengths must reuse ONE compiled scan (prompt_len is
    traced) — a REST server sees arbitrary lengths per request."""
    wf, toks = _lm_workflow(max_epochs=0)
    gen = LMGenerator(wf.trainer, max_len=16)
    gen.generate(toks[:2, :4], max_new=2)
    gen.generate(toks[:2, :7], max_new=5)
    gen.generate(toks[:2, :10], max_new=1)
    assert len(gen._compiled) == 1, list(gen._compiled)


def test_compile_cache_is_bounded_lru():
    """Batch size is client-controlled over REST: the per-generator
    executable cache must evict, not grow without bound."""
    from veles_tpu.models import generate as gen_mod
    wf, toks = _lm_workflow(max_epochs=0)
    gen = LMGenerator(wf.trainer, max_len=16)
    cap = gen_mod.COMPILE_CACHE_SIZE
    for b in range(1, cap + 2):                  # cap + 1 distinct batches
        gen.generate(toks[:b, :4], max_new=2)
    assert len(gen._compiled) == cap, list(gen._compiled)
    assert 1 not in gen._compiled                # oldest evicted
    # recency, not FIFO: re-hit the current-oldest key, then insert one
    # more — the hit key must survive and the next-oldest must go
    gen.generate(toks[:2, :4], max_new=2)
    gen.generate(toks[: cap + 2, :4], max_new=2)
    assert 2 in gen._compiled
    assert 3 not in gen._compiled, list(gen._compiled)


def test_greedy_and_sampling_share_one_executable():
    """greedy is a traced per-row flag now — mixed request kinds at one
    batch size reuse a single compiled scan."""
    wf, toks = _lm_workflow(max_epochs=0)
    gen = LMGenerator(wf.trainer, max_len=16)
    gen.generate(toks[:2, :4], max_new=2)                     # greedy
    gen.generate(toks[:2, :4], max_new=2, temperature=0.8)    # sampling
    assert len(gen._compiled) == 1, list(gen._compiled)


def test_generate_batch_matches_solo_calls():
    """The serving coalescer's core invariant: a request's tokens are
    IDENTICAL whether it ran alone or merged into any batch (per-row
    params, per-(seed, position) sampling keys)."""
    wf, toks = _lm_workflow(max_epochs=8)
    gen = LMGenerator(wf.trainer, max_len=16)
    reqs = [
        (toks[0, :8],  {"max_new": 6}),                        # greedy
        (toks[1, :5],  {"max_new": 4, "temperature": 0.9,
                        "seed": 3}),
        (toks[2, :10], {"max_new": 3, "temperature": 0.7,
                        "top_k": 5, "seed": 11}),
        (toks[3, :6],  {"max_new": 8, "temperature": 1.1,
                        "top_p": 0.8, "seed": 4}),
    ]
    merged = gen.generate_batch([p for p, _ in reqs],
                                [o for _, o in reqs])
    for (prompt, opts), got in zip(reqs, merged):
        solo = gen.generate(prompt[None], **opts)[0]
        np.testing.assert_array_equal(got, solo)
    # and merging in a different order changes nothing either
    merged2 = gen.generate_batch([p for p, _ in reqs[::-1]],
                                 [o for _, o in reqs[::-1]])
    for a, b in zip(merged2, merged[::-1]):
        np.testing.assert_array_equal(a, b)


def test_top_k_and_top_p_sampling():
    """top_k=1 must equal greedy; top_p≈0 likewise; both reproducible."""
    wf, toks = _lm_workflow(max_epochs=6)
    gen = LMGenerator(wf.trainer, max_len=16)
    prompt = toks[:4, :8]
    greedy = gen.generate(prompt, max_new=6)
    k1 = gen.generate(prompt, max_new=6, temperature=0.9, top_k=1)
    np.testing.assert_array_equal(greedy, k1)
    p0 = gen.generate(prompt, max_new=6, temperature=0.9, top_p=1e-6)
    np.testing.assert_array_equal(greedy, p0)
    a = gen.generate(prompt, max_new=6, temperature=0.9, top_k=5,
                     top_p=0.9, seed=4)
    b = gen.generate(prompt, max_new=6, temperature=0.9, top_k=5,
                     top_p=0.9, seed=4)
    np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError):
        gen.generate(prompt, max_new=2, top_p=0.0)


def test_bf16_cache_dtype():
    wf, toks = _lm_workflow(max_epochs=4)
    import jax.numpy as jnp
    gen = LMGenerator(wf.trainer, max_len=16, cache_dtype=jnp.bfloat16)
    out = gen.generate(toks[:2, :8], max_new=4)
    assert out.shape == (2, 12)
    # bf16 cache vs f32 cache: same greedy continuation on this easy task
    ref = LMGenerator(wf.trainer, max_len=16).generate(toks[:2, :8],
                                                       max_new=4)
    np.testing.assert_array_equal(out, ref)


def test_int8_kv_cache_generation():
    """int8 KV cache (QuantCache): greedy continuation matches the f32
    cache on a trained model (quantization noise ≪ the logit margins),
    across the full-scan, prefill, and beam paths."""
    import jax.numpy as jnp

    t = 96
    wf, toks = _lm_workflow(max_epochs=8, t=t)
    gen8 = LMGenerator(wf.trainer, max_len=t, cache_dtype="int8")
    ref = LMGenerator(wf.trainer, max_len=t)
    # the cache really is int8 + scales
    c = gen8._init_caches(2, jnp.float32)
    assert c[0][0].data.dtype == jnp.int8
    assert c[0][0].scale.shape == (2, 4, t, 1)

    short = toks[:4, :8]                     # full-scan path
    np.testing.assert_array_equal(gen8.generate(short, max_new=6),
                                  ref.generate(short, max_new=6))
    long = toks[:4, :48]                     # chunked-prefill path
    np.testing.assert_array_equal(gen8.generate(long, max_new=8),
                                  ref.generate(long, max_new=8))
    bt8, _ = gen8.beam_search(long, max_new=5, beam=3)
    bt, _ = ref.beam_search(long, max_new=5, beam=3)
    np.testing.assert_array_equal(bt8, bt)
    # sampled decoding stays reproducible under quantization
    a = gen8.generate(long, max_new=6, temperature=0.8, seed=3)
    b = gen8.generate(long, max_new=6, temperature=0.8, seed=3)
    np.testing.assert_array_equal(a, b)


def test_sampling_params_do_not_recompile():
    """top_k/top_p are traced — distinct values reuse ONE executable."""
    wf, toks = _lm_workflow(max_epochs=0)
    gen = LMGenerator(wf.trainer, max_len=16)
    for tk, tp in ((0, 1.0), (5, 0.9), (3, 0.7), (8, 0.99)):
        gen.generate(toks[:2, :6], max_new=3, temperature=0.8,
                     top_k=tk, top_p=tp, seed=1)
    assert len(gen._compiled) == 1, list(gen._compiled)
    with pytest.raises(ValueError):
        gen.generate(toks[:2, :6], max_new=2, temperature=0.8, top_k=-1)
    with pytest.raises(ValueError):
        gen.generate(toks[:2, :6], max_new=2, temperature=0.8,
                     top_k=10 ** 6)


def test_beam_search_matches_greedy_at_beam1_and_scores_exactly():
    wf, toks = _lm_workflow(max_epochs=8)
    gen = LMGenerator(wf.trainer, max_len=16)
    prompt = toks[:4, :8]
    greedy = gen.generate(prompt, max_new=6)
    b1, s1 = gen.beam_search(prompt, max_new=6, beam=1)
    np.testing.assert_array_equal(b1, greedy)

    b4, s4 = gen.beam_search(prompt, max_new=6, beam=4)
    np.testing.assert_array_equal(b4[:, :8], prompt)
    # on this near-deterministic toy model the wider beam finds a
    # sequence at least as likely (NOT a beam-search guarantee in
    # general — pruning can lose the greedy prefix)
    assert (s4 >= s1 - 1e-4).all(), (s1, s4)

    # the returned score must equal the teacher-forced logprob of the
    # returned sequence (positions 8..13 predicted from 7..12)
    logits = gen.score(b4)                       # [B, T-1, V]
    logp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits), axis=-1))
    want = np.take_along_axis(
        logp[:, 7:13], b4[:, 8:14, None], axis=-1)[..., 0].sum(axis=1)
    np.testing.assert_allclose(s4, want, rtol=1e-4, atol=1e-4)

    with pytest.raises(ValueError):
        gen.beam_search(prompt, max_new=6, beam=0)


def test_tensor_parallel_decode_matches_single_device(f32_precision):
    """A model trained under a {model: 2} mesh decodes through the SAME
    sharded params (column-parallel projections, head-sharded KV caches);
    greedy tokens must match the single-device path and the full logits
    must agree to numerical tolerance (the psum over the contracted
    model axis reorders float adds)."""
    import jax
    from veles_tpu.parallel import MeshConfig, make_mesh

    mc = MeshConfig(make_mesh({"model": 2}, jax.devices()[:2]))
    wf, toks = _lm_workflow(max_epochs=10, mesh_config=mc,
                            n_kv_heads=2)
    gen_tp = LMGenerator(wf.trainer, max_len=16)        # auto: trainer mesh
    assert gen_tp.mesh_cfg is mc
    prompt = toks[:4, :8]
    out_tp = gen_tp.generate(prompt, max_new=6)

    # reference: identical training run without a mesh
    wf1, _ = _lm_workflow(max_epochs=10, n_kv_heads=2)
    gen1 = LMGenerator(wf1.trainer, max_len=16)
    assert gen1.mesh_cfg is None
    np.testing.assert_allclose(
        np.asarray(wf.trainer.params["l00_embedding"]["table"]),
        np.asarray(wf1.trainer.params["l00_embedding"]["table"]),
        rtol=1e-4, atol=1e-5)                          # same training
    out1 = gen1.generate(prompt, max_new=6)
    np.testing.assert_array_equal(out_tp, out1)
    np.testing.assert_allclose(gen_tp.score(toks[:2]), gen1.score(toks[:2]),
                               rtol=2e-3, atol=2e-3)
    # beam search rides the same sharded step
    bt, bs = gen_tp.beam_search(prompt, max_new=4, beam=3)
    b1, s1 = gen1.beam_search(prompt, max_new=4, beam=3)
    np.testing.assert_array_equal(bt, b1)
    np.testing.assert_allclose(bs, s1, rtol=1e-3, atol=1e-3)


def test_tensor_parallel_decode_rejects_indivisible_kv_heads():
    import jax
    from veles_tpu.parallel import MeshConfig, make_mesh

    mc = MeshConfig(make_mesh({"model": 4}, jax.devices()[:4]))
    wf, _ = _lm_workflow(max_epochs=0, mesh_config=mc, n_kv_heads=2)
    with pytest.raises(ValueError, match="divisible by the model axis"):
        LMGenerator(wf.trainer, max_len=16)


@pytest.mark.parametrize("zoo_kwargs", [
    {}, {"n_kv_heads": 2}, {"pos": "rope"}, {"window": 24}])
def test_chunked_prefill_matches_full_scan(zoo_kwargs, f32_precision):
    """Long prompts route through the parallel prefill + short
    generation scan; tokens must match the position-by-position full
    scan exactly — greedy, sampled, and near-max_len overshoot."""
    t = 96
    wf, toks = _lm_workflow(max_epochs=6, t=t, **zoo_kwargs)
    gen = LMGenerator(wf.trainer, max_len=t)
    assert gen.prefill_min <= 48       # prompts below DO use prefill

    ref = LMGenerator(wf.trainer, max_len=t)
    ref.prefill_min = 10 ** 9          # force the full scan

    prompt = toks[:4, :48]
    for kwargs in ({}, {"temperature": 0.8, "seed": 5},
                   {"temperature": 0.7, "top_k": 5, "seed": 2}):
        got = gen.generate(prompt, max_new=12, **kwargs)
        want = ref.generate(prompt, max_new=12, **kwargs)
        np.testing.assert_array_equal(got, want)
    assert any(isinstance(k, tuple) and k[0] == "pre"
               for k in gen._compiled), list(gen._compiled)
    assert all(not (isinstance(k, tuple) and k[0] == "pre")
               for k in ref._compiled), list(ref._compiled)

    # near-max_len: the power-of-two generation bucket overshoots past
    # the last position and must clamp idempotently
    got = gen.generate(toks[:2, :90], max_new=6)
    want = ref.generate(toks[:2, :90], max_new=6)
    np.testing.assert_array_equal(got, want)


def test_chunked_prefill_beam_search_matches_full_scan(f32_precision):
    """Beam search with a long prompt routes through ONE batch-wide
    prefill tiled across the beams — tokens and scores must match the
    beam-per-position full scan exactly, incl. generating right up to
    max_len (no overshoot headroom)."""
    t = 96
    wf, toks = _lm_workflow(max_epochs=6, t=t, n_kv_heads=2)
    gen = LMGenerator(wf.trainer, max_len=t)
    ref = LMGenerator(wf.trainer, max_len=t)
    ref.prefill_min = 10 ** 9
    for t0, max_new, beam in ((48, 10, 4), (40, 7, 3), (90, 6, 2)):
        got_t, got_s = gen.beam_search(toks[:3, :t0], max_new=max_new,
                                       beam=beam)
        want_t, want_s = ref.beam_search(toks[:3, :t0], max_new=max_new,
                                         beam=beam)
        np.testing.assert_array_equal(got_t, want_t)
        np.testing.assert_allclose(got_s, want_s, rtol=1e-6, atol=1e-6)
    assert any(isinstance(k, tuple) and k[0] == "beamgen"
               for k in gen._compiled), list(gen._compiled)


def test_chunked_prefill_bf16_cache_rope_parity(f32_precision):
    """The dtype-ordering trap: the cache must hold rope(k) computed in
    the CACHE dtype (mha_step's ordering) on both paths, or bf16-cache
    serving diverges between prefill and full scan."""
    import jax.numpy as jnp

    t = 96
    wf, toks = _lm_workflow(max_epochs=6, t=t, pos="rope")
    gen = LMGenerator(wf.trainer, max_len=t, cache_dtype=jnp.bfloat16)
    ref = LMGenerator(wf.trainer, max_len=t, cache_dtype=jnp.bfloat16)
    ref.prefill_min = 10 ** 9
    for kwargs in ({}, {"temperature": 0.8, "seed": 11}):
        got = gen.generate(toks[:3, :40], max_new=10, **kwargs)
        want = ref.generate(toks[:3, :40], max_new=10, **kwargs)
        np.testing.assert_array_equal(got, want)


def test_chunked_prefill_generate_batch_mixed_lengths(f32_precision):
    """Mixed prompt lengths: prefill covers the common prefix, the scan
    teacher-forces the longer prompts' tails — same tokens as the full
    scan for every row."""
    t = 96
    wf, toks = _lm_workflow(max_epochs=6, t=t)
    gen = LMGenerator(wf.trainer, max_len=t)
    ref = LMGenerator(wf.trainer, max_len=t)
    ref.prefill_min = 10 ** 9
    prompts = [toks[0, :40], toks[1, :64], toks[2, :52]]
    opts = [{"max_new": 10},
            {"max_new": 8, "temperature": 0.9, "seed": 3},
            {"max_new": 12, "temperature": 0.8, "top_k": 4, "seed": 9}]
    got = gen.generate_batch(prompts, opts)
    want = ref.generate_batch(prompts, opts)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_incremental_matches_full_forward_window(f32_precision):
    """Sliding-window stack: the KV-cache step must apply the same
    window mask the training forward uses."""
    wf, toks = _lm_workflow(max_epochs=0, window=5)
    gen = LMGenerator(wf.trainer, max_len=16)
    inc = gen.score(toks[:4])
    full = np.asarray(
        jax.jit(wf.trainer._forward, static_argnums=(2,))(
            wf.trainer.params, jnp.asarray(toks[:4]), False,
            jax.random.key(0)), np.float32)[:, :-1]
    np.testing.assert_allclose(inc, full, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("zoo_kwargs", [
    {}, {"n_kv_heads": 2, "pos": "rope"}])
def test_speculative_decode_matches_greedy(zoo_kwargs, f32_precision):
    """In-jit n-gram speculation is greedy-EXACT: identical tokens to
    generate() for any draft width, and on this repetitive corpus the
    round count proves multi-token acceptance actually happened."""
    t = 96
    wf, toks = _lm_workflow(max_epochs=8, t=t, **zoo_kwargs)
    gen = LMGenerator(wf.trainer, max_len=t)
    prompt = toks[:1, :48]
    want = gen.generate(prompt, max_new=20)
    for dk in (4, 8):
        got = gen.generate_speculative(prompt, max_new=20, draft_k=dk)
        np.testing.assert_array_equal(got, want)
    assert any(isinstance(k, tuple) and k[0] == "spec"
               for k in gen._compiled), list(gen._compiled)
    # UNTRAINED model: argmax never reproduces the prompt, so this
    # pins the teacher-forced tail (the bonus token must not overwrite
    # prompt positions) and true exactness, not corpus memorization
    wf0, toks0 = _lm_workflow(max_epochs=0, t=t, **zoo_kwargs)
    gen0 = LMGenerator(wf0.trainer, max_len=t)
    p0 = toks0[:1, :48]
    got0 = gen0.generate_speculative(p0, max_new=20, draft_k=8)
    np.testing.assert_array_equal(got0[:, :48], p0)   # prompt intact
    np.testing.assert_array_equal(got0, gen0.generate(p0, max_new=20))
    # fallbacks: batch > 1 and short prompts route to plain generate()
    np.testing.assert_array_equal(
        gen.generate_speculative(toks[:2, :48], max_new=4),
        gen.generate(toks[:2, :48], max_new=4))
    np.testing.assert_array_equal(
        gen.generate_speculative(toks[:1, :8], max_new=4),
        gen.generate(toks[:1, :8], max_new=4))
    with pytest.raises(ValueError, match="draft_k"):
        gen.generate_speculative(prompt, max_new=4, draft_k=1)


def test_rolling_window_cache_bounds_memory(f32_precision):
    """Sliding-window blocks get a ring-buffer cache of exactly
    ``window`` slots: serve-time KV memory is O(window) no matter how
    long the context — and generation still matches the training
    forward's window mask (score oracle) at positions far past the
    window."""
    import jax.numpy as jnp

    t, w = 96, 16
    wf, toks = _lm_workflow(max_epochs=6, t=t, window=w, pos="rope")
    gen = LMGenerator(wf.trainer, max_len=t)
    caches = gen._init_caches(2, jnp.float32)
    for ck, cv in caches:
        assert ck.shape == (2, 4, w, 8), ck.shape     # w slots, not t
    # logits match the full training forward (window mask) at every
    # position, incl. far beyond the window
    inc = gen.score(toks[:3])
    full = np.asarray(
        jax.jit(wf.trainer._forward, static_argnums=(2,))(
            wf.trainer.params, jnp.asarray(toks[:3]), False,
            jax.random.key(0)), np.float32)[:, :-1]
    np.testing.assert_allclose(inc, full, rtol=2e-3, atol=2e-3)
    # prefill path == full scan on the ring buffer, deep into the
    # context (prompt 11x the window)
    ref = LMGenerator(wf.trainer, max_len=t)
    ref.prefill_min = 10 ** 9
    for kwargs in ({}, {"temperature": 0.8, "seed": 7}):
        np.testing.assert_array_equal(
            gen.generate(toks[:3, :80], max_new=10, **kwargs),
            ref.generate(toks[:3, :80], max_new=10, **kwargs))
    # beam rides the ring too
    bt, bs = gen.beam_search(toks[:2, :70], max_new=6, beam=3)
    rt, rs = ref.beam_search(toks[:2, :70], max_new=6, beam=3)
    np.testing.assert_array_equal(bt, rt)
    np.testing.assert_allclose(bs, rs, rtol=1e-5, atol=1e-5)
    # int8 composes with the ring (QuantCache slots)
    gen8 = LMGenerator(wf.trainer, max_len=t, cache_dtype="int8")
    c8 = gen8._init_caches(2, jnp.float32)
    assert c8[0][0].data.shape == (2, 4, w, 8)
    np.testing.assert_array_equal(
        gen8.generate(toks[:3, :80], max_new=10),
        gen.generate(toks[:3, :80], max_new=10))
    # int8 + ring PREFILL == int8 + ring full scan (the in-chunk view
    # must be the quantized one everywhere, head positions included)
    ref8 = LMGenerator(wf.trainer, max_len=t, cache_dtype="int8")
    ref8.prefill_min = 10 ** 9
    for kwargs in ({}, {"temperature": 0.8, "seed": 5}):
        np.testing.assert_array_equal(
            gen8.generate(toks[:3, :80], max_new=10, **kwargs),
            ref8.generate(toks[:3, :80], max_new=10, **kwargs))


def test_generation_with_tied_embeddings(f32_precision):
    wf, toks = _lm_workflow(max_epochs=0, tie_embeddings=True)
    gen = LMGenerator(wf.trainer, max_len=16)
    inc = gen.score(toks[:4])
    full = np.asarray(
        jax.jit(wf.trainer._forward, static_argnums=(2,))(
            wf.trainer.params, jnp.asarray(toks[:4]), False,
            jax.random.key(0)), np.float32)[:, :-1]
    np.testing.assert_allclose(inc, full, rtol=2e-4, atol=2e-4)
    # temperature sampling path (logit scaling, not weight scaling)
    a = gen.generate(toks[:2, :6], max_new=4, temperature=0.8, seed=2)
    b = gen.generate(toks[:2, :6], max_new=4, temperature=0.8, seed=2)
    np.testing.assert_array_equal(a, b)


class TestInt8ServingWeights:
    """weights="int8" (ops.quant W8A8-dynamic): the serving params become
    int8 + scales, decode still works end to end, and the quantized
    logits track the float ones within quantization error."""

    def test_quant_ops_precision(self):
        from veles_tpu.ops import quant
        r = np.random.RandomState(0)
        x = jnp.asarray(r.randn(4, 32), jnp.float32)
        w = jnp.asarray(r.randn(32, 48), jnp.float32) * 0.2
        qw = quant.quantize_weight(w)
        assert qw.q.dtype == jnp.int8 and qw.scale.shape == (48,)
        y, ref = quant.int8_matmul(x, qw), x @ w
        err = float(jnp.max(jnp.abs(y - ref)))
        assert err < 0.05 * float(jnp.max(jnp.abs(ref))), err
        # per-row table: gathered rows dequantize near-exactly and the
        # transposed direction (tied head) matches x @ tableT
        table = jnp.asarray(r.randn(13, 32), jnp.float32)
        qt = quant.quantize_weight(table, axis=1)
        rows = quant.take_rows(qt, jnp.asarray([0, 5, 12]))
        np.testing.assert_allclose(np.asarray(rows),
                                   np.asarray(table)[[0, 5, 12]],
                                   rtol=0.02, atol=0.02)
        yt = quant.int8_matmul_t(x, qt)
        reft = x @ table.T
        assert float(jnp.max(jnp.abs(yt - reft))) < \
            0.05 * float(jnp.max(jnp.abs(reft)))

    @pytest.mark.parametrize("zoo_kwargs", [
        {"pos": "rope", "n_kv_heads": 2}, {"tie_embeddings": True}])
    def test_int8_decode_tracks_float(self, zoo_kwargs, f32_precision):
        wf, toks = _lm_workflow(max_epochs=8, **zoo_kwargs)
        gen_f = LMGenerator(wf.trainer, max_len=16)
        gen_q = LMGenerator(wf.trainer, max_len=16, weights="int8")
        from veles_tpu.ops import quant
        flat = jax.tree_util.tree_leaves(
            gen_q.params, is_leaf=lambda x: isinstance(x,
                                                       quant.QuantWeight))
        assert any(isinstance(leaf, quant.QuantWeight) for leaf in flat)
        # per-position scores within quantization error of the float path
        sq = gen_q.score(toks[:4])
        sf = gen_f.score(toks[:4])
        scale = np.abs(sf).max()
        assert np.max(np.abs(sq - sf)) < 0.08 * scale
        # greedy decode runs, is deterministic, and (trained model,
        # peaked logits) matches the float continuation
        a = gen_q.generate(toks[:4, :8], max_new=6)
        b = gen_q.generate(toks[:4, :8], max_new=6)
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(
            a, gen_f.generate(toks[:4, :8], max_new=6))

    def test_bf16_serving_weights(self, f32_precision):
        """weights="bf16": the whole float tree casts down (halved
        decode weight traffic), scores stay close, decode matches the
        float continuation on a trained model."""
        wf, toks = _lm_workflow(max_epochs=8)
        gen_f = LMGenerator(wf.trainer, max_len=16)
        gen_h = LMGenerator(wf.trainer, max_len=16, weights="bf16")
        table = gen_h.params[gen_h._embed.name]["table"]
        assert table.dtype == jnp.bfloat16
        sf, sh = gen_f.score(toks[:4]), gen_h.score(toks[:4])
        assert np.max(np.abs(sh - sf)) < 0.05 * np.abs(sf).max()
        np.testing.assert_array_equal(
            gen_h.generate(toks[:4, :8], max_new=6),
            gen_f.generate(toks[:4, :8], max_new=6))

    def test_int8_tensor_parallel_decode(self, f32_precision):
        """int8 serving under a model-axis mesh (the lifted
        restriction): the int8 payload is re-placed with the sharding
        of the float weight it replaces, scales replicated — and the
        sharded decode must produce the single-device int8 decode's
        tokens."""
        from veles_tpu.parallel import MeshConfig, make_mesh
        mc = MeshConfig(make_mesh({"model": 2}, jax.devices()[:2]))
        wf, toks = _lm_workflow(max_epochs=10, mesh_config=mc,
                                n_kv_heads=2)
        gen_tp = LMGenerator(wf.trainer, max_len=16, weights="int8")
        assert gen_tp.mesh_cfg is mc
        # payload sharded like the original weight, scales replicated
        from veles_tpu.ops import quant
        qw = gen_tp.params["l02_transformer_block"]["mha"]["wq"]
        assert isinstance(qw, quant.QuantWeight)
        orig = wf.trainer.params["l02_transformer_block"]["mha"]["wq"]
        assert qw.q.sharding == orig.sharding
        assert qw.scale.sharding.is_fully_replicated
        wf1, _ = _lm_workflow(max_epochs=10, n_kv_heads=2)
        gen1 = LMGenerator(wf1.trainer, max_len=16, weights="int8")
        prompt = toks[:4, :8]
        np.testing.assert_array_equal(gen_tp.generate(prompt, max_new=6),
                                      gen1.generate(prompt, max_new=6))

    def test_quant_weight_guards(self):
        from veles_tpu.parallel import MeshConfig, make_mesh
        wf, _ = _lm_workflow(max_epochs=0, n_kv_heads=2)
        with pytest.raises(ValueError, match="int8"):
            LMGenerator(wf.trainer, max_len=16, weights="int4")
        mc = MeshConfig(make_mesh({"model": 2}, jax.devices()[:2]))
        # w4a8 keeps the single-device restriction (the nibble-packed
        # payload halves the contraction axis — training specs don't
        # describe it)
        with pytest.raises(ValueError, match="single-device"):
            LMGenerator(wf.trainer, max_len=16, mesh_cfg=mc,
                        weights="w4a8")
        wf_moe, _ = _lm_workflow(max_epochs=0, n_experts=2)
        with pytest.raises(ValueError, match="MoE"):
            LMGenerator(wf_moe.trainer, max_len=16, weights="int8")
        with pytest.raises(ValueError, match="MoE"):
            LMGenerator(wf_moe.trainer, max_len=16, weights="w4a8")


class TestContinuousBatching:
    @pytest.mark.parametrize("ticks_per_dispatch,chunked_prefill",
                             [(1, True), (4, True), (1, False), (4, False)])
    def test_staggered_requests_match_solo_greedy(self, f32_precision,
                                                  ticks_per_dispatch,
                                                  chunked_prefill):
        """In-flight batching: requests submitted at DIFFERENT ticks,
        sharing the slot pool mid-decode, must produce exactly the solo
        greedy continuation — slot placement and neighbors are
        invisible (the continuous-batching correctness contract), at
        per-token admission AND with K engine ticks fused into one
        dispatch (rows freeze in-jit at their budget)."""
        from veles_tpu.models.generate import ContinuousBatcher
        wf, toks = _lm_workflow(max_epochs=8)
        gen = LMGenerator(wf.trainer, max_len=16)
        cb = ContinuousBatcher(gen, slots=3,
                               ticks_per_dispatch=ticks_per_dispatch,
                               chunked_prefill=chunked_prefill)

        prompts = [toks[0, :4].tolist(), toks[1, :6].tolist(),
                   toks[2, :3].tolist(), toks[3, :5].tolist()]
        max_news = [8, 6, 9, 7]
        rids = [cb.submit(prompts[0], max_news[0]),
                cb.submit(prompts[1], max_news[1])]
        for _ in range(3):            # run partway before more arrive
            cb.tick()
        rids.append(cb.submit(prompts[2], max_news[2]))
        cb.tick()
        rids.append(cb.submit(prompts[3], max_news[3]))  # queues: 3 slots
        cb.run_all()

        for rid, prompt, max_new in zip(rids, prompts, max_news):
            got = cb.result(rid)
            want = gen.generate(np.asarray([prompt], np.int32),
                                max_new)[0].tolist()
            assert got == want, (rid, got, want)

    def test_sliding_window_model_rides_the_pool(self, f32_precision):
        """Rolling ring-buffer caches through the batcher: the prefill
        chunk rounds DOWN (ring slots must never hold a position past
        the cursor) and the tick's prompt-forcing finishes admission —
        outputs still match the solo generator."""
        from veles_tpu.models.generate import ContinuousBatcher
        wf, toks = _lm_workflow(max_epochs=8, window=6, impl="flash")
        gen = LMGenerator(wf.trainer, max_len=16)
        cb = ContinuousBatcher(gen, slots=2, ticks_per_dispatch=2)
        rids = [cb.submit(toks[i, :5].tolist(), 7) for i in range(3)]
        cb.run_all()
        for i, rid in enumerate(rids):
            want = gen.generate(toks[i:i + 1, :5], 7)[0].tolist()
            assert cb.result(rid) == want, (i, cb.result(rid), want)

    def test_slot_reuse_and_queueing(self, f32_precision):
        """More requests than slots: the queue drains through freed
        slots; every request completes with its own continuation."""
        from veles_tpu.models.generate import ContinuousBatcher
        wf, toks = _lm_workflow(max_epochs=8)
        gen = LMGenerator(wf.trainer, max_len=16)
        cb = ContinuousBatcher(gen, slots=2)
        rids = [cb.submit(toks[i, :4].tolist(), 5) for i in range(5)]
        cb.run_all()
        assert cb.idle()
        for i, rid in enumerate(rids):
            want = gen.generate(toks[i:i + 1, :4], 5)[0].tolist()
            assert cb.result(rid) == want

    def test_temperature_rows_deterministic_per_seed(self, f32_precision):
        """A sampled row's draws depend only on (seed, position) — the
        same request replayed alone reproduces its tokens."""
        from veles_tpu.models.generate import ContinuousBatcher
        wf, toks = _lm_workflow(max_epochs=8)
        gen = LMGenerator(wf.trainer, max_len=16)
        cb1 = ContinuousBatcher(gen, slots=3)
        r1 = cb1.submit(toks[0, :4].tolist(), 6, temperature=0.8, seed=7)
        cb1.submit(toks[1, :5].tolist(), 6)       # a neighbor
        cb1.run_all()
        cb2 = ContinuousBatcher(gen, slots=1)     # alone, different slot
        r2 = cb2.submit(toks[0, :4].tolist(), 6, temperature=0.8, seed=7)
        cb2.run_all()
        assert cb1.result(r1) == cb2.result(r2)
        # and BOTH match the solo generator's sampled path — the
        # batcher's key derivation cannot drift without this tripping
        want = gen.generate(toks[:1, :4], 6, temperature=0.8,
                            seed=7)[0].tolist()
        assert cb1.result(r1) == want


class TestPagedKV:
    """Block-table KV pool (PagedContinuousBatcher): exact parity with
    the dense batcher, memory scaling with the pool budget instead of
    slots x max_len, admission backpressure on pool exhaustion, and the
    guard rails."""

    def _run(self, cb, gen, toks):
        rids = [cb.submit(toks[0, :4].tolist(), 8),
                cb.submit(toks[1, :6].tolist(), 6,
                          temperature=0.7, seed=11)]
        for _ in range(3):
            cb.tick()
        rids.append(cb.submit(toks[2, :3].tolist(), 9))
        cb.run_all()
        return [cb.pop_result(r) for r in rids]

    @pytest.mark.parametrize("ticks_per_dispatch", [1, 4])
    def test_matches_dense_batcher_exactly(self, f32_precision,
                                           ticks_per_dispatch):
        from veles_tpu.models.generate import (ContinuousBatcher,
                                               PagedContinuousBatcher)
        wf, toks = _lm_workflow(max_epochs=8)
        gen = LMGenerator(wf.trainer, max_len=16)
        dense = self._run(ContinuousBatcher(
            gen, slots=3, ticks_per_dispatch=ticks_per_dispatch),
            gen, toks)
        paged = self._run(PagedContinuousBatcher(
            gen, slots=3, ticks_per_dispatch=ticks_per_dispatch,
            block=4, pool_tokens=48), gen, toks)
        assert paged == dense
        # and both match the solo generator (greedy rows)
        want = gen.generate(toks[:1, :4], 8)[0].tolist()
        assert paged[0] == want

    def test_pool_backpressure_and_block_accounting(self, f32_precision):
        """A pool too small for all requests at once still completes
        every request (queued ones wait for freed blocks), and every
        block returns to the free list."""
        from veles_tpu.models.generate import (ContinuousBatcher,
                                               PagedContinuousBatcher)
        wf, toks = _lm_workflow(max_epochs=8)
        gen = LMGenerator(wf.trainer, max_len=16)
        cb = PagedContinuousBatcher(gen, slots=3, block=4,
                                    pool_tokens=16)   # 4 blocks total
        assert cb.free_blocks() == 4
        rids = [cb.submit(toks[i, :4].tolist(), 8) for i in range(3)]
        # 12 tokens/request = 3 blocks: only ONE fits at a time
        cb.tick()
        assert sum(r is not None for r in cb._slot_req) == 1
        cb.run_all()
        dense = ContinuousBatcher(gen, slots=3)
        for r in rids:
            dense.submit(toks[rids.index(r), :4].tolist(), 8)
        dense.run_all()
        for i, rid in enumerate(rids):
            assert cb.pop_result(rid) == dense.pop_result(i)
        assert cb.free_blocks() == 4          # all blocks returned

    def test_pool_memory_scales_with_budget_not_slots(self,
                                                      f32_precision):
        wf, toks = _lm_workflow(max_epochs=0)
        gen = LMGenerator(wf.trainer, max_len=16)
        from veles_tpu.models.generate import (ContinuousBatcher,
                                               PagedContinuousBatcher)
        dense = ContinuousBatcher(gen, slots=8)
        paged = PagedContinuousBatcher(gen, slots=8, block=4,
                                       pool_tokens=32)
        db = sum(l.nbytes for l in
                 jax.tree_util.tree_leaves(dense._caches))
        pb = sum(l.nbytes for l in
                 jax.tree_util.tree_leaves(paged._pool))
        # 8 slots x 16 tokens dense vs 32-token budget (+1 dummy block)
        assert pb <= db * (32 + 4) / (8 * 16) + 1e-9, (db, pb)

    def test_guard_rails(self, f32_precision):
        from veles_tpu.models.generate import PagedContinuousBatcher
        wf, _ = _lm_workflow(max_epochs=0)
        gen = LMGenerator(wf.trainer, max_len=16)
        with pytest.raises(ValueError, match="block"):
            PagedContinuousBatcher(gen, block=5)      # 16 % 5 != 0
        # a sliding-window model is served: its layers keep a ring of
        # pool blocks a slot, and the streams are the dense batcher's
        # rolling-window ones
        from veles_tpu.models.generate import ContinuousBatcher
        wfw, toks = _lm_workflow(max_epochs=8, window=6, impl="flash")
        genw = LMGenerator(wfw.trainer, max_len=16)
        cb = PagedContinuousBatcher(genw, slots=3, block=2,
                                    pool_tokens=48)
        assert cb.ring_blocks == (8,)     # no more than max_len holds
        assert self._run(cb, genw, toks) == \
            self._run(ContinuousBatcher(genw, slots=3), genw, toks)
        assert cb.blocks_in_use() == (0, 0)
        with pytest.raises(ValueError, match="prefix_cache cannot serve"):
            PagedContinuousBatcher(genw, block=2, prefix_cache=True)

    def test_there_is_one_tick_and_no_switch(self, f32_precision):
        """The paged batcher has one tick (pool read through the block
        table inside the Pallas kernel — no dense gather) and no
        argument that chooses another; its token streams are the dense
        batcher's, the reference the gather tick was itself checked
        against."""
        from veles_tpu.models.generate import (ContinuousBatcher,
                                               PagedContinuousBatcher)
        wf, toks = _lm_workflow(max_epochs=8)
        gen = LMGenerator(wf.trainer, max_len=16)
        for value in (False, True):
            with pytest.raises(TypeError, match="fused"):
                PagedContinuousBatcher(gen, slots=3, block=4,
                                       pool_tokens=48, fused=value)
        cb = PagedContinuousBatcher(gen, slots=3, block=4,
                                    pool_tokens=48)
        assert cb.fused is True
        assert self._run(cb, gen, toks) == \
            self._run(ContinuousBatcher(gen, slots=3), gen, toks)

    def test_fused_rope_gqa_model(self, f32_precision):
        """Per-row rope rotation + GQA grouping through the fused
        path: every slot decodes at its own depth, so a broadcast
        position bug would corrupt exactly these streams."""
        from veles_tpu.models.generate import (ContinuousBatcher,
                                               PagedContinuousBatcher)
        wf, toks = _lm_workflow(max_epochs=8, pos="rope",
                                n_kv_heads=2)
        gen = LMGenerator(wf.trainer, max_len=16)
        dense = self._run(ContinuousBatcher(gen, slots=3), gen, toks)
        cb = PagedContinuousBatcher(gen, slots=3, block=4,
                                    pool_tokens=48)
        assert cb.fused
        assert self._run(cb, gen, toks) == dense

    def test_window_ge_max_len_is_served_as_a_full_layer(
            self, f32_precision):
        """window >= max_len never bites: its layers are of the
        whole-context group (no ring, the pool is the one-group pool)
        and the streams are the dense batcher's."""
        from veles_tpu.models.generate import (ContinuousBatcher,
                                               PagedContinuousBatcher)
        wf, toks = _lm_workflow(max_epochs=8, window=16, impl="flash")
        gen = LMGenerator(wf.trainer, max_len=16)
        cb = PagedContinuousBatcher(gen, slots=3, block=4,
                                    pool_tokens=48)
        assert cb.ring_blocks == () and len(cb._caches) == 2
        assert self._run(cb, gen, toks) == \
            self._run(ContinuousBatcher(gen, slots=3), gen, toks)

    def test_quant_pool_runs_fused_kernel(self, f32_precision):
        """int8 KV pools (QuantCache leaves) now run the fused
        kernel's QUANTIZED variant — int8 tiles streamed from HBM,
        dequantized in kernel with f32 accumulation — and the token
        streams must still match the dense int8 batcher (same math,
        narrower wire)."""
        from veles_tpu.models.generate import (ContinuousBatcher,
                                               PagedContinuousBatcher)
        wf, toks = _lm_workflow(max_epochs=8)
        gen = LMGenerator(wf.trainer, max_len=16, cache_dtype="int8")
        cb = PagedContinuousBatcher(gen, slots=3, block=4,
                                    pool_tokens=48)
        assert cb.fused                       # quantized kernel path
        dense = self._run(ContinuousBatcher(gen, slots=3), gen, toks)
        assert self._run(cb, gen, toks) == dense

    def test_engine_metrics_expose_free_blocks(self, f32_precision):
        from veles_tpu.services.restful import ContinuousEngine
        wf, toks = _lm_workflow(max_epochs=0)
        gen = LMGenerator(wf.trainer, max_len=16)
        eng = ContinuousEngine(gen, slots=2, paged_block=4,
                               pool_tokens=32)
        try:
            eng.submit(toks[0, :4].tolist(), 4)
            m = eng.metrics()
            assert m["free_kv_blocks"] == 8   # all returned post-serve
        finally:
            eng.stop()


class TestSpeculativeTicks:
    """Speculative continuous batching (speculative_k > 0): every
    active row verifies up to k drafted tokens per tick.  The bar is
    EXACT stream equality with the 1-token pool across greedy,
    sampled, and mid-flight-prompt rows — speculation may only change
    how many ticks a stream takes, never its tokens."""

    def _run(self, cb, toks):
        rids = [cb.submit(toks[0, :4].tolist(), 8),
                cb.submit(toks[1, :6].tolist(), 4,
                          temperature=0.7, seed=11)]
        for _ in range(2):
            cb.tick()
        rids.append(cb.submit(toks[2, :3].tolist(), 7))
        cb.run_all()
        return [cb.pop_result(r) for r in rids]

    @pytest.mark.parametrize("ticks_per_dispatch", [1, 4])
    def test_exact_parity_with_one_token_pool(self, f32_precision,
                                              ticks_per_dispatch):
        from veles_tpu.models.generate import ContinuousBatcher
        wf, toks = _lm_workflow(max_epochs=8)
        gen = LMGenerator(wf.trainer, max_len=16)
        plain = self._run(ContinuousBatcher(
            gen, slots=3, ticks_per_dispatch=ticks_per_dispatch),
            toks)
        spec = self._run(ContinuousBatcher(
            gen, slots=3, ticks_per_dispatch=ticks_per_dispatch,
            speculative_k=4), toks)
        assert spec == plain
        # and the greedy stream matches the solo generator
        assert spec[0] == gen.generate(toks[:1, :4], 8)[0].tolist()

    def test_speculation_actually_accelerates(self, f32_precision):
        """On a periodic LM (vocab 5: the ramp's bigrams repeat inside
        the context, so drafts copy a whole earlier cycle), the spec
        pool must finish in FEWER ticks — otherwise the chunk verify
        is dead weight."""
        from veles_tpu.models.generate import ContinuousBatcher
        wf, toks = _lm_workflow(max_epochs=8, vocab=5)
        gen = LMGenerator(wf.trainer, max_len=16)

        def count(cb):
            rid = cb.submit(toks[0, :6].tolist(), 6)
            n = 0
            while not cb.idle():
                cb.tick()
                n += 1
            return n, cb.pop_result(rid)

        n1, out1 = count(ContinuousBatcher(gen, slots=1))
        nk, outk = count(ContinuousBatcher(gen, slots=1,
                                           speculative_k=4))
        assert outk == out1
        assert nk < n1, (nk, n1)

    def test_guard_rails(self, f32_precision):
        from veles_tpu.models.generate import (ContinuousBatcher,
                                               PagedContinuousBatcher)
        wf, toks = _lm_workflow(max_epochs=0)
        gen = LMGenerator(wf.trainer, max_len=16)
        cb = ContinuousBatcher(gen, slots=2, speculative_k=4)
        with pytest.raises(ValueError, match="speculative"):
            cb.submit(toks[0, :8].tolist(), 8)    # 8+8+4 > 16
        with pytest.raises(ValueError, match="dense-pool only"):
            PagedContinuousBatcher(gen, block=4, speculative_k=4)
        with pytest.raises(ValueError, match="\\[2, 64\\]"):
            ContinuousBatcher(gen, speculative_k=1)
        with pytest.raises(ValueError, match="no room"):
            ContinuousBatcher(gen, speculative_k=15)   # 15+2 > 16
        from veles_tpu.services.restful import ContinuousEngine
        with pytest.raises(ValueError, match="dense-pool only"):
            # the engine must FORWARD the knob so the paged guard
            # fires instead of silently serving without speculation
            ContinuousEngine(gen, slots=2, paged_block=4,
                             pool_tokens=32, speculative_k=4)
        wfw, _ = _lm_workflow(max_epochs=0, window=6, impl="flash")
        genw = LMGenerator(wfw.trainer, max_len=16)
        with pytest.raises(ValueError, match="linear"):
            ContinuousBatcher(genw, speculative_k=4)

    def test_adapter_routing_through_spec_ticks(self, f32_precision):
        """Adapter grafting rides the chunk verify too: a banked model
        through the spec pool must match the plain pool per adapter."""
        from veles_tpu.models.generate import ContinuousBatcher
        wf, toks = _lm_workflow(max_epochs=8)
        wf2, _ = _lm_workflow(max_epochs=8, seed=77)
        # bank needs lora-shaped adapters — reuse the lora fixture
        # machinery cheaply: train a rank-2 adapter on wf's base
        from veles_tpu.models import zoo
        from veles_tpu.loader.fullbatch import FullBatchLoader
        from veles_tpu.models.standard_workflow import StandardWorkflow
        prng.seed_all(31)
        r = np.random.RandomState(5)
        toks2 = ((np.arange(16)[None, :] * 3
                  + r.randint(0, 4, 192)[:, None]) % 13).astype(
                      np.int32)
        loader = FullBatchLoader(None, data=toks2, labels=toks2,
                                 minibatch_size=48,
                                 class_lengths=[0, 48, 144])
        awf = StandardWorkflow(
            layers=zoo.transformer_lm(vocab_size=13, d_model=32,
                                      n_heads=4, n_layers=2, lr=5e-2,
                                      dropout=0.0, lora_rank=2),
            loader=loader, loss="lm",
            decision_config={"max_epochs": 6}, name="spec-adapter")
        awf.initialize()
        awf.warm_start({"params": wf.trainer.host_params()})
        awf.run()
        gen = LMGenerator(wf.trainer, max_len=16)
        gen.load_adapter_bank([awf.trainer.host_params()])
        prompt = toks[0, :4].tolist()

        def run(cb):
            rids = [cb.submit(prompt, 7, adapter=a) for a in (0, 1)]
            cb.run_all()
            return [cb.pop_result(x) for x in rids]

        plain = run(ContinuousBatcher(gen, slots=2))
        spec = run(ContinuousBatcher(gen, slots=2, speculative_k=4))
        assert spec == plain
        assert plain[0] != plain[1]       # routing genuinely distinct


@pytest.mark.parametrize("speculative_k", [0, 4])
def test_stream_partials_progress_and_cleanup(f32_precision,
                                              speculative_k):
    """partial(rid) grows monotonically tick by tick along the final
    result's prefix, and is dropped at completion (long-running
    servers must not accumulate).  Holds under speculative ticks too
    (multi-token jumps per update)."""
    from veles_tpu.models.generate import ContinuousBatcher
    wf, toks = _lm_workflow(max_epochs=8)
    gen = LMGenerator(wf.trainer, max_len=16)
    cb = ContinuousBatcher(gen, slots=2, speculative_k=speculative_k)
    rid = cb.submit(toks[0, :4].tolist(), 6)
    seen = []
    while not cb.idle():
        cb.tick()
        p = cb.partial(rid)
        if p is not None:
            assert not seen or p[:len(seen[-1])] == seen[-1]
            seen.append(list(p))
    want = gen.generate(toks[:1, :4], 6)[0].tolist()
    assert cb.pop_result(rid) == want
    assert seen and seen[-1] == want[:len(seen[-1])]
    assert len(seen) >= 3                  # genuinely incremental
    assert cb.partial(rid) is None         # dropped at completion


def test_engine_fused_dispatch_serves_identical_streams(f32_precision):
    """ticks_per_dispatch>1 through the ENGINE (the remote-device
    throughput knob), on BOTH batcher flavors: responses — buffered
    AND streamed — must be identical to the per-token engine."""
    from veles_tpu.services.restful import ContinuousEngine
    wf, toks = _lm_workflow(max_epochs=8)
    gen = LMGenerator(wf.trainer, max_len=16)
    e1 = ContinuousEngine(gen, slots=2)
    e4 = ContinuousEngine(gen, slots=2, ticks_per_dispatch=4)
    e4p = ContinuousEngine(gen, slots=2, paged_block=4,
                           pool_tokens=48, ticks_per_dispatch=4)
    try:
        p = toks[0, :4].tolist()
        assert e4.cb.ticks_per_dispatch == 4      # dense wiring
        assert e4p.cb.ticks_per_dispatch == 4     # paged wiring
        a = list(map(int, e1.submit(p, 7)))
        assert a == list(map(int, e4.submit(p, 7)))
        assert a == list(map(int, e4p.submit(p, 7)))
        sa = [c for ch in e1.stream(p, 7) for c in ch]
        sb = [c for ch in e4.stream(p, 7) for c in ch]
        assert sa == sb == a[len(p):]
    finally:
        e1.stop(); e4.stop(); e4p.stop()


class TestPrefixCache:
    """Copy-on-write prefix sharing in the paged pool: concurrent
    requests with a common prompt prefix share its KV blocks.  The
    bar: token streams stay EXACTLY the no-sharing batcher's, block
    accounting reflects the sharing, and every block returns to the
    free list when the last owner releases."""

    def _mk(self, **kw):
        from veles_tpu.models.generate import PagedContinuousBatcher
        wf, toks = _lm_workflow(max_epochs=8)
        gen = LMGenerator(wf.trainer, max_len=16)
        cb = PagedContinuousBatcher(gen, slots=3, block=4,
                                    pool_tokens=48, prefix_cache=True,
                                    **kw)
        return cb, gen, toks

    @pytest.mark.parametrize("ticks_per_dispatch", [1, 4])
    def test_shared_prefix_tokens_and_accounting(self, f32_precision,
                                                 ticks_per_dispatch):
        from veles_tpu.models.generate import PagedContinuousBatcher
        cb, gen, toks = self._mk(ticks_per_dispatch=ticks_per_dispatch)
        base = PagedContinuousBatcher(
            gen, slots=3, block=4, pool_tokens=48,
            ticks_per_dispatch=ticks_per_dispatch)
        # 9-token prompt, block 4: blocks 0-1 end before position
        # plen-1=8 (the first decode write) -> 2 shareable blocks
        prompt = toks[0, :9].tolist()
        # 13 or 16 tokens -> 4 blocks, and more new tokens than one
        # dispatch decodes: both rows still hold their blocks after it
        max_new = 3 + ticks_per_dispatch
        free0 = cb.free_blocks()
        r1 = cb.submit(prompt, max_new)
        r2 = cb.submit(prompt, max_new)
        cb.tick()                             # both admitted
        # 4 + 4 blocks without sharing; 2 shared -> 6 allocated
        assert free0 - cb.free_blocks() == 6
        cb.run_all()
        b1 = base.submit(prompt, max_new)
        b2 = base.submit(prompt, max_new)
        base.run_all()
        assert cb.pop_result(r1) == base.pop_result(b1)
        assert cb.pop_result(r2) == base.pop_result(b2)
        assert cb.free_blocks() == free0      # all returned

    def test_divergent_second_block_shares_first_only(self,
                                                      f32_precision):
        cb, gen, toks = self._mk()
        p1 = toks[0, :9].tolist()
        p2 = list(p1[:4]) + toks[1, 4:9].tolist()
        assert p1[:4] == p2[:4] and p1[4:8] != p2[4:8]
        free0 = cb.free_blocks()
        r1 = cb.submit(p1, 4)
        r2 = cb.submit(p2, 4)
        cb.tick()
        # 4 + 4 blocks; of the 2 shareable only block 0 matches (the
        # prompts diverge inside block 1) -> 7 allocated
        assert free0 - cb.free_blocks() == 7
        cb.run_all()
        # each stream matches its own solo decode
        assert cb.pop_result(r1) == gen.generate(
            np.asarray([p1], np.int32), 4)[0].tolist()
        assert cb.pop_result(r2) == gen.generate(
            np.asarray([p2], np.int32), 4)[0].tolist()
        assert cb.free_blocks() == free0

    def test_release_order_keeps_shared_blocks_alive(self,
                                                     f32_precision):
        """First sharer finishes while the second still decodes — the
        shared blocks must survive until the LAST owner releases."""
        cb, gen, toks = self._mk()
        prompt = toks[0, :8].tolist()
        free0 = cb.free_blocks()
        r1 = cb.submit(prompt, 2)             # finishes first
        r2 = cb.submit(prompt, 6)
        cb.run_all()
        assert cb.pop_result(r2) == gen.generate(
            np.asarray([prompt], np.int32), 6)[0].tolist()
        assert cb.pop_result(r1) == gen.generate(
            np.asarray([prompt], np.int32), 2)[0].tolist()
        assert cb.free_blocks() == free0
        assert not cb._prefix_reg and not cb._prefix_ref

    def test_shorter_sharer_never_writes_a_shared_block(self,
                                                        f32_precision):
        """Sharers with DIFFERENT prompt lengths: a 12-token owner
        registers blocks 0-1, but an 8-token sharer's first decode
        write lands at position 7 — inside block 1 — so it may match
        block 0 ONLY.  (The regression: matching by coverage alone
        would let it write into the shared block.)"""
        cb, gen, toks = self._mk()
        pa = toks[0, :12].tolist()
        pb = pa[:8]
        free0 = cb.free_blocks()
        ra = cb.submit(pa, 3)                 # 15 tokens -> 4 blocks
        rb = cb.submit(pb, 4)                 # 12 tokens -> 3 blocks
        cb.tick()
        # 4 + 3 minus exactly ONE shared (block 0) -> 6 allocated
        assert free0 - cb.free_blocks() == 6
        cb.run_all()
        assert cb.pop_result(ra) == gen.generate(
            np.asarray([pa], np.int32), 3)[0].tolist()
        assert cb.pop_result(rb) == gen.generate(
            np.asarray([pb], np.int32), 4)[0].tolist()
        assert cb.free_blocks() == free0

    def test_matched_admission_skips_the_prefix_forward(
            self, f32_precision):
        """The compute-skip contract: a second same-prefix request must
        admit through the RESUME path (chunk from the matched
        boundary), never re-run the full prompt prefill — and still
        produce the exact no-sharing stream (covered above; here we
        pin WHICH path ran)."""
        cb, gen, toks = self._mk()
        prompt = toks[0, :9].tolist()
        calls = {"full": 0, "resume": 0}
        orig_full, orig_res = gen._prefill_fn, gen._prefill_resume_fn

        def spy_full(*a, **k):
            calls["full"] += 1
            return orig_full(*a, **k)

        def spy_res(*a, **k):
            calls["resume"] += 1
            return orig_res(*a, **k)

        gen._prefill_fn, gen._prefill_resume_fn = spy_full, spy_res
        try:
            r1 = cb.submit(prompt, 3)
            r2 = cb.submit(prompt, 3)
            cb.run_all()
        finally:
            gen._prefill_fn, gen._prefill_resume_fn = (orig_full,
                                                       orig_res)
        assert calls == {"full": 1, "resume": 1}, calls
        assert cb.pop_result(r1) == cb.pop_result(r2)

    def test_engine_exposes_prefix_gauges(self, f32_precision):
        from veles_tpu.services.restful import ContinuousEngine
        wf, toks = _lm_workflow(max_epochs=0)
        gen = LMGenerator(wf.trainer, max_len=16)
        eng = ContinuousEngine(gen, slots=2, paged_block=4,
                               pool_tokens=48, prefix_cache=True)
        try:
            eng.submit(toks[0, :9].tolist(), 3)
            m = eng.metrics()
            # post-serve: all owners released, registry drained
            assert m["prefix_shared_blocks"] == 0
            assert m["prefix_block_refs"] == 0
            assert m["free_kv_blocks"] == 12
        finally:
            eng.stop()

    def test_sharing_lets_requests_fit_a_tight_pool(self,
                                                    f32_precision):
        """Two same-prefix requests that canNOT fit independently admit
        CONCURRENTLY once sharing is on — the memory win, observable
        through admission."""
        from veles_tpu.models.generate import PagedContinuousBatcher
        wf, toks = _lm_workflow(max_epochs=8)
        gen = LMGenerator(wf.trainer, max_len=16)
        prompt = toks[0, :9].tolist()         # 4 blocks per request
        tight = PagedContinuousBatcher(gen, slots=2, block=4,
                                       pool_tokens=24)  # 6 blocks
        tight.submit(prompt, 4); tight.submit(prompt, 4)
        tight.tick()
        assert sum(r is not None for r in tight._slot_req) == 1
        shared = PagedContinuousBatcher(gen, slots=2, block=4,
                                        pool_tokens=24,
                                        prefix_cache=True)
        r1 = shared.submit(prompt, 4); r2 = shared.submit(prompt, 4)
        shared.tick()
        assert sum(r is not None for r in shared._slot_req) == 2
        shared.run_all(); tight.run_all()
        want = gen.generate(np.asarray([prompt], np.int32),
                            4)[0].tolist()
        assert shared.pop_result(r1) == want
        assert shared.pop_result(r2) == want


def test_paged_rejects_request_larger_than_pool(f32_precision):
    """A request needing more blocks than the whole pool must fail at
    submit — accepted-but-never-admittable would deadlock run_all()
    and hang the serving engine forever."""
    from veles_tpu.models.generate import PagedContinuousBatcher
    wf, toks = _lm_workflow(max_epochs=0)
    gen = LMGenerator(wf.trainer, max_len=16)
    cb = PagedContinuousBatcher(gen, slots=2, block=4, pool_tokens=8)
    with pytest.raises(ValueError, match="pool only has"):
        cb.submit(toks[0, :8].tolist(), 8)    # 4 blocks > 2-block pool
    assert cb.idle() and cb.free_blocks() == 2
