"""Multi-head attention — naive, blockwise (flash) and Pallas paths.

New capability beyond the reference (SURVEY.md §5 "Long-context /
sequence parallelism: Absent" — the reference predates attention); the
TPU build treats long-context as first-class.  Three implementations with
one contract:

- ``attention``          O(T²) memory reference implementation (einsum),
                         ground truth for the tests.
- ``blockwise_attention``online-softmax ``lax.scan`` over key/value
                         blocks: O(T·block) memory, pure XLA, works on any
                         backend, and is what ring attention reuses per
                         shard (parallel.ring).
- ``flash_attention``    Pallas TPU kernel (ops.pallas.flash), VMEM-tiled;
                         falls back to interpret mode off-TPU.

All take [B, H, T, D] and return [B, H, T, D]; softmax math in f32
regardless of input dtype."""

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


class QuantCache(NamedTuple):
    """int8-quantized KV cache: per-(batch, head, position) symmetric
    scales over the head dim.  Quarters the serve-time cache memory vs
    f32 (halves vs bf16) — the storage bound on long-context serving.
    A pytree, so the decode scan / beam gathers treat it like a plain
    array via tree_map."""

    data: jnp.ndarray      # int8  [B, Hkv, T, hd]
    scale: jnp.ndarray     # f32   [B, Hkv, T, 1]


def quantize_kv(x):
    """x [..., T, hd] → (int8 data, f32 scale[..., T, 1]): symmetric
    per-position quantization over the head dim (the shared
    ops.quant.symmetric_int8 scheme)."""
    from veles_tpu.ops.quant import symmetric_int8
    return symmetric_int8(x)


def dequantize_kv(cache):
    """QuantCache → float array (f32) — the prefill in-chunk view, so
    prefilled positions see exactly what later decode steps will read
    back from the quantized cache."""
    return cache.data.astype(jnp.float32) * cache.scale


def _scale(d, scale=None):
    return 1.0 / math.sqrt(d) if scale is None else scale


def attention(q, k, v, causal=False, scale=None, bias=None, window=None):
    """Reference O(T²) attention.  q,k,v: [B, H, T, D].  ``window`` (with
    causal) keeps only the last ``window`` positions per query — the
    sliding-window mask."""
    *_, tq, d = q.shape
    tk = k.shape[-2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s * _scale(d, scale)
    if bias is not None:
        s = s + bias
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError("window must be >= 1")
    if causal:
        rows = jnp.arange(tq)[:, None] + (tk - tq)
        cols = jnp.arange(tk)[None]
        mask = rows >= cols
        if window is not None:
            mask = mask & (rows - cols < window)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def blockwise_attention(q, k, v, causal=False, scale=None, block_k=512,
                        q_offset=0, k_offset=0, carry=None,
                        return_carry=False, window=None):
    """Online-softmax attention scanning over key blocks.

    ``q_offset``/``k_offset`` are the *global* sequence positions of the
    local q/k shards — this is what lets ring attention apply a correct
    causal mask across devices.  ``carry``/``return_carry`` expose the
    (acc, max, sum) online-softmax state so partial attention over
    different kv shards can be chained (the ring step):

        carry = None
        for each kv shard:
            carry = blockwise_attention(..., carry=carry, return_carry=True)
        out = finalize_attention(carry)
    """
    if window is not None:
        # match flash_attention: never silently ignore or degenerate the
        # sliding window for direct callers of the blockwise entry point
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError("window must be >= 1")
    b, h, tq, d = q.shape
    tk = k.shape[-2]
    block_k = min(block_k, tk)
    nk = -(-tk // block_k)
    pad = nk * block_k - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    sc = _scale(d, scale)
    qpos = q_offset + jnp.arange(tq)

    if carry is None:
        acc = jnp.zeros((b, h, tq, d), jnp.float32)
        m = jnp.full((b, h, tq), NEG_INF, jnp.float32)
        l = jnp.zeros((b, h, tq), jnp.float32)
    else:
        acc, m, l = carry

    kb = k.reshape(b, h, nk, block_k, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, nk, block_k, d).transpose(2, 0, 1, 3, 4)

    def step(carry, inputs):
        acc, m, l = carry
        ki, kblk, vblk = inputs
        kpos = k_offset + ki * block_k + jnp.arange(block_k)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kblk,
                       preferred_element_type=jnp.float32) * sc
        valid = kpos < (k_offset + tk)          # padding mask
        if causal:
            valid = valid[None, :] & (qpos[:, None] >= kpos[None, :])
            if window is not None:
                valid = valid & (qpos[:, None] - kpos[None, :] < window)
            s = jnp.where(valid, s, NEG_INF)
        else:
            s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard: all-masked rows keep m=NEG_INF; exp(NEG_INF-NEG_INF)=1
        # would poison l, so renormalize against a safe max
        m_safe = jnp.maximum(m_new, -0.5 * abs(NEG_INF))
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(jnp.maximum(m, -0.5 * abs(NEG_INF)) - m_safe)
        corr = jnp.where(m <= NEG_INF / 2, 0.0, corr)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (acc, m_new, l), None

    (acc, m, l), _ = lax.scan(step, (acc, m, l),
                              (jnp.arange(nk), kb, vb))
    if return_carry:
        return acc, m, l
    return finalize_attention((acc, m, l)).astype(q.dtype)


def finalize_attention(carry):
    """Normalize the online-softmax accumulator: out = acc / l."""
    acc, _, l = carry
    return acc / jnp.maximum(l, 1e-30)[..., None]


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=None, backward="fused",
                    window=None, block_q_dq=None, block_k_dq=None,
                    block_q_dkv=None, block_k_dkv=None, shard=None):
    """Pallas TPU flash attention (ops.pallas.flash); [B, H, T, D].
    ``window`` = sliding-window causal attention (blocks outside the
    band are skipped entirely — O(T·window) compute).  Block sizes
    (forward and the independent dq/dkv backward grids) default from
    ``root.common.engine.flash.*``, then the kernel autotuner's winner
    cache — None forwards so the kernel-side resolution decides.

    ``shard=(mesh, batch_axis, head_axis)``: run the kernel per device
    under ``shard_map``, batch rows split over ``batch_axis`` and heads
    over ``head_axis`` (attention is independent per row and head, so
    no collective is needed).  On silicon the kernel is a Mosaic custom
    call, which GSPMD cannot partition: left bare inside a multi-device
    ``jit`` it does not even lower (JAX 0.9.0: "Mosaic kernels cannot
    be automatically partitioned"; interpret mode hides this).  An axis
    that is absent, of size 1, or does not divide its dim is left
    unsplit — correctness never depends on divisibility."""
    from veles_tpu.ops.pallas import flash
    fn = functools.partial(
        flash.flash_attention, causal=causal,
        scale=_scale(q.shape[-1], scale), block_q=block_q,
        block_k=block_k, interpret=interpret, backward=backward,
        window=window, block_q_dq=block_q_dq, block_k_dq=block_k_dq,
        block_q_dkv=block_q_dkv, block_k_dkv=block_k_dkv)
    if shard is None:
        return fn(q, k, v)
    mesh, batch_axis, head_axis = shard

    def fit(axis, n):
        size = mesh.shape.get(axis, 1)
        return axis if size > 1 and n % size == 0 else None

    from jax.sharding import PartitionSpec as P
    spec = P(fit(batch_axis, q.shape[0]), fit(head_axis, q.shape[1]))
    if spec == P(None, None):
        return fn(q, k, v)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


# ---------------------------------------------------------------------------
# multi-head attention layer math

def mha_init(rng, d_model, n_heads, dtype=jnp.float32, n_kv_heads=None):
    """QKV + output projection params.  ``rng`` is the framework PRNG
    (veles_tpu.prng RandomGenerator) for reproducibility.

    ``n_kv_heads < n_heads`` = grouped-query attention (GQA): k/v project
    to fewer heads, each shared by ``n_heads // n_kv_heads`` query heads —
    smaller k/v projections (params + FLOPs) and a smaller KV state at
    serve time.  (During training the forward broadcasts k/v back to
    n_heads before the attention core, so peak activation memory there
    matches full MHA.)"""
    if n_kv_heads is None:
        n_kv_heads = n_heads
    if n_heads % n_kv_heads:
        raise ValueError("n_heads %d %% n_kv_heads %d != 0"
                         % (n_heads, n_kv_heads))
    d_kv = (d_model // n_heads) * n_kv_heads
    std = 1.0 / math.sqrt(d_model)
    def w(shape):
        return jnp.asarray(rng.normal(0.0, std, shape), dtype)
    return {
        "wq": w((d_model, d_model)), "wk": w((d_model, d_kv)),
        "wv": w((d_model, d_kv)), "wo": w((d_model, d_model)),
        "bq": jnp.zeros((d_model,), dtype), "bk": jnp.zeros((d_kv,), dtype),
        "bv": jnp.zeros((d_kv,), dtype), "bo": jnp.zeros((d_model,), dtype),
    }


def split_heads(x, n_heads):
    b, t, dm = x.shape
    return x.reshape(b, t, n_heads, dm // n_heads).transpose(0, 2, 1, 3)


def merge_heads(x):
    return x.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[2], -1)


def _proj(x, w, b, policy):
    from veles_tpu.ops.quant import is_quant, quant_matmul
    if is_quant(w):
        # quantized serving weights (int8 W8A8 / w4a8): the payload
        # stays narrow into the dot, cutting decode HBM traffic
        return quant_matmul(x, w) + b.astype(jnp.float32)
    if policy is None:
        return x @ w + b
    y = jnp.matmul(policy.cast_in(x), policy.cast_in(w),
                   preferred_element_type=policy.accum)
    return y + b.astype(policy.accum)


def mha_forward(params, x, n_heads, causal=False, impl="blockwise",
                attn_fn=None, policy=None, n_kv_heads=None,
                use_rope=False, window=None, flash_shard=None):
    """x: [B, T, d_model] → [B, T, d_model].

    ``attn_fn(q, k, v, causal)`` overrides the core attention — this is the
    hook ring/Ulysses sequence parallelism plugs into (parallel.ring).
    ``policy`` (ops.policy.Policy) casts the projection matmuls and the
    attention inputs to the compute dtype (bf16 on the MXU).
    ``n_kv_heads`` enables GQA: k/v heads broadcast to the query heads
    before the core attention (same kernels, smaller projections).
    ``use_rope`` rotates q/k by absolute position (rope()).
    ``window`` = sliding-window causal attention (all impls share the
    q - k < window mask).
    ``flash_shard`` = ``flash_attention``'s ``shard`` triple, for
    ``impl="flash"`` under a data/model mesh."""
    if window is not None:
        # every backend also validates this itself; kept here so the
        # error precedes the projection matmuls
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError("window must be >= 1")
    if n_kv_heads is None:
        n_kv_heads = n_heads
    q, k, v = _qkv_proj(params, x, n_heads, n_kv_heads, policy)
    if use_rope:
        # rotation happens on the GLOBAL [B, H, T, D] arrays, before any
        # sequence-parallel shard_map (ring/Ulysses take global arrays
        # and shard internally) — positions are the true 0..T-1
        pos = jnp.arange(x.shape[1])
        q = rope(q, pos)
        k = rope(k, pos)
    k, v = _broadcast_kv(k, v, n_heads, n_kv_heads)
    if attn_fn is None:
        if impl == "naive":
            attn_fn = attention
        elif impl == "flash":
            attn_fn = functools.partial(flash_attention,
                                        shard=flash_shard)
        else:
            attn_fn = blockwise_attention
        if window is not None:
            attn_fn = functools.partial(attn_fn, window=window)
    elif window is not None:
        raise ValueError("window is not supported with sequence-"
                         "parallel attention (impl=ring/ulysses)")
    o = attn_fn(q, k, v, causal=causal)
    return _proj(merge_heads(o), params["wo"], params["bo"], policy)


def _qkv_proj(params, x, n_heads, n_kv_heads, policy):
    """Shared q/k/v projection + head split (mha_forward, mha_prefill,
    mha_step all route through here so they can never drift apart).

    LoRA (Hu et al. 2021, the standard q/v recipe): an optional
    ``params["lora"]`` sub-dict carries rank-r factors qa/qb and va/vb;
    the effective projections become Wq + qa·qb and Wv + va·vb.  Every
    decode path inherits the adapters through this one chokepoint.
    (Base-weight freezing is the LAYER's job — TransformerBlock
    stop_gradients everything but the lora subtree at train time.)"""
    cast = (lambda t: t) if policy is None else policy.cast_in
    lora = params.get("lora")

    def proj(wk_, bk_, ak_, bk2_, heads):
        y = _proj(x, params[wk_], params[bk_], policy)
        if lora is not None and ak_ in lora:
            d = jnp.matmul(jnp.matmul(cast(x), cast(lora[ak_])),
                           cast(lora[bk2_]))
            y = y + d.astype(y.dtype)
        return split_heads(cast(y), heads)

    q = proj("wq", "bq", "qa", "qb", n_heads)
    # k carries NO adapters (the standard q/v-only recipe) — plain base
    k = split_heads(cast(_proj(x, params["wk"], params["bk"], policy)),
                    n_kv_heads)
    v = proj("wv", "bv", "va", "vb", n_kv_heads)
    return q, k, v


def _broadcast_kv(k, v, n_heads, n_kv_heads):
    """GQA: broadcast kv heads up to the query heads."""
    if n_kv_heads != n_heads:
        rep = n_heads // n_kv_heads
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return k, v


def mha_prefill(params, x, cache_k, cache_v, n_heads, n_kv_heads=None,
                scale=None, policy=None, use_rope=False, window=None):
    """Chunked prefill: run the WHOLE prompt chunk x [B, Tp, d_model]
    through attention in one parallel pass (blockwise core — O(Tp·block)
    memory) and write its k/v into cache positions [0, Tp).

    Equivalent to Tp sequential mha_step calls, at full-forward cost:
    position i attends [0, i] via the causal(+window) mask, the cache
    stores k/v with mha_step's EXACT dtype ordering (cast to the cache
    dtype BEFORE the rope rotation), and the in-chunk attention reads
    the cache-dtype k/v — the same view mha_step sees.
    Returns (y [B, Tp, d_model], cache_k, cache_v)."""
    if n_kv_heads is None:
        n_kv_heads = n_heads
    quant = isinstance(cache_k, QuantCache)
    t_cache = (cache_k.data if quant else cache_k).shape[2]
    tp = x.shape[1]
    rolling = window is not None and t_cache == window
    q, k, v = _qkv_proj(params, x, n_heads, n_kv_heads, policy)
    if not quant:
        k = k.astype(cache_k.dtype)
        v = v.astype(cache_v.dtype)
    if use_rope:
        pos = jnp.arange(tp)
        q = rope(q, pos)
        k = (rope(k, pos) if quant
             else rope(k, pos).astype(cache_k.dtype))

    if rolling:
        # ring buffer: keep only the chunk's LAST min(tp, window)
        # positions; each lands in its slot (pos % window) — unique
        # slots, so the scatter has no duplicate-index hazard.  The
        # caller guarantees every chunk position is a real prompt token
        # (models.generate rounds the prefill chunk DOWN), so after
        # this write slot i holds the latest position <= tp - 1.
        keep = min(tp, window)
        tail_pos = jnp.arange(tp - keep, tp)
        slots = tail_pos % window

        def cache_write(cache, val):
            if not quant:
                return (cache.at[:, :, slots, :]
                        .set(val[:, :, tp - keep:, :]), val)
            # quantize the WHOLE chunk for the in-chunk view (in-chunk
            # queries attend head positions too, and the sequential
            # path reads everything quantized — the views must match);
            # only the tail slots are stored
            d, s = quantize_kv(val)
            new = QuantCache(
                cache.data.at[:, :, slots, :]
                .set(d[:, :, tp - keep:, :]),
                cache.scale.at[:, :, slots, :]
                .set(s[:, :, tp - keep:, :]))
            return new, dequantize_kv(QuantCache(d, s)).astype(val.dtype)
    else:
        def cache_write(cache, val):
            if not quant:
                return jax.lax.dynamic_update_slice(
                    cache, val, (0, 0, 0, 0)), val
            d, s = quantize_kv(val)
            new = QuantCache(
                jax.lax.dynamic_update_slice(cache.data, d,
                                             (0, 0, 0, 0)),
                jax.lax.dynamic_update_slice(cache.scale, s,
                                             (0, 0, 0, 0)))
            # the in-chunk attention must see the QUANTIZED view —
            # exactly what later decode steps read back from the cache
            return new, dequantize_kv(QuantCache(d, s)).astype(val.dtype)

    write = cache_write

    cache_k, k = write(cache_k, k)
    cache_v, v = write(cache_v, v)
    k, v = _broadcast_kv(k, v, n_heads, n_kv_heads)
    o = blockwise_attention(q, k, v, causal=True, scale=scale,
                            window=window)
    return (_proj(merge_heads(o), params["wo"], params["bo"], policy),
            cache_k, cache_v)


def mha_chunk_step(params, x, cache_k, cache_v, start, n_heads,
                   n_kv_heads=None, scale=None, policy=None,
                   use_rope=False, window=None):
    """K incremental positions in ONE parallel pass against an existing
    cache: x [B, K, d_model] holds the tokens at positions
    [start, start + K); their k/v write into the cache and every row i
    attends cache positions <= start + i (+ sliding window) — the
    speculative-decoding verify step.  Linear caches only (a rolling
    ring's slot->position map cannot tolerate the rejected-draft tail
    this writes past the cursor).  ``start`` is traced.
    Returns (y [B, K, d_model], cache_k, cache_v)."""
    if n_kv_heads is None:
        n_kv_heads = n_heads
    quant = isinstance(cache_k, QuantCache)
    kk = x.shape[1]
    q, k1, v1 = _qkv_proj(params, x, n_heads, n_kv_heads, policy)
    if not quant:
        k1 = k1.astype(cache_k.dtype)
        v1 = v1.astype(cache_v.dtype)
    if use_rope:
        pos = start + jnp.arange(kk)
        q = rope(q, pos)
        k1 = (rope(k1, pos) if quant
              else rope(k1, pos).astype(cache_k.dtype))

    def write(cache, val):
        if not quant:
            return jax.lax.dynamic_update_slice(cache, val,
                                                (0, 0, start, 0))
        d, s = quantize_kv(val)
        return QuantCache(
            jax.lax.dynamic_update_slice(cache.data, d,
                                         (0, 0, start, 0)),
            jax.lax.dynamic_update_slice(cache.scale, s,
                                         (0, 0, start, 0)))

    cache_k = write(cache_k, k1)
    cache_v = write(cache_v, v1)

    b, h, _, hd = q.shape
    g = h // n_kv_heads
    qg = q.reshape(b, n_kv_heads, g * kk, hd)   # flatten (group, K)
    if quant:
        s = jnp.einsum("bkgd,bktd->bkgt", qg,
                       cache_k.data.astype(qg.dtype),
                       preferred_element_type=jnp.float32)
        s = s * cache_k.scale[..., 0][:, :, None, :]
    else:
        s = jnp.einsum("bkgd,bktd->bkgt", qg, cache_k,
                       preferred_element_type=jnp.float32)
    s = s.reshape(b, n_kv_heads, g, kk, -1)
    s = s * _scale(hd, scale)
    t_cache = (cache_k.data if quant else cache_k).shape[2]
    positions = jnp.arange(t_cache)[None, None, None, None, :]
    rows = start + jnp.arange(kk)[None, None, None, :, None]
    live = positions <= rows
    if window is not None:
        live = live & (rows - positions < window)
    s = jnp.where(live, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).reshape(b, n_kv_heads, g * kk, -1)
    if quant:
        pv = p * cache_v.scale[..., 0][:, :, None, :]
        o = jnp.einsum("bkgt,bktd->bkgd", pv.astype(qg.dtype),
                       cache_v.data.astype(qg.dtype),
                       preferred_element_type=jnp.float32)
    else:
        o = jnp.einsum("bkgt,bktd->bkgd", p.astype(cache_v.dtype),
                       cache_v, preferred_element_type=jnp.float32)
    # [b, kv, g*kk, hd] -> [b, kk, kv, g, hd] -> [b, kk, h*hd]
    # (head index = kv*g + gi, matching split_heads/merge_heads)
    o = jnp.transpose(o.reshape(b, n_kv_heads, g, kk, hd),
                      (0, 3, 1, 2, 4))
    o = o.reshape(b, kk, h * hd).astype(x.dtype)
    return (_proj(o, params["wo"], params["bo"], policy),
            cache_k, cache_v)


def mha_step(params, x, cache_k, cache_v, pos, n_heads, n_kv_heads=None,
             scale=None, policy=None, use_rope=False, window=None):
    """One incremental-decoding step with a KV cache.

    x: [B, 1, d_model] (the token at position ``pos``);
    cache_k/cache_v: [B, n_kv_heads, T_cache, head_dim] — the cache
    stores KV HEADS ONLY, so GQA's smaller KV state is realized here
    (the query groups attend to the shared kv head without
    materializing copies) — or QuantCache pairs (int8 data +
    per-position scales; the scores fold the scales in after the
    int8-input einsum, so no dequantized [B, H, T, hd] copy ever
    materializes).

    ROLLING cache: with a sliding ``window``, T_cache == window means
    the cache is a ring buffer — position ``pos`` lives in slot
    ``pos % window`` and slot ``i`` holds absolute position
    ``pos - ((pos - i) % window)`` (the latest position <= pos mapping
    to that slot).  Serve-time memory is then O(window) regardless of
    context length.  T_cache > window keeps the linear layout.
    Returns (y [B, 1, d_model], cache_k, cache_v) with position ``pos``
    written."""
    if n_kv_heads is None:
        n_kv_heads = n_heads
    quant = isinstance(cache_k, QuantCache)
    kdt = cache_k.data.dtype if quant else cache_k.dtype
    t_cache = (cache_k.data if quant else cache_k).shape[2]
    rolling = window is not None and t_cache == window
    slot = (pos % window) if rolling else pos
    q, k1, v1 = _qkv_proj(params, x, n_heads, n_kv_heads, policy)
    if not quant:
        k1 = k1.astype(cache_k.dtype)                  # [B, Hkv, 1, hd]
        v1 = v1.astype(cache_v.dtype)
    if use_rope:
        p1 = jnp.full((1,), pos, jnp.int32)
        q = rope(q, p1)
        k1 = (rope(k1, p1) if quant
              else rope(k1, p1).astype(kdt))   # cache stores rotated k

    def write(cache, val):
        if not quant:
            return jax.lax.dynamic_update_slice(cache, val,
                                                (0, 0, slot, 0))
        d, s = quantize_kv(val)
        return QuantCache(
            jax.lax.dynamic_update_slice(cache.data, d,
                                         (0, 0, slot, 0)),
            jax.lax.dynamic_update_slice(cache.scale, s,
                                         (0, 0, slot, 0)))

    cache_k = write(cache_k, k1)
    cache_v = write(cache_v, v1)

    b, h, _, hd = q.shape
    g = h // n_kv_heads
    qg = q.reshape(b, n_kv_heads, g, hd)
    if quant:
        s = jnp.einsum("bkgd,bktd->bkgt", qg,
                       cache_k.data.astype(qg.dtype),
                       preferred_element_type=jnp.float32)
        # fold the per-position k scales in AFTER the dot
        s = s * cache_k.scale[..., 0][:, :, None, :]
    else:
        s = jnp.einsum("bkgd,bktd->bkgt", qg, cache_k,
                       preferred_element_type=jnp.float32)
    s = s * _scale(hd, scale)
    if rolling:
        # slot i holds absolute position pos - ((pos - i) % window):
        # always inside the window by construction, live once written
        slots = jnp.arange(window)[None, None, None, :]
        p_slot = pos - ((pos - slots) % window)
        live = p_slot >= 0
    else:
        positions = jnp.arange(t_cache)[None, None, None, :]
        live = positions <= pos
        if window is not None:
            live = live & (pos - positions < window)
    s = jnp.where(live, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if quant:
        # fold the per-position v scales into the probabilities
        pv = p * cache_v.scale[..., 0][:, :, None, :]
        o = jnp.einsum("bkgt,bktd->bkgd", pv.astype(qg.dtype),
                       cache_v.data.astype(qg.dtype),
                       preferred_element_type=jnp.float32)
    else:
        o = jnp.einsum("bkgt,bktd->bkgd", p.astype(cache_v.dtype),
                       cache_v, preferred_element_type=jnp.float32)
    o = o.reshape(b, 1, h * hd).astype(x.dtype)
    return (_proj(o, params["wo"], params["bo"], policy),
            cache_k, cache_v)


def _rope_rows(x, pos):
    """rope() at PER-ROW positions: x [B, H, 1, hd], pos [B] int32 —
    the continuous batcher decodes every slot at its own depth, so the
    rotation angle differs per batch row (rope() itself broadcasts one
    [T] position vector over the batch)."""
    return jax.vmap(lambda xb, pb: rope(xb[None], pb[None])[0])(
        x, pos.astype(jnp.int32))


def mha_step_paged(params, x, pool_k, pool_v, table, pos, n_heads,
                   n_kv_heads=None, scale=None, policy=None,
                   use_rope=False):
    """One incremental-decoding step against a PAGED KV pool.

    The paged continuous batcher's fused path: instead of gathering
    each row's pool blocks into a dense [B, Hkv, T, hd] cache view and
    calling mha_step, the new k/v scatter straight into their pool
    block and the attention reads the pool through the block table
    (ops.pallas.paged — scalar-prefetch kernel, no dense
    re-materialization).

    x: [B, 1, d_model] — every row decodes its OWN position ``pos[b]``
    (a [B] vector, unlike mha_step's scalar: slots run at different
    depths).  pool_k/pool_v: [1+P, Hkv, block, hd], block 0 reserved —
    or QuantCache pairs (int8 data + f32 per-position scales): the new
    k/v quantize at the write exactly like mha_step's dense int8
    cache, and the kernel streams the int8 pool from HBM and
    dequantizes in VMEM with f32 accumulation.  table: [B, nbm] int32
    pool-block ids; row b's key at absolute position t lives in pool
    block table[b, t // block], offset t % block.

    Sliding windows are not supported here — the batcher's gather path
    remains the fallback (and rolling windows are already rejected at
    pool construction).
    Returns (y [B, 1, d_model], pool_k, pool_v) with ``pos`` written.
    """
    from veles_tpu.ops.pallas.paged import paged_attention_decode
    if n_kv_heads is None:
        n_kv_heads = n_heads
    quant = isinstance(pool_k, QuantCache)
    pos = pos.astype(jnp.int32)
    q, k1, v1 = _qkv_proj(params, x, n_heads, n_kv_heads, policy)
    if not quant:
        k1 = k1.astype(pool_k.dtype)
        v1 = v1.astype(pool_v.dtype)
    if use_rope:
        q = _rope_rows(q, pos)
        k1 = (_rope_rows(k1, pos) if quant
              else _rope_rows(k1, pos).astype(pool_k.dtype))

    bs = (pool_k.data if quant else pool_k).shape[2]
    rows = jnp.arange(x.shape[0])
    blk = table[rows, pos // bs]
    off = pos % bs

    # write targets are exclusively-owned blocks: allocation is a
    # host-side free-list pop, and prefix-SHARED blocks are never
    # write targets (the batcher shares only blocks strictly before
    # any owner's first written position, _shareable_blocks) — so the
    # [B]-indexed scatter has no duplicate hazard
    def write(pool, val):
        if not quant:
            return pool.at[blk, :, off].set(val[:, :, 0])
        d, s = quantize_kv(val)              # [B, Hkv, 1, hd]/[..., 1]
        return QuantCache(pool.data.at[blk, :, off].set(d[:, :, 0]),
                          pool.scale.at[blk, :, off].set(s[:, :, 0]))

    pool_k = write(pool_k, k1)
    pool_v = write(pool_v, v1)

    b, h, _, hd = q.shape
    # the kernel runs the MXU in the pool dtype (bf16 serving; int8
    # pools dequantize in kernel to f32); the dense einsum path mixes
    # f32 q with the cache dtype instead — numerics differ at the
    # last-ulp level, same as flash vs naive
    qk = q[:, :, 0] if quant else q[:, :, 0].astype(pool_k.dtype)
    o = paged_attention_decode(qk, pool_k, pool_v, table, pos,
                               scale=_scale(hd, scale))
    o = o.reshape(b, 1, h * hd).astype(x.dtype)
    return (_proj(o, params["wo"], params["bo"], policy),
            pool_k, pool_v)


def rope(x, positions, base=10000.0):
    """Rotary position embedding (RoFormer).  x: [B, H, T, D] with D
    even; ``positions`` [T] int — rotates consecutive (even, odd) feature
    pairs by position-dependent angles, encoding relative offsets in the
    q·k inner product (no position table, extrapolates past train
    length)."""
    d = x.shape[-1]
    half = d // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[None, None]            # [1, 1, T, half]
    sin = jnp.sin(angles)[None, None]
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    rot = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rot.reshape(x.shape).astype(x.dtype)
