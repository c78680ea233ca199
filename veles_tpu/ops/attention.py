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


class KVCache(NamedTuple):
    """One block's per-token serve-time state: keys and values,
    [B, Hkv, T, hd] each (or QuantCache pairs).  A pytree of named
    leaves: the batchers walk it and never count its leaves."""

    k: jnp.ndarray
    v: jnp.ndarray


class KVIdxCache(NamedTuple):
    """``KVCache`` plus the sparse-attention indexer's key,
    [B, 1, T, d_index]: a second kind of per-token state beside K and
    V, paged through the same block table."""

    k: jnp.ndarray
    v: jnp.ndarray
    idx: jnp.ndarray


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

def mha_init(rng, d_model, n_heads, dtype=jnp.float32, n_kv_heads=None,
             bias=True, head_dim=None, qk_norm=False, indexer=None):
    """QKV + output projection params.  ``rng`` is the framework PRNG
    (veles_tpu.prng RandomGenerator) for reproducibility.

    ``head_dim`` (default ``d_model // n_heads``) lets the heads' total
    width differ from the model's; ``bias=False`` leaves the four bias
    leaves out; ``qk_norm`` adds a per-head RMSNorm gain for q and k
    (applied before the rotation); ``indexer`` = ``{"heads", "head_dim",
    "topk"}`` adds the sparse-attention indexer's projections (its
    queries, its one key head with a LayerNorm, its head weights).  The
    defaults draw exactly what they always drew, in the same order.

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
    hd = head_dim or d_model // n_heads
    d_q, d_kv = hd * n_heads, hd * n_kv_heads
    std = 1.0 / math.sqrt(d_model)
    def w(shape):
        return jnp.asarray(rng.normal(0.0, std, shape), dtype)
    params = {
        "wq": w((d_model, d_q)), "wk": w((d_model, d_kv)),
        "wv": w((d_model, d_kv)), "wo": w((d_q, d_model)),
    }
    if bias:
        params.update(
            bq=jnp.zeros((d_q,), dtype), bk=jnp.zeros((d_kv,), dtype),
            bv=jnp.zeros((d_kv,), dtype), bo=jnp.zeros((d_model,), dtype))
    if qk_norm:
        params["q_norm"] = jnp.ones((hd,), jnp.float32)
        params["k_norm"] = jnp.ones((hd,), jnp.float32)
    if indexer:
        hi, di = int(indexer["heads"]), int(indexer["head_dim"])
        from veles_tpu.ops import norm
        params["indexer"] = {
            "wq": w((d_model, hi * di)), "wk": w((d_model, di)),
            "ww": w((d_model, hi)), "k_ln": norm.layer_norm_init((di,))}
    return params


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
        y = quant_matmul(x, w)
        return y if b is None else y + b.astype(jnp.float32)
    if policy is None:
        return x @ w if b is None else x @ w + b
    y = jnp.matmul(policy.cast_in(x), policy.cast_in(w),
                   preferred_element_type=policy.accum)
    return y if b is None else y + b.astype(policy.accum)


def mha_forward(params, x, n_heads, causal=False, impl="blockwise",
                attn_fn=None, policy=None, n_kv_heads=None,
                use_rope=False, window=None, flash_shard=None,
                rope_base=10000.0, indexer=None):
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
    ``impl="flash"`` under a data/model mesh.
    ``indexer`` = ``{"heads", "head_dim", "topk"}``: learned sparse
    attention — every query attends the ``topk`` keys its index scores
    rank first (``dsa_attend``; causal, rotary, no window)."""
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
        q = rope(q, pos, rope_base)
        k = rope(k, pos, rope_base)
    if indexer:
        _check_indexer(causal, window, attn_fn)
        qi, ki, wi = _indexer_proj(params["indexer"], x, indexer, policy,
                                   jnp.arange(x.shape[1]), rope_base)
        o = dsa_attend(q, k, v, qi, ki, wi, 0, int(indexer["topk"]))
        return _proj(merge_heads(o), params["wo"], params.get("bo"),
                     policy)
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
    return _proj(merge_heads(o), params["wo"], params.get("bo"), policy)


def _check_indexer(causal, window, attn_fn=None):
    if not causal or window is not None or attn_fn is not None:
        raise ValueError("the sparse-attention indexer needs causal "
                         "attention with no window and no sequence-"
                         "parallel core")


def _qkv_proj(params, x, n_heads, n_kv_heads, policy):
    """Shared q/k/v projection + head split (mha_forward, mha_prefill,
    mha_step all route through here so they can never drift apart).

    LoRA (Hu et al. 2021, the standard q/v recipe): an optional
    ``params["lora"]`` sub-dict carries rank-r factors qa/qb and va/vb;
    the effective projections become Wq + qa·qb and Wv + va·vb.  Every
    decode path inherits the adapters through this one chokepoint.
    (Base-weight freezing is the LAYER's job — TransformerBlock
    stop_gradients everything but the lora subtree at train time.)

    QK-norm: with ``q_norm`` / ``k_norm`` gains among the params, every
    head of q and k is RMS-normalised over its own width (float32
    statistics) before any rotation."""
    cast = (lambda t: t) if policy is None else policy.cast_in
    lora = params.get("lora")

    def proj(wk_, bk_, ak_, bk2_, heads):
        y = _proj(x, params[wk_], params.get(bk_), policy)
        if lora is not None and ak_ in lora:
            d = jnp.matmul(jnp.matmul(cast(x), cast(lora[ak_])),
                           cast(lora[bk2_]))
            y = y + d.astype(y.dtype)
        return split_heads(cast(y), heads)

    q = proj("wq", "bq", "qa", "qb", n_heads)
    # k carries NO adapters (the standard q/v-only recipe) — plain base
    k = split_heads(cast(_proj(x, params["wk"], params.get("bk"),
                               policy)), n_kv_heads)
    v = proj("wv", "bv", "va", "vb", n_kv_heads)
    if "q_norm" in params:
        from veles_tpu.ops.norm import rms_norm
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    return q, k, v


def _broadcast_kv(k, v, n_heads, n_kv_heads):
    """GQA: broadcast kv heads up to the query heads."""
    if n_kv_heads != n_heads:
        rep = n_heads // n_kv_heads
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return k, v


# ---------------------------------------------------------------------------
# learned sparse attention (DeepSeek-Sparse-Attention indexer)

def _indexer_proj(ip, x, indexer, policy, positions, rope_base,
                  rows=False):
    """The indexer's view of ``x`` [B, T, d_model]: its queries
    [B, Hi, T, di] and its ONE key head [B, 1, T, di] (LayerNorm, then
    both rotated like q and k), and the per-query head weights
    [B, T, Hi] in float32, scaled by ``Hi^-0.5 · di^-0.5``.  ``rows``:
    ``positions`` is a [B] vector, one position a row (T = 1)."""
    from veles_tpu.ops.norm import layer_norm
    hi, di = int(indexer["heads"]), int(indexer["head_dim"])
    cast = (lambda t: t) if policy is None else policy.cast_in
    qi = split_heads(cast(_proj(x, ip["wq"], None, policy)), hi)
    ki = _proj(x, ip["wk"], None, policy)
    ki = cast(layer_norm(ki, ip["k_ln"]["gamma"], ip["k_ln"]["beta"]))
    ki = ki[:, None]
    wi = _proj(x, ip["ww"], None, policy).astype(jnp.float32) \
        * (hi ** -0.5 * di ** -0.5)
    turn = _rope_rows if rows else rope
    return (turn(qi, positions, rope_base), turn(ki, positions, rope_base),
            wi)


def index_scores(qi, ki, wi):
    """``I[b, q, s] = Σ_h wi[b, q, h] · ReLU(qi[b, h, q] · ki[b, s])``
    in float32 (the operands as they come, the products accumulated in
    float32).  qi [B, Hi, Tq, di], ki [B, Tk, di], wi [B, Tq, Hi] →
    [B, Tq, Tk].  An exact zero is +0.0 whatever the signs of the
    weights (-0.0 would rank below it)."""
    s = jnp.einsum("bhqd,bkd->bhqk", qi, ki,
                   preferred_element_type=jnp.float32)
    s = jnp.einsum("bhqk,bqh->bqk", jax.nn.relu(s), wi,
                   preferred_element_type=jnp.float32,
                   precision=lax.Precision.HIGHEST)
    return jnp.where(s == 0.0, 0.0, s)


def _ordered_bits(x):
    """float32 → uint32 whose unsigned order is the floats' order."""
    b = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


#: bits of the k-th largest value that one read of the scores settles
#: (``2**bits - 1`` counts a read, ``32 / bits`` reads)
DSA_SELECT_BITS = 2


def _kth_image(count_ge, k):
    """The largest uint32 ``th`` of which a row still holds ``k`` images
    ``>= th`` — the k-th largest image — most significant bits first,
    ``DSA_SELECT_BITS`` a read.  ``count_ge(cands)``: for each uint32
    [rows...] of the list, the int32 [rows...] count of a row's images
    at or above it, all from ONE read of the images."""
    nb = DSA_SELECT_BITS

    def grow(i, th):
        shift = (32 - nb * (i + 1)).astype(jnp.uint32)
        counts = count_ge([th | (jnp.uint32(m) << shift)
                           for m in range(1, 1 << nb)])
        # the counts fall as the candidate grows: the digit is the
        # number of candidates that still hold k
        digit = sum((c >= k).astype(jnp.uint32) for c in counts)
        return th | (digit << shift)

    return lax.fori_loop(0, 32 // nb, grow, jnp.zeros(k.shape, jnp.uint32))


def dsa_select(scores, valid, topk):
    """The ``topk`` largest ``scores`` among the ``valid`` entries of
    every row (all of them while a row has no more), EXACT, ties
    towards the lower position.  scores [..., Tk] float32, valid bool of
    the same shape → bool mask.

    The k-th largest value is found bit by bit on the order-preserving
    integer image of the scores (counting passes, no sort); entries
    equal to it are taken in position order until k are chosen.  Every
    pass ranges over the WHOLE row: a caller that knows a row's live
    length bounds the selection itself (``dsa_select_blocks``, the
    prefill pass's; same rule, same mask)."""
    u = jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))
    k = jnp.minimum(jnp.sum(valid, axis=-1, dtype=jnp.int32), topk)
    th = _kth_image(
        lambda cands: [jnp.sum(u >= c[..., None], axis=-1, dtype=jnp.int32)
                       for c in cands], k)
    above = u > th[..., None]
    equal = u == th[..., None]
    need = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    rank = jnp.cumsum(equal, axis=-1, dtype=jnp.int32)
    return valid & (above | (equal & (rank <= need[..., None])))


#: keys a step of ``dsa_attend`` handles at once (index scores,
#: selection and attention alike), and queries a call handles at once
DSA_KEY_BLOCK = 1024
DSA_QUERY_CHUNK = 2048


def dsa_live_blocks(live_keys, tk):
    """``(blocks, keys)`` a query's selection ranges over in a row of
    ``tk`` keys of which those at or beyond ``live_keys`` are dead:
    whole blocks of ``DSA_KEY_BLOCK``, so up to a block more than
    ``live_keys``.  The ONE place the bound is computed — the program's
    trip counts (``live_keys`` traced) and the batcher's ``staged_keys``
    (a Python int) both come from here.  None: the whole row."""
    kb = min(DSA_KEY_BLOCK, tk)
    nkb = -(-tk // kb)
    if live_keys is None:
        return nkb, tk
    n = (live_keys + kb - 1) // kb
    if isinstance(n, int):
        return min(n, nkb), min(n * kb, tk)
    return jnp.minimum(n, nkb), jnp.minimum(n * kb, tk)


def dsa_select_blocks(u, n_live, topk, dtype=jnp.bool_):
    """``dsa_select`` over the first ``n_live`` key blocks of a row kept
    block-major — the same rule, the same mask, and nothing read,
    counted or written past the live blocks.  u [n_blocks, ..., kb]
    uint32: the order-preserving images of the scores (``_ordered_bits``),
    0 where an entry is not valid; the keys of block j lie before those
    of block j + 1.  ``n_live`` may be traced (a dynamic trip count: no
    program per length) → bool mask of u's shape, blocks at or past
    ``n_live`` all False; written as ``dtype`` (int8 for the kernel of
    ``ops/pallas/dsa.py``: Mosaic reads no bool).

    Every counting pass is a loop over the live blocks that adds into
    one count a row; the ties at the k-th value are ranked two-level,
    as ``dsa_positions`` does: a block's total carried along, a running
    count inside the block."""
    rows = u.shape[1:-1]

    def count(tests):
        """a row's entries that pass, for each test of the list: one
        read of the live blocks"""
        def add(j, acc):
            blk = lax.dynamic_index_in_dim(u, j, 0, keepdims=False)
            return [a + jnp.sum(t(blk), axis=-1, dtype=jnp.int32)
                    for a, t in zip(acc, tests)]

        return lax.fori_loop(0, n_live, add,
                             [jnp.zeros(rows, jnp.int32)] * len(tests))

    k = jnp.minimum(count([lambda blk: blk > 0])[0], topk)
    # a valid image is over 0 and a row holds k of them, so th >= 1
    # wherever k >= 1: no entry at 0 is ever at or above the threshold
    th = _kth_image(
        lambda cands: count([lambda blk, c=c: blk >= c[..., None]
                             for c in cands]), k)
    need = k - count([lambda blk: blk > th[..., None]])[0]

    def mark(j, carry):
        chosen, seen = carry
        blk = lax.dynamic_index_in_dim(u, j, 0, keepdims=False)
        equal = blk == th[..., None]
        rank = seen[..., None] + jnp.cumsum(equal, axis=-1,
                                            dtype=jnp.int32)
        keep = (blk > th[..., None]) | (equal & (rank <= need[..., None]))
        return (lax.dynamic_update_index_in_dim(
                    chosen, keep.astype(dtype), j, 0),
                seen + jnp.sum(equal, axis=-1, dtype=jnp.int32))

    return lax.fori_loop(0, n_live, mark,
                         (jnp.zeros(u.shape, dtype),
                          jnp.zeros(rows, jnp.int32)))[0]


def dsa_attend(q, k, v, qi, ki, wi, q_start, topk, scale=None,
               live_keys=None):
    """Sparse attention of the queries at positions ``q_start ..
    q_start + Tq - 1`` over keys at positions 0 .. Tk-1 (a cache, or the
    sequence itself): index scores, the exact per-query top-``topk``
    among the keys at or before the query, then softmax attention over
    the selected keys only.

    q [B, H, Tq, hd]; k, v [B, Hkv, Tk, hd] (GQA: H // Hkv query heads
    share a kv head, no copies); qi [B, Hi, Tq, di]; ki [B, 1, Tk, di];
    wi [B, Tq, Hi].  ``q_start`` may be traced.  ``live_keys`` (traced,
    optional): keys at or beyond it are known dead, and EVERYTHING the
    call does over keys — index scores, the selection's counting passes
    and its count of ties, the attention — stops at the last live key
    block (``dsa_live_blocks``); the mask is the whole row's, bit for
    bit.  None keeps every trip count static (and the function
    differentiable).  Queries go through in chunks of
    ``DSA_QUERY_CHUNK``, keys in blocks of ``DSA_KEY_BLOCK``: no
    [Tq, Tk] matrix per head ever exists, one uint32 [B, chunk, Tk] of
    index scores' images does.  The attention under the mask runs in the
    Pallas kernel ``veles_dsa_prefill`` where the shapes tile
    (``dsa_prefill_tiles``), else in the XLA loop ``dsa_attend_blocks``
    — the same arithmetic, and the kernel's gradient is the loop's."""
    tq = q.shape[2]
    qc = DSA_QUERY_CHUNK
    if tq <= qc:
        return _dsa_chunk(q, k, v, qi, ki, wi, q_start, topk, scale,
                          live_keys)
    n = -(-tq // qc)
    pad = n * qc - tq

    def chunks(a, axis):
        if pad:
            widths = [(0, 0)] * a.ndim
            widths[axis] = (0, pad)
            a = jnp.pad(a, widths)
        a = a.reshape(a.shape[:axis] + (n, qc) + a.shape[axis + 1:])
        return jnp.moveaxis(a, axis, 0)

    def one(args):
        i, qq, qqi, wwi = args
        return _dsa_chunk(qq, k, v, qqi, ki, wwi, q_start + i * qc, topk,
                          scale, live_keys)

    o = lax.map(one, (jnp.arange(n), chunks(q, 2), chunks(qi, 2),
                      chunks(wi, 1)))
    o = jnp.moveaxis(o, 0, 2).reshape(q.shape[:2] + (n * qc, q.shape[3]))
    return o[:, :, :tq]


def _dsa_chunk(q, k, v, qi, ki, wi, q_start, topk, scale, live_keys):
    b, h, tq, hd = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    g = h // hkv
    kb = min(DSA_KEY_BLOCK, tk)
    nkb = -(-tk // kb)
    qpos = q_start + jnp.arange(tq)
    n_live, _ = dsa_live_blocks(live_keys, tk)

    def block(j):
        # the last block of a length that kb does not divide starts
        # early and overlaps its neighbour; ``own`` are its own keys
        start = jnp.minimum(j * kb, tk - kb)
        kpos = start + jnp.arange(kb)
        return start, kpos, kpos >= j * kb

    def score_block(j, u):
        start, kpos, own = block(j)
        kblk = lax.dynamic_slice_in_dim(ki[:, 0], start, kb, axis=1)
        s = index_scores(qi, kblk, wi)                   # [b, tq, kb]
        valid = own[None, None] & (kpos[None, None]
                                   <= qpos[None, :, None])
        return lax.dynamic_update_index_in_dim(
            u, jnp.where(valid, _ordered_bits(s), jnp.uint32(0)), j, 0)

    # block-major, so that a block is a slice of the leading dimension;
    # what lies past the live blocks is never read
    u = lax.fori_loop(0, n_live, score_block,
                      jnp.zeros((nkb, b, tq, kb), jnp.uint32))

    sc = _scale(hd, scale)
    q = q.reshape(b, hkv, g, tq, hd)
    # the kernel where the shapes tile and one dtype goes through the
    # matmuls (``mha_chunk_step`` casts q to the cache's), else the loop
    if q.dtype == k.dtype == v.dtype and dsa_prefill_tiles(tq, tk, hd):
        o = _dsa_prefill(q, k, v,
                         dsa_select_blocks(u, n_live, topk, jnp.int8),
                         jnp.asarray(q_start, jnp.int32),
                         jnp.asarray(n_live, jnp.int32), sc)
    else:
        o = dsa_attend_blocks(q, k, v, dsa_select_blocks(u, n_live, topk),
                              n_live, sc)
    return o.reshape(b, h, tq, hd)


def dsa_prefill_tiles(tq, tk, hd):
    """Whether the masked attention of ``tq`` queries over a row of
    ``tk`` keys at head dim ``hd`` runs in the Pallas kernel
    ``veles_dsa_prefill`` (``ops/pallas/dsa.py``; its tiles, else None).
    The ONE place the choice is made — ``_dsa_chunk`` and the batcher's
    ``staged_kernel_tokens`` both ask here."""
    from veles_tpu.ops.pallas import dsa
    return dsa.prefill_tiles(tq, tk, hd, min(DSA_KEY_BLOCK, tk))


def dsa_attend_blocks(q, k, v, chosen, n_live, scale):
    """Softmax attention of every query over the keys ``chosen`` marks,
    one key block a step over the first ``n_live`` blocks (traced, or an
    int: static trip counts, differentiable): the XLA loop, the ground
    truth of the kernel ``veles_dsa_prefill`` and the path of the shapes
    it does not take — it alone handles a last block that overlaps its
    neighbour (a ``tk`` that ``kb`` does not divide).  q [B, Hkv, G, Tq,
    hd]; k, v [B, Hkv, Tk, hd]; chosen [n_blocks, B, Tq, kb], bool or
    integer → q's shape and dtype."""
    b, hkv, g, tq, hd = q.shape
    tk, kb = k.shape[2], chosen.shape[-1]
    qg = q.reshape(b, hkv, g * tq, hd)

    def attend_block(j, carry):
        acc, m, l = carry
        # the last block of a length that kb does not divide starts
        # early, as the selection's did
        start = jnp.minimum(j * kb, tk - kb)
        kblk = lax.dynamic_slice_in_dim(k, start, kb, axis=2)
        vblk = lax.dynamic_slice_in_dim(v, start, kb, axis=2)
        s = jnp.einsum("bkqd,bktd->bkqt", qg, kblk,
                       preferred_element_type=jnp.float32) * scale
        keep = lax.dynamic_index_in_dim(chosen, j, 0, keepdims=False)
        keep = jnp.broadcast_to(keep.astype(jnp.bool_)[:, None, None],
                                (b, hkv, g, tq, kb)).reshape(s.shape)
        s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # a query always selects itself, so a row's maximum is finite
        # from its own block on; before that p and corr are nought
        p = jnp.where(keep, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkqt,bktd->bkqd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return acc, m_new, l

    acc, _, l = lax.fori_loop(
        0, n_live, attend_block,
        (jnp.zeros((b, hkv, g * tq, hd), jnp.float32),
         jnp.full((b, hkv, g * tq), NEG_INF, jnp.float32),
         jnp.zeros((b, hkv, g * tq), jnp.float32)))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o.reshape(q.shape).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _dsa_prefill(q, k, v, chosen, q_start, n_live, scale):
    """``dsa_attend_blocks`` in the kernel; its gradient is the loop's
    for the same mask, over the whole row at static trip counts (the
    blocks at or past ``n_live`` are all False)."""
    from veles_tpu.ops.pallas import dsa
    return dsa.dsa_prefill_attention(q, k, v, chosen, q_start, n_live,
                                     scale)


def _dsa_prefill_fwd(q, k, v, chosen, q_start, n_live, scale):
    return (_dsa_prefill(q, k, v, chosen, q_start, n_live, scale),
            (q, k, v, chosen))


def _dsa_prefill_bwd(scale, res, ct):
    q, k, v, chosen = res
    _, vjp = jax.vjp(lambda q, k, v: dsa_attend_blocks(
        q, k, v, chosen, chosen.shape[0], scale), q, k, v)
    return vjp(ct) + (None, None, None)


_dsa_prefill.defvjp(_dsa_prefill_fwd, _dsa_prefill_bwd)


def chunk_attend(q, cache_k, cache_v, q_start, window=None, scale=None):
    """Causal (and sliding-window) attention of the queries at positions
    ``q_start .. q_start + Tq - 1`` over a cache row they were just
    written into, one key block of ``DSA_KEY_BLOCK`` a step under an
    online softmax: no score tensor over the row ever exists.

    q [B, H, Tq, hd]; cache_k, cache_v [B, Hkv, Tk, hd] arrays or
    ``QuantCache`` pairs (GQA: H // Hkv query heads share a KV head).
    Slot s of the row holds position s; under a ``window`` it holds the
    LATEST position at or before the chunk's last that is congruent to
    s mod Tk — a row as long as the context never wraps and reads the
    same, a shorter one is a ring (``Tk >= window + Tq - 1``: the
    caller's to keep).  ``q_start`` may be traced.  Only the blocks
    that hold a key some query attends are visited: the live ones of a
    full row (up to the chunk's own last key), the band's of a window.

    The attention under the mask runs in the Pallas kernel
    ``veles_dsa_prefill`` (flash attention under an arbitrary mask,
    ``ops/pallas/dsa.py``) wherever its tiles take the shapes
    (``dsa_prefill_tiles``) and one dtype goes through the matmuls —
    q is cast to the cache's, as the paged decode kernel's is — else in
    an XLA loop over the same blocks."""
    quant = isinstance(cache_k, QuantCache)
    kd = cache_k.data if quant else cache_k
    b, h, tq, hd = q.shape
    hkv, tk = kd.shape[1], kd.shape[2]
    g = h // hkv
    kb = min(DSA_KEY_BLOCK, tk)
    nkb = -(-tk // kb)
    sc = _scale(hd, scale)
    last = q_start + tq - 1
    qpos = q_start + jnp.arange(tq)

    def keep(j):
        """bool [Tq, kb]: which slots of block j each query attends"""
        slot = j * kb + jnp.arange(kb)
        if window is None:
            kpos = slot
        else:
            kpos = last - (last - slot) % tk
        live = (slot < tk) & (kpos >= 0) if window is not None \
            else slot < tk
        live = live[None] & (kpos[None] <= qpos[:, None])
        if window is not None:
            live = live & (qpos[:, None] - kpos[None] < window)
        return live

    # the blocks that hold an attended key: up to the chunk's last key,
    # and under a window from the band's first — all of a wrapped ring
    wrapped = last >= tk
    hi = jnp.where(wrapped, nkb, jnp.minimum(last // kb + 1, nkb))
    lo = 0 if window is None else jnp.where(
        wrapped, 0, jnp.maximum(q_start - window + 1, 0) // kb)

    def padded(a):
        return a if nkb * kb == tk else jnp.pad(
            a, ((0, 0), (0, 0), (0, nkb * kb - tk), (0, 0)))

    if not quant and dsa_prefill_tiles(tq, nkb * kb, hd):
        dt = kd.dtype
        mask = jax.vmap(keep)(jnp.arange(nkb)).astype(jnp.int8)
        mask = jnp.broadcast_to(mask[:, None], (nkb, b, tq, kb))
        # the kernel clamps a q tile's key tiles at its last query's
        # own; a wrapped ring has no such order, so it is told a start
        # past every slot
        o = _dsa_prefill(q.astype(dt).reshape(b, hkv, g, tq, hd),
                         padded(cache_k), padded(cache_v.astype(dt)), mask,
                         jnp.where(wrapped, nkb * kb, q_start).astype(
                             jnp.int32),
                         jnp.asarray(hi, jnp.int32), sc)
        return o.reshape(b, h, tq, hd)

    qg = q.reshape(b, hkv, g * tq, hd)
    kdat, vdat = padded(kd), padded(cache_v.data if quant else cache_v)
    if quant:
        kscale, vscale = padded(cache_k.scale), padded(cache_v.scale)

    def attend_block(j, carry):
        acc, m, l = carry
        kblk = lax.dynamic_slice_in_dim(kdat, j * kb, kb, axis=2)
        vblk = lax.dynamic_slice_in_dim(vdat, j * kb, kb, axis=2)
        if quant:
            # the per-position scales fold in after the dot (mha_step)
            s = jnp.einsum("bkqd,bktd->bkqt", qg, kblk.astype(qg.dtype),
                           preferred_element_type=jnp.float32)
            s = s * lax.dynamic_slice_in_dim(
                kscale, j * kb, kb, axis=2)[..., 0][:, :, None, :]
        else:
            s = jnp.einsum("bkqd,bktd->bkqt", qg, kblk,
                           preferred_element_type=jnp.float32)
        s = s * sc
        live = jnp.broadcast_to(keep(j)[None, None, None],
                                (b, hkv, g, tq, kb)).reshape(s.shape)
        s = jnp.where(live, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # a query attends itself, so a row's maximum is finite from its
        # own block on; before that p and corr are nought
        p = jnp.where(live, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        if quant:
            pv = (p * lax.dynamic_slice_in_dim(
                vscale, j * kb, kb, axis=2)[..., 0][:, :, None, :]
                  ).astype(qg.dtype)
            vblk = vblk.astype(qg.dtype)
        else:
            pv = p.astype(vblk.dtype)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkqt,bktd->bkqd", pv, vblk,
            preferred_element_type=jnp.float32)
        return acc, m_new, l

    acc, _, l = lax.fori_loop(
        lo, hi, attend_block,
        (jnp.zeros((b, hkv, g * tq, hd), jnp.float32),
         jnp.full((b, hkv, g * tq), NEG_INF, jnp.float32),
         jnp.zeros((b, hkv, g * tq), jnp.float32)))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o.reshape(b, h, tq, hd)


def _cache_kv(cache):
    quant = isinstance(cache.k, QuantCache)
    if quant and hasattr(cache, "idx"):
        raise ValueError("an int8 KV cache does not carry the sparse-"
                         "attention indexer's keys")
    return quant


def mha_prefill(params, x, cache, n_heads, n_kv_heads=None,
                scale=None, policy=None, use_rope=False, window=None,
                rope_base=10000.0, indexer=None):
    """Chunked prefill: run the WHOLE prompt chunk x [B, Tp, d_model]
    through attention in one parallel pass (blockwise core — O(Tp·block)
    memory) and write its k/v (and, with an ``indexer``, its index keys)
    into cache positions [0, Tp).

    Equivalent to Tp sequential mha_step calls, at full-forward cost:
    position i attends [0, i] via the causal(+window) mask, the cache
    stores k/v with mha_step's EXACT dtype ordering (cast to the cache
    dtype BEFORE the rope rotation), and the in-chunk attention reads
    the cache-dtype k/v — the same view mha_step sees.
    Returns (y [B, Tp, d_model], cache)."""
    if n_kv_heads is None:
        n_kv_heads = n_heads
    quant = _cache_kv(cache)
    cache_k, cache_v = cache.k, cache.v
    t_cache = (cache_k.data if quant else cache_k).shape[2]
    tp = x.shape[1]
    rolling = window is not None and t_cache == window
    q, k, v = _qkv_proj(params, x, n_heads, n_kv_heads, policy)
    if not quant:
        k = k.astype(cache_k.dtype)
        v = v.astype(cache_v.dtype)
    if use_rope:
        pos = jnp.arange(tp)
        q = rope(q, pos, rope_base)
        k = (rope(k, pos, rope_base) if quant
             else rope(k, pos, rope_base).astype(cache_k.dtype))

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
    if indexer:
        _check_indexer(True, window)
        qi, ki, wi = _indexer_proj(params["indexer"], x, indexer, policy,
                                   jnp.arange(tp), rope_base)
        ki = ki.astype(cache.idx.dtype)
        cache = KVIdxCache(cache_k, cache_v, jax.lax.dynamic_update_slice(
            cache.idx, ki, (0, 0, 0, 0)))
        o = dsa_attend(q, k, v, qi, ki, wi, 0, int(indexer["topk"]),
                       scale=scale)
    else:
        cache = KVCache(cache_k, cache_v)
        k, v = _broadcast_kv(k, v, n_heads, n_kv_heads)
        o = blockwise_attention(q, k, v, causal=True, scale=scale,
                                window=window)
    return (_proj(merge_heads(o), params["wo"], params.get("bo"), policy),
            cache)


def mha_chunk_step(params, x, cache, start, n_heads,
                   n_kv_heads=None, scale=None, policy=None,
                   use_rope=False, window=None, rope_base=10000.0,
                   indexer=None):
    """K incremental positions in ONE parallel pass against an existing
    cache: x [B, K, d_model] holds the tokens at positions
    [start, start + K); their k/v write into the cache and every row i
    attends cache positions <= start + i (+ sliding window) — the
    speculative-decoding verify step and a staged prefill pass.
    ``start`` is traced.  The attention goes over key blocks under an
    online softmax (``chunk_attend``): the live blocks of a full layer,
    the band's of a window layer, never a score tensor over the row.

    A ``window`` layer's row is read and written as a RING: position p
    lives in slot ``p mod T_cache``.  A row as long as the context never
    wraps and is the linear cache it always was; a shorter one (the
    paged batcher's staging ring, ``T_cache >= window + K - 1``) keeps
    the last ``T_cache`` positions.  (The speculative verify still
    needs linear rows: the rejected-draft tail it writes past the
    cursor would wrap into live slots.)  With an
    ``indexer`` the chunk's index keys are written too and every row
    attends the keys its index scores select: ``dsa_attend`` with
    ``live_keys = start + K``, so the index scores, the selection and
    the attention all range over the key blocks up to the chunk's own
    last key and over nothing of the row past them — the selected sets
    are those of a selection over the whole row.
    Returns (y [B, K, d_model], cache)."""
    if n_kv_heads is None:
        n_kv_heads = n_heads
    quant = _cache_kv(cache)
    cache_k, cache_v = cache.k, cache.v
    kk = x.shape[1]
    q, k1, v1 = _qkv_proj(params, x, n_heads, n_kv_heads, policy)
    if not quant:
        k1 = k1.astype(cache_k.dtype)
        v1 = v1.astype(cache_v.dtype)
    if use_rope:
        pos = start + jnp.arange(kk)
        q = rope(q, pos, rope_base)
        k1 = (rope(k1, pos, rope_base) if quant
              else rope(k1, pos, rope_base).astype(cache_k.dtype))

    t_cache = (cache_k.data if quant else cache_k).shape[2]

    def put(cache, val):
        if window is None:
            return jax.lax.dynamic_update_slice(cache, val,
                                                (0, 0, start, 0))
        # a ring: K <= T_cache distinct slots
        slots = (start + jnp.arange(kk)) % t_cache
        return cache.at[:, :, slots].set(val, unique_indices=True)

    def write(cache, val):
        if not quant:
            return put(cache, val)
        d, s = quantize_kv(val)
        return QuantCache(put(cache.data, d), put(cache.scale, s))

    cache_k = write(cache_k, k1)
    cache_v = write(cache_v, v1)

    b, h, _, hd = q.shape
    if indexer:
        _check_indexer(True, window)
        qi, ki, wi = _indexer_proj(params["indexer"], x, indexer, policy,
                                   start + jnp.arange(kk), rope_base)
        cache_i = write(cache.idx, ki.astype(cache.idx.dtype))
        o = dsa_attend(q.astype(cache_k.dtype), cache_k, cache_v,
                       qi.astype(cache_i.dtype), cache_i, wi, start,
                       int(indexer["topk"]), scale=scale,
                       live_keys=start + kk)
        o = merge_heads(o).astype(x.dtype)
        return (_proj(o, params["wo"], params.get("bo"), policy),
                KVIdxCache(cache_k, cache_v, cache_i))
    o = chunk_attend(q, cache_k, cache_v, start, window, scale)
    o = merge_heads(o).astype(x.dtype)
    return (_proj(o, params["wo"], params.get("bo"), policy),
            KVCache(cache_k, cache_v))


def mha_step(params, x, cache, pos, n_heads, n_kv_heads=None,
             scale=None, policy=None, use_rope=False, window=None,
             rope_base=10000.0, indexer=None):
    """One incremental-decoding step with a KV cache.

    x: [B, 1, d_model] (the token at position ``pos``);
    cache.k / cache.v: [B, n_kv_heads, T_cache, head_dim] — the cache
    stores KV HEADS ONLY, so GQA's smaller KV state is realized here
    (the query groups attend to the shared kv head without
    materializing copies) — or QuantCache pairs (int8 data +
    per-position scales; the scores fold the scales in after the
    int8-input einsum, so no dequantized [B, H, T, hd] copy ever
    materializes).  With an ``indexer``, cache.idx [B, 1, T_cache,
    d_index] holds the index keys and the softmax runs over the
    selected keys only (``dsa_select``).

    ROLLING cache: with a sliding ``window``, T_cache == window means
    the cache is a ring buffer — position ``pos`` lives in slot
    ``pos % window`` and slot ``i`` holds absolute position
    ``pos - ((pos - i) % window)`` (the latest position <= pos mapping
    to that slot).  Serve-time memory is then O(window) regardless of
    context length.  T_cache > window keeps the linear layout.
    Returns (y [B, 1, d_model], cache) with position ``pos`` written."""
    if n_kv_heads is None:
        n_kv_heads = n_heads
    quant = _cache_kv(cache)
    cache_k, cache_v = cache.k, cache.v
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
        q = rope(q, p1, rope_base)
        k1 = (rope(k1, p1, rope_base) if quant
              else rope(k1, p1, rope_base).astype(kdt))   # rotated k

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
    if indexer:
        _check_indexer(True, window)
        qi, ki, wi = _indexer_proj(params["indexer"], x, indexer, policy,
                                   jnp.full((1,), pos, jnp.int32),
                                   rope_base)
        cache_i = write(cache.idx, ki.astype(cache.idx.dtype))
        scores = index_scores(qi.astype(cache_i.dtype), cache_i[:, 0],
                              wi)                       # [b, 1, t]
        chosen = dsa_select(scores, jnp.arange(t_cache)[None, None]
                            <= pos, int(indexer["topk"]))
        live = live & chosen[:, :, None]
        cache = KVIdxCache(cache_k, cache_v, cache_i)
    else:
        cache = KVCache(cache_k, cache_v)
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
    return _proj(o, params["wo"], params.get("bo"), policy), cache


def _rope_rows(x, pos, base=10000.0):
    """rope() at PER-ROW positions: x [B, H, 1, hd], pos [B] int32 —
    the continuous batcher decodes every slot at its own depth, so the
    rotation angle differs per batch row (rope() itself broadcasts one
    [T] position vector over the batch)."""
    return jax.vmap(lambda xb, pb: rope(xb[None], pb[None], base)[0])(
        x, pos.astype(jnp.int32))


def mha_step_paged(params, x, pool, table, pos, n_heads,
                   n_kv_heads=None, scale=None, policy=None,
                   use_rope=False, rope_base=10000.0, indexer=None,
                   window=None):
    """One incremental-decoding step against a PAGED KV pool.

    The paged continuous batcher's tick: the new k/v are written
    straight into their pool block and the attention reads the pool
    through the block table (ops.pallas.paged — scalar-prefetch
    kernel, no dense [B, Hkv, T, hd] view of a row's blocks).

    x: [B, 1, d_model] — every row decodes its OWN position ``pos[b]``
    (a [B] vector, unlike mha_step's scalar: slots run at different
    depths).  pool.k / pool.v: [1+P, Hkv, block, hd], block 0 reserved
    — or QuantCache pairs (int8 data + f32 per-position scales): the new
    k/v quantize at the write exactly like mha_step's dense int8
    cache, and the kernel streams the int8 pool from HBM and
    dequantizes in VMEM with f32 accumulation.  table: [B, nbm] int32
    pool-block ids; row b's key at absolute position t lives in pool
    block table[b, t // block], offset t % block.

    With an ``indexer``, pool.idx [1+P, 1, block, d_index] holds the
    index keys: a row at or past position ``topk`` scores its live
    pages, takes the exact top-``topk`` and attends those keys through
    the table (``paged_sparse_attend``); a row under it attends all its
    keys, through the same ``veles_paged_decode`` kernel as a model
    without an indexer.  Which of the two a row takes follows from its
    position alone, and a tick pays for a path only if a row takes it.

    With a ``window`` the table is a RING of ``nbm`` pool blocks: the
    key at absolute position t lives in block ``table[b, (t // block)
    mod nbm]`` (``nbm`` blocks hold at least ``window`` keys and a
    block more), a row attends its last ``window`` keys only, and the
    kernel walks the pages of ``[pos - window + 1, pos]``
    (``veles_paged_decode_window``).
    Returns (y [B, 1, d_model], pool, attended) with ``pos`` written;
    ``attended`` [B] int32: the keys each row's softmax ran over, as
    the path that ran counted them (the batcher's ``sel_keys``).
    """
    from veles_tpu.ops.pallas.paged import paged_attention_decode
    if n_kv_heads is None:
        n_kv_heads = n_heads
    quant = _cache_kv(pool)
    pool_k, pool_v = pool.k, pool.v
    pos = pos.astype(jnp.int32)
    q, k1, v1 = _qkv_proj(params, x, n_heads, n_kv_heads, policy)
    if not quant:
        k1 = k1.astype(pool_k.dtype)
        v1 = v1.astype(pool_v.dtype)
    if use_rope:
        q = _rope_rows(q, pos, rope_base)
        k1 = (_rope_rows(k1, pos, rope_base) if quant
              else _rope_rows(k1, pos, rope_base).astype(pool_k.dtype))

    bs = (pool_k.data if quant else pool_k).shape[2]
    rows = jnp.arange(x.shape[0])
    entry = pos // bs
    if window is not None:
        entry = entry % table.shape[1]
    blk = table[rows, entry]
    off = pos % bs

    # write targets are exclusively-owned blocks: allocation is a
    # host-side free-list pop, and prefix-SHARED blocks are never
    # write targets (the batcher shares only blocks strictly before
    # any owner's first written position, _shareable_blocks) — so the
    # [B]-indexed scatter has no duplicate hazard
    # and one row at a time, each an in-place ``dynamic_update_slice``
    # of a [1, H, 1, width] slab: a [B]-indexed scatter over dims 0 and
    # 2 made XLA re-lay the WHOLE pool into a scatter-friendly layout
    # and back round every layer's custom call (2.4 GB of copies a tick
    # to store 2 MB: PERF.md, PR 24/27)
    def put(pool, val):                      # val [B, H, 1, width]
        def one(b, p):
            row = lax.dynamic_slice_in_dim(val, b, 1, axis=0)
            return lax.dynamic_update_slice(
                p, row.astype(p.dtype), (blk[b], 0, off[b], 0))
        return lax.fori_loop(0, val.shape[0], one, pool)

    def write(pool, val):
        if not quant:
            return put(pool, val)
        d, s = quantize_kv(val)              # [B, Hkv, 1, hd]/[..., 1]
        return QuantCache(put(pool.data, d), put(pool.scale, s))

    pool_k = write(pool_k, k1)
    pool_v = write(pool_v, v1)

    b, h, _, hd = q.shape
    # the kernel runs the MXU in the pool dtype (bf16 serving; int8
    # pools dequantize in kernel to f32); the dense einsum path mixes
    # f32 q with the cache dtype instead — numerics differ at the
    # last-ulp level, same as flash vs naive
    qk = q[:, :, 0] if quant else q[:, :, 0].astype(pool_k.dtype)
    sc = _scale(hd, scale)
    if not indexer:
        o = paged_attention_decode(qk, pool_k, pool_v, table, pos,
                                   scale=sc, window=window)
        pool = KVCache(pool_k, pool_v)
        attended = pos + 1 if window is None \
            else jnp.minimum(pos + 1, window)
    else:
        _check_indexer(True, window)
        topk = int(indexer["topk"])
        qi, ki, wi = _indexer_proj(params["indexer"], x, indexer, policy,
                                   pos, rope_base, rows=True)
        pool_i = write(pool.idx, ki.astype(pool.idx.dtype))
        pool = KVIdxCache(pool_k, pool_v, pool_i)
        dense = pos < topk          # all of a row's keys are selected

        def dense_rows(_):
            # a sparse row walks one page here, and its result is not
            # taken
            return paged_attention_decode(
                qk, pool_k, pool_v, table, jnp.where(dense, pos, 0),
                scale=sc).astype(jnp.float32)

        def sparse_rows(_):
            return paged_sparse_attend(
                qk, pool_k, pool_v, pool_i, table, pos,
                qi[:, :, 0].astype(pool_i.dtype), wi[:, 0], topk, sc)

        def nought(_):
            return jnp.zeros((b, h, hd), jnp.float32)

        # the sparse branch alone returns a count beside its result:
        # ``(f32[B, H, hd], s32[B])`` is how a device trace tells this
        # conditional from every other of the tick
        o_sparse, n_sparse = lax.cond(
            jnp.any(~dense), sparse_rows,
            lambda _: (nought(None), jnp.zeros((b,), jnp.int32)), None)
        o = jnp.where(dense[:, None, None],
                      lax.cond(jnp.any(dense), dense_rows, nought, None),
                      o_sparse)
        attended = jnp.where(dense, pos + 1, n_sparse)
    o = o.reshape(b, 1, h * hd).astype(x.dtype)
    return (_proj(o, params["wo"], params.get("bo"), policy), pool,
            attended)


#: keys a chunk of ``dsa_positions`` holds (the vector lanes)
DSA_POSITION_CHUNK = 128


def dsa_positions(chosen, n_sel):
    """The positions a selection holds, in position order: chosen [B, T]
    bool with at most ``n_sel`` true a row → ``(sel [B, n_sel] int32,
    live [B, n_sel] bool)``; slot j of a row is its (j+1)-th selected
    position where the row has that many (``live``).

    Two levels, no sort, no scatter and no search by gathers (on a v5e
    at [8, 34816] → 2,048: 0.25 ms, where a binary search of the running
    count takes 2.2 ms and a two-key sort of scores and positions as
    long as selection and this together; PERF.md, PR 29): the running
    count over chunks of ``DSA_POSITION_CHUNK`` keys says which chunk
    holds a rank — a comparison against every chunk's count — and the
    running count inside that one chunk, fetched as a row, says where."""
    b, t = chosen.shape
    lane = min(DSA_POSITION_CHUNK, t)
    pad = -t % lane
    chunks = jnp.pad(chosen, ((0, 0), (0, pad))).reshape(b, -1, lane)
    per = jnp.sum(chunks, axis=2, dtype=jnp.int32)           # [B, C]
    upto = jnp.cumsum(per, axis=1)
    want = jnp.arange(1, n_sel + 1, dtype=jnp.int32)
    at = jnp.sum(upto[:, None, :] < want[None, :, None], axis=2,
                 dtype=jnp.int32)                            # [B, n_sel]
    at = jnp.minimum(at, chunks.shape[1] - 1)
    before = jnp.take_along_axis(upto - per, at, axis=1)
    inside = jnp.cumsum(
        jnp.take_along_axis(chunks, at[:, :, None], axis=1), axis=2,
        dtype=jnp.int32)                                     # [B, n, lane]
    off = jnp.sum(inside < (want[None] - before)[:, :, None], axis=2,
                  dtype=jnp.int32)
    sel = at * lane + jnp.minimum(off, lane - 1)
    return jnp.minimum(sel, t - 1), want[None] <= upto[:, -1:]


def paged_sparse_attend(q, pool_k, pool_v, pool_i, table, pos, qi, wi,
                        topk, scale):
    """Decode-time sparse attention through the block table.  q
    [B, H, hd]; pool_k / pool_v [1+P, Hkv, bs, hd]; pool_i
    [1+P, 1, bs, di]; table [B, nbm]; pos [B]; qi [B, Hi, di]; wi
    [B, Hi] → (float32 [B, H, hd], keys attended a row int32 [B]).

    A row's index keys are read page by page through its table and
    scored against its one query; ``dsa_select`` (the prefill's own
    selection) picks among the keys at or before ``pos``, and the
    positions it holds are gathered from the K and V pools token by
    token and attended."""
    b, h, hd = q.shape
    hkv, bs = pool_k.shape[1], pool_k.shape[2]
    g = h // hkv
    t = table.shape[1] * bs
    n_sel = min(topk, t)
    # whole pages of index keys, rows of the pool seen as [1+P, bs*di]
    ki = pool_i.reshape(pool_i.shape[0], -1)[table].reshape(b, t, -1)
    scores = index_scores(qi[:, :, None], ki, wi[:, None])[:, 0]
    chosen = dsa_select(scores, jnp.arange(t)[None] <= pos[:, None], topk)
    sel, live = dsa_positions(chosen, n_sel)
    # one selected token of one KV head is one row of the pool seen as
    # [(1+P)*Hkv*bs, hd]: a plain row gather, which leaves the pool in
    # the layout the writes and the decode kernel keep it in (indexing
    # dims 0 and 2 at once made XLA re-lay the whole pool)
    blk = jnp.take_along_axis(table, sel // bs, axis=1)
    rows = ((blk[:, :, None] * hkv + jnp.arange(hkv)) * bs
            + (sel % bs)[:, :, None])                    # [B, n, Hkv]
    ks = pool_k.reshape(-1, hd)[rows]                    # [B, n, Hkv, hd]
    vs = pool_v.reshape(-1, hd)[rows]
    qg = q.reshape(b, hkv, g, hd)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, ks,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(live[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", p.astype(vs.dtype), vs,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, h, hd), jnp.sum(live, axis=1, dtype=jnp.int32)


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
