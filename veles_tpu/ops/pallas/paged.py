"""Paged-KV decode attention as a Pallas TPU kernel.

The paged continuous batcher (models/generate.py PagedContinuousBatcher)
keeps every slot's KV cache in a SHARED block pool addressed through
per-slot block tables.  Its first implementation gathered each row's
blocks into a dense [B, H, T, hd] view every tick, ran the dense decode
core, and scattered one position back — ~2x cache traffic vs dense
slots, measured as a ~20% serving-throughput tax on silicon
(docs/perf.md).  This kernel erases the gather: the block table rides
the grid as a SCALAR-PREFETCH argument, so each (batch, kv-head,
block) grid step DMAs its K/V tile straight from the pool block the
table names — the classic paged-attention move (Kwon et al. 2023)
recast for the TPU: instead of pointer-chasing inside the kernel,
Pallas's prefetched index_map picks the pool block per grid step and
Mosaic pipelines the HBM→VMEM copies.

Reads are exactly the live blocks (dead table entries all point at the
reserved dummy block 0, so their copies collapse to one reusable tile
and their scores are masked), and only up to each row's own length —
dense decode by contrast streams every slot's full max_len.

Layout contract (matches PagedContinuousBatcher):
  q      [B, Hq, hd]        query at the position being decoded (rope
                            already applied), Hq = G * Hkv
  pool_k [1+P, Hkv, bs, hd] block 0 reserved as the dummy target
  pool_v [1+P, Hkv, bs, hd]
  table  [B, nbm] int32     per-row pool-block ids (0 = unallocated)
  pos    [B] int32          per-row position just written; keys
                            0..pos[b] inclusive are live
  -> out [B, Hq, hd]

Ground truth: ``paged_attention_reference`` (the gather formulation) —
the tests pin kernel == reference; off-TPU the kernel runs in interpret
mode like every kernel in this package.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.pallas import autodetect_interpret, register_kernel_audit

NEG_INF = -1e30
_LANES = 128

#: min sublane tile for the q block: bf16 wants 16 rows, f32 8 — 16
#: covers both, and the padded rows cost nothing measurable at decode
#: (the kernel is HBM-bound on the K/V stream, not the tiny q tile)
_MIN_G = 16

#: the decode kernel's two pool flavors, keyed by "is the pool a
#: QuantCache": (``pallas_call`` name, VP6xx audit display name).  The
#: first is the HLO instruction's name in a device trace
#: (``%veles_paged_decode.1 = ... custom-call``) — what the benchmark's
#: ``paged_roofline_pct`` sums; a contract recorded in PERF.md that any
#: later implementation of this layer keeps.  Both names come from
#: here so the lint and the trace cannot drift apart.
KERNEL_NAMES = {
    False: ("veles_paged_decode", "paged.decode"),
    True: ("veles_paged_decode_q8", "paged.decode.q8"),
}


def _decode_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                   acc, m, l, *, scale, bs, nbm):
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, NEG_INF)
        l[:] = jnp.zeros_like(l)

    # a block whose first key is already past the row's position is
    # fully dead: skip the whole update (its table entry is 0, so the
    # DMA re-reads the one dummy tile — bandwidth-free after block 0)
    @pl.when(i * bs <= pos_ref[b])
    def _():
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        kpos = i * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kpos <= pos_ref[b]
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, corr)
        l[:] = l[:] * corr + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), l.shape)
        m[:] = jnp.broadcast_to(m_new, m.shape)
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == nbm - 1)
    def _():
        o_ref[0, 0] = (acc[:] / jnp.maximum(l[:, :1], 1e-30)).astype(
            o_ref.dtype)


def _decode_kernel_quant(table_ref, pos_ref, q_ref, k_ref, ks_ref,
                         v_ref, vs_ref, o_ref, acc, m, l, *, scale, bs,
                         nbm):
    """Quantized-pool flavor: the K/V tiles arrive int8 (HBM streams
    one byte per element — the whole point) with per-position f32
    scales, and are dequantized IN KERNEL, in VMEM, with f32
    accumulation throughout.  The scales fold in after the dots
    exactly like the dense QuantCache einsums in ops.attention
    (q·(k·s) == (q·k)·s per position), so the math is the gather
    tick's, just narrower on the wire."""
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, NEG_INF)
        l[:] = jnp.zeros_like(l)

    @pl.when(i * bs <= pos_ref[b])
    def _():
        s = jax.lax.dot_general(
            q_ref[0, 0].astype(jnp.float32),
            k_ref[0, 0].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        # per-position k scales, then the 1/sqrt(hd) logit scale
        s = s * ks_ref[0, 0][:, 0][None, :] * scale
        kpos = i * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kpos <= pos_ref[b]
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, corr)
        l[:] = l[:] * corr + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), l.shape)
        m[:] = jnp.broadcast_to(m_new, m.shape)
        # fold the per-position v scales into the probabilities (the
        # QuantCache move), keep the accumulate f32
        pv = p * vs_ref[0, 0][:, 0][None, :]
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            pv, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == nbm - 1)
    def _():
        o_ref[0, 0] = (acc[:] / jnp.maximum(l[:, :1], 1e-30)).astype(
            o_ref.dtype)


def _resolve_block_g(g, hd, dtype, block_g=None):
    """Resolve the q-group sublane pad: explicit argument > site config
    (``root.common.serve.paged_block_g``) > autotuner winner
    (``veles_tpu.tuner``, kernel ``paged.decode``) > ``_MIN_G``.  Always
    clamped to hold the real group (>= g) and the sublane tile.
    Reachable from the audit hook and every decode trace, so a
    non-integer config value falls through to the tuner instead of
    raising (same contract as :func:`preferred_pool_block`)."""
    if block_g is None:
        from veles_tpu.config import root
        cfg = root.common.get("serve", {})
        block_g = (cfg or {}).get("paged_block_g") if cfg else None
    try:
        block_g = int(block_g or 0)
    except (TypeError, ValueError):      # "auto", garbage
        block_g = 0
    if not block_g:
        try:
            from veles_tpu import tuner
            win = tuner.lookup("paged.decode",
                               tuner.paged_shape_key(hd, g), dtype)
            if win:
                block_g = int(win.get("block_g") or 0)
        except Exception:  # noqa: BLE001 — tuning is advisory
            pass
    return max(int(block_g or 0), g, _MIN_G)


def preferred_pool_block(hd, g=1, dtype=jnp.bfloat16, default=16):
    """The KV pool block size serving should allocate when the caller
    did not pin one: site config (``root.common.serve.paged_block``) >
    autotuner winner > ``default``.  The pool layout is decided at
    admission time by PagedContinuousBatcher — the kernel then simply
    follows whatever block the pool was built with, so THIS is the
    point where a tuned ``paged.decode`` block takes effect.  The
    config value goes through the ONE ``serve.paged_block`` grammar
    (``models.generate.parse_paged_block`` — shared with the engine,
    so the audit hook and serving can never disagree): only an
    explicit positive block pins; ``"auto"``/``-1``/off-values/garbage
    fall through to the tuner — this is reachable from the lint's
    audit hook, so it must never raise on any config value."""
    from veles_tpu.config import root
    cfg = root.common.get("serve", {})
    pinned = (cfg or {}).get("paged_block") if cfg else None
    if pinned is not None:
        try:
            from veles_tpu.models.generate import parse_paged_block
            _, block = parse_paged_block(pinned)
        except (TypeError, ValueError):  # garbage ("fast", [1], ...)
            block = None
        if block:
            return int(block)
    try:
        from veles_tpu import tuner
        win = tuner.lookup("paged.decode", tuner.paged_shape_key(hd, g),
                           dtype)
        if win and win.get("block"):
            return int(win["block"])
    except Exception:  # noqa: BLE001 — tuning is advisory
        pass
    # untuned fallback is sublane-aware: int8 pools (QuantCache) need
    # 32-row tiles on real silicon, bf16/f32 keep the historical 16
    from veles_tpu.ops.pallas import mosaic_sublane_min
    return max(int(default), mosaic_sublane_min(dtype))


def paged_attention_decode(q, pool_k, pool_v, table, pos, scale=None,
                           interpret=None, block_g=None):
    """One decode step of attention over a paged KV pool (see module
    docstring for the layout contract).  Returns [B, Hq, hd].

    ``pool_k``/``pool_v`` may be plain arrays OR
    ``ops.attention.QuantCache`` pairs (int8 data [1+P, Hkv, bs, hd] +
    f32 per-position scales [1+P, Hkv, bs, 1]) — the quantized pool
    streams one byte per KV element from HBM and dequantizes in
    kernel with f32 accumulation (``_decode_kernel_quant``).

    ``block_g`` — the q-group sublane pad (rows per grid step); unset,
    it resolves through config > autotuner > ``_MIN_G``; quantized
    pools key the tuner lookup by the POOL dtype (int8), matching how
    ``tuner.sweeps.sweep_paged(dtype="int8")`` records winners."""
    from veles_tpu.ops.attention import QuantCache
    quant = isinstance(pool_k, QuantCache)
    kd = pool_k.data if quant else pool_k
    b, hq, hd = q.shape
    npool, hkv, bs, _ = kd.shape
    nbm = table.shape[1]
    if hq % hkv:
        raise ValueError("Hq %d %% Hkv %d != 0" % (hq, hkv))
    g = hq // hkv
    gp = _resolve_block_g(g, hd, kd.dtype if quant else q.dtype,
                          block_g)
    scale = (hd ** -0.5) if scale is None else scale

    # [B, Hq, hd] -> [B, Hkv, Gp, hd]: group queries under their kv
    # head; pad the group dim up to the sublane tile (padded rows carry
    # zeros — their softmax is uniform over live keys, finite, and the
    # rows are sliced off below)
    qg = q.reshape(b, hkv, g, hd)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))

    def at_q(bi, h, i, tbl, ps):
        return (bi, h, 0, 0)

    def at_pool(bi, h, i, tbl, ps):
        return (tbl[bi, i], h, 0, 0)

    if quant:
        kernel = functools.partial(_decode_kernel_quant, scale=scale,
                                   bs=bs, nbm=nbm)
        in_specs = [
            pl.BlockSpec((1, 1, gp, hd), at_q),
            pl.BlockSpec((1, 1, bs, hd), at_pool),   # k int8
            pl.BlockSpec((1, 1, bs, 1), at_pool),    # k scales
            pl.BlockSpec((1, 1, bs, hd), at_pool),   # v int8
            pl.BlockSpec((1, 1, bs, 1), at_pool),    # v scales
        ]
        operands = (qg, pool_k.data, pool_k.scale, pool_v.data,
                    pool_v.scale)
    else:
        kernel = functools.partial(_decode_kernel, scale=scale, bs=bs,
                                   nbm=nbm)
        in_specs = [
            pl.BlockSpec((1, 1, gp, hd), at_q),
            pl.BlockSpec((1, 1, bs, hd), at_pool),
            pl.BlockSpec((1, 1, bs, hd), at_pool),
        ]
        operands = (qg, pool_k, pool_v)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv, nbm),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, gp, hd), at_q),
            scratch_shapes=[
                pltpu.VMEM((gp, hd), jnp.float32),
                pltpu.VMEM((gp, _LANES), jnp.float32),
                pltpu.VMEM((gp, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, hd), q.dtype),
        interpret=autodetect_interpret(interpret),
        name=KERNEL_NAMES[quant][0],
    )(table.astype(jnp.int32), pos.astype(jnp.int32), *operands)
    return out[:, :, :g].reshape(b, hq, hd)


def paged_attention_reference(q, pool_k, pool_v, table, pos,
                              scale=None):
    """Gather-formulation ground truth (identical math to the dense
    decode einsum in ops.attention.mha_step): materialize each row's
    blocks densely, run a masked softmax.  QuantCache pools
    dequantize the gathered view (data × per-position scale — exactly
    what the in-kernel fold computes).  Used by the tests and as the
    documentation of the kernel's exact semantics."""
    from veles_tpu.ops.attention import QuantCache
    b, hq, hd = q.shape
    kd = pool_k.data if isinstance(pool_k, QuantCache) else pool_k
    _, hkv, bs, _ = kd.shape
    nbm = table.shape[1]
    g = hq // hkv
    scale = (hd ** -0.5) if scale is None else scale

    def dense(pool):
        if isinstance(pool, QuantCache):
            v = (pool.data[table].astype(jnp.float32)
                 * pool.scale[table])         # [B, nbm, Hkv, bs, hd]
        else:
            v = pool[table]                   # [B, nbm, Hkv, bs, hd]
        v = jnp.moveaxis(v, 2, 1)             # [B, Hkv, nbm, bs, hd]
        return v.reshape(b, hkv, nbm * bs, hd)

    k = dense(pool_k)
    v = dense(pool_v)
    qg = q.reshape(b, hkv, g, hd)
    s = jnp.einsum("bkgd,bktd->bkgt", qg, k,
                   preferred_element_type=jnp.float32) * scale
    live = (jnp.arange(nbm * bs)[None, None, None, :]
            <= pos[:, None, None, None])
    s = jnp.where(live, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,bktd->bkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, hq, hd).astype(q.dtype)


# --------------------------------------------------------------------------
# VP6xx launch-audit hook (analysis.numerics_audit): the decode
# kernel's launch geometry as data — pure arithmetic, nothing traced.
# --------------------------------------------------------------------------

def audit_launch(hd, bs, g=1, dtype=jnp.bfloat16, nbm=32, masked=True,
                 checked=(), q_dtype=jnp.bfloat16):
    """Launch description for one paged-decode configuration.  ``bs``
    is the KV pool block (PagedContinuousBatcher ``block``), ``g`` the
    query-group size (Hq/Hkv) — padded to the sublane tile exactly as
    ``paged_attention_decode`` does.  ``dtype`` is the POOL dtype:
    int8 describes the quantized-pool kernel variant (int8 K/V tiles +
    f32 per-position scale tiles, float q/out in ``q_dtype``)."""
    import numpy as np
    gp = max(g, _MIN_G)
    quant = np.dtype(dtype) == np.dtype(np.int8)
    if quant:
        blocks = [("q", (1, 1, gp, hd), q_dtype, {"full_lane": True}),
                  ("k", (1, 1, bs, hd), dtype, {"full_lane": True}),
                  ("k_scale", (1, 1, bs, 1), jnp.float32,
                   {"full_lane": True}),
                  ("v", (1, 1, bs, hd), dtype, {"full_lane": True}),
                  ("v_scale", (1, 1, bs, 1), jnp.float32,
                   {"full_lane": True}),
                  ("o", (1, 1, gp, hd), q_dtype, {"full_lane": True})]
    else:
        blocks = [("q", (1, 1, gp, hd), dtype, {"full_lane": True}),
                  ("k", (1, 1, bs, hd), dtype, {"full_lane": True}),
                  ("v", (1, 1, bs, hd), dtype, {"full_lane": True}),
                  ("o", (1, 1, gp, hd), dtype, {"full_lane": True})]
    return [{
        "kernel": KERNEL_NAMES[bool(quant)][1],
        "masked": masked, "checked": checked,
        "blocks": blocks,
        "scratch": [("acc", (gp, hd), jnp.float32),
                    ("m", (gp, _LANES), jnp.float32),
                    ("l", (gp, _LANES), jnp.float32)],
        # every row reads up to its own length; dead blocks hit the
        # reserved dummy block and their scores are masked
        "grid_axes": [("pool-blocks", nbm * bs, bs)],
    }]


@register_kernel_audit("paged")
def _configured_launches():
    """What ``--serve`` with paged KV would actually launch at the
    flagship head dim: the pool block through the same config > tuner >
    default chain the batcher uses (:func:`preferred_pool_block`), the
    q-group pad through :func:`_resolve_block_g` — so an over-budget
    tuned winner fails the lint exactly like a hand-misconfigured
    ``paged_block``.  BOTH pool flavors are audited: the bf16 pool and
    the int8 (``cache_dtype="int8"``) QuantCache pool, each resolved
    at its own dtype key."""
    hd, g = 128, 1
    launches = []
    for dtype in (jnp.bfloat16, jnp.int8):
        bs = preferred_pool_block(hd, g, dtype)
        launches.extend(audit_launch(
            hd, bs, g=_resolve_block_g(g, hd, dtype), dtype=dtype))
    return launches
