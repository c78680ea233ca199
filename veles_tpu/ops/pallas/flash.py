"""Flash attention as a Pallas TPU kernel.

Online-softmax tiling (Dao et al.) mapped to the TPU memory hierarchy:
grid = (batch·heads, q-blocks, k-blocks) executed with the k dimension
innermost; the f32 accumulator and the running (max, sum) statistics
live in VMEM scratch that persists across the inner k sweep, so each q
tile streams every k/v tile through VMEM exactly once — O(T·block) VMEM
instead of the O(T²) score matrix.  Matmuls hit the MXU with f32
accumulation (``preferred_element_type``); causal blocks entirely
off-diagonal are skipped (``@pl.when``), halving the work for
autoregressive models.

The backward pass is fused too (FlashAttention-2): the forward saves
one log-sum-exp residual per q row, and two Pallas kernels produce dQ
(k innermost) and dK/dV (q innermost) from it — no T² matrix in either
direction.  ``backward="recompute"`` keeps the differentiate-through-
blockwise path as a cross-check oracle."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.pallas import autodetect_interpret, register_kernel_audit

NEG_INF = -1e30
_LANES = 128          # m/l scratch padded to a full lane tile

#: the three kernels, by the key ``audit_launch(kernels=...)`` selects
#: them with: (``pallas_call`` name, VP6xx audit display name).  The
#: first is the HLO instruction's name in a device trace
#: (``%veles_flash_fwd.1 = ... custom-call``) — what the benchmark's
#: ``flash_roofline_pct`` sums; a contract recorded in PERF.md that any
#: later implementation of this layer keeps.  Both names come from
#: here so the lint and the trace cannot drift apart.
KERNEL_NAMES = {
    "forward": ("veles_flash_fwd", "flash.forward"),
    "bwd_dq": ("veles_flash_bwd_dq", "flash.bwd_dq"),
    "bwd_dkv": ("veles_flash_bwd_dkv", "flash.bwd_dkv"),
}


def _masked_scores(x_ref, y_ref, row_start, col_start, scale, causal, tk,
                   rows_are_q, window=None):
    """Scaled score tile xyᵀ with its padding+causal(+sliding-window)
    validity mask — shared by the forward and both backward kernels so
    the three can never desynchronize.  ``rows_are_q``: rows index
    queries and columns keys (forward / dQ); False = the transposed
    dK/dV layout.  Dot inputs keep their storage dtype (bf16 rides the
    MXU at full rate); preferred_element_type pins f32 accumulation."""
    s = jax.lax.dot_general(
        x_ref[0], y_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    rows = row_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = col_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    k_idx, q_idx = (cols, rows) if rows_are_q else (rows, cols)
    valid = k_idx < tk                  # key padding
    if causal:
        valid = valid & (q_idx >= k_idx)
        if window is not None:
            valid = valid & (q_idx - k_idx < window)
    return s, valid


def _block_live(qi, ki, block_q, block_k, causal, window):
    """Whether a (q-block, k-block) tile intersects the causal(+window)
    band at all — dead tiles are skipped entirely (@pl.when)."""
    if not causal:
        return True
    live = ki * block_k <= qi * block_q + block_q - 1
    if window is not None:
        # newest key in the block must still be inside the oldest
        # query's window:  k_max >= q_min - window + 1
        live = live & (ki * block_k + block_k - 1
                       >= qi * block_q - window + 1)
    return live


# --------------------------------------------------------------------------
# Sliding-window grid shrink + causal copy elision.
#
# With a window, each q block's live k blocks form a STATIC-width span
# (window/block geometry), so the inner grid axis only needs that many
# steps instead of all T/block_k — a T=8192/window=1024 forward launches
# ~1/7 of the tiles.  The kernel derives the true k-block index as
# lo(qi) + kj.  Independently, for plain causal masks the dead
# off-diagonal tiles clamp their BlockSpec index to the last live block:
# consecutive grid steps that map to the same block elide the HBM→VMEM
# copy, so skipped tiles stop costing bandwidth too.
# --------------------------------------------------------------------------

def _k_lo(qi, block_q, block_k, window):
    """First live k block for q block ``qi`` under a sliding window."""
    return jnp.maximum(0, (qi * block_q - window + 1) // block_k)


def _k_span(block_q, block_k, window, nk):
    """Static width of the live k-block span per q block."""
    if window is None:
        return nk
    return min(nk, (block_q + window - 2) // block_k + 2)


def _q_lo(ki, block_q, block_k):
    """First live (causal) q block for k block ``ki``."""
    return (ki * block_k) // block_q


def _q_span(block_q, block_k, window, nq):
    """Static width of the live q-block span per k block (window)."""
    if window is None:
        return nq
    return min(nq, (block_k + window - 2) // block_q + 2)


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m, l,
            *, scale, causal, block_q, block_k, nk, nk_grid, tk, window):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    # with a window the inner axis walks only the live span: the true
    # k-block index is lo(qi) + kj
    ki = (kj if window is None
          else _k_lo(qi, block_q, block_k, window) + kj)

    @pl.when(kj == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, NEG_INF)
        l[:] = jnp.zeros_like(l)

    # skip tiles entirely outside the causal(+window) band (and the
    # shrunken span's overshoot past the last real k block)
    live = _block_live(qi, ki, block_q, block_k, causal, window)
    if window is not None:
        live = live & (ki < nk)

    @pl.when(live)
    def _():
        s, valid = _masked_scores(q_ref, k_ref, qi * block_q,
                                  ki * block_k, scale, causal, tk,
                                  rows_are_q=True, window=window)
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
        # probabilities cast DOWN to v's dtype for the MXU; the f32
        # running accumulator preserves precision across k blocks
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk_grid - 1)
    def _():
        lsum = jnp.maximum(l[:, :1], 1e-30)
        out = acc[:] / lsum
        o_ref[0] = out.astype(o_ref.dtype)
        # log-sum-exp of the scaled scores per q row — the only residual
        # the fused backward needs (p = exp(s - lse) reconstructs
        # exactly).  Stored lane-broadcast (block_q, _LANES): Mosaic
        # requires output block minors (divisible-by-8, 128), which a
        # (1, block_q) row tile violates; the lane copies are sliced
        # off right after the pallas_call.
        lse_ref[0] = m[:] + jnp.log(lsum)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, scale, causal, block_q, block_k,
                   nk, nk_grid, tk, window):
    """dQ: grid (bh, q-blocks, k-span), k innermost; dq accumulates in
    f32 VMEM scratch across the k sweep.
        p  = exp(s - lse);  dp = dO·Vᵀ;  ds = p⊙(dp - Δ)·scale
        dq += ds·K
    """
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    ki = (kj if window is None
          else _k_lo(qi, block_q, block_k, window) + kj)

    @pl.when(kj == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    live = _block_live(qi, ki, block_q, block_k, causal, window)
    if window is not None:
        live = live & (ki < nk)

    @pl.when(live)
    def _():
        s, valid = _masked_scores(q_ref, k_ref, qi * block_q,
                                  ki * block_k, scale, causal, tk,
                                  rows_are_q=True, window=window)
        p = jnp.where(valid, jnp.exp(s - lse_ref[0][:, :1]), 0.0)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        k = k_ref[0]
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk_grid - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k, nq, nq_grid, tk, window):
    """dK, dV: grid (bh, k-blocks, q-span), q innermost; both
    accumulators live in f32 VMEM scratch across the q sweep.  The
    score tile keeps the forward orientation (rows = q) so the per-row
    lse/Δ residuals broadcast along lanes without a transpose; the
    k-major products contract over the q rows instead:
        p  = exp(s - lse);          dv += pᵀ·dO
        dp = dO·Vᵀ;  ds = p⊙(dp - Δ)·scale;  dk += dsᵀ·Q
    Padded q rows contribute nothing (their dO and Δ are zero)."""
    ki = pl.program_id(1)
    qj = pl.program_id(2)
    qi = (qj if window is None
          else _q_lo(ki, block_q, block_k) + qj)

    @pl.when(qj == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    live = _block_live(qi, ki, block_q, block_k, causal, window)
    if window is not None:
        live = live & (qi < nq)

    @pl.when(live)
    def _():
        s, valid = _masked_scores(q_ref, k_ref, qi * block_q,
                                  ki * block_k, scale, causal, tk,
                                  rows_are_q=True,
                                  window=window)              # [bq, bk]
        p = jnp.where(valid, jnp.exp(s - lse_ref[0][:, :1]), 0.0)
        do = do_ref[0]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bq, bk]
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        q = q_ref[0]
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qj == nq_grid - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _pad_to(x, axis, mult):
    t = x.shape[axis]
    pad = (-t) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _d64_cap(t):
    """Default block cap for the d<=64 VMEM regime: up to 1024, rounded
    to the operand's own padded length (caps follow each operand — in
    non-causal cross-attention tk != tq, and a block_k cap from tq
    would pad K/V up to 8x for nothing)."""
    return max(128, min(1024, -(-t // 128) * 128))


def _resolve_blocks(tq, tk, d, dtype, block_q=None, block_k=None,
                    block_q_dq=None, block_k_dq=None, block_q_dkv=None,
                    block_k_dkv=None):
    """Resolve all six block choices for one flash launch.

    Per knob, first hit wins: explicit argument > site-config key
    (``root.common.engine.flash.*``, with ``*_d64`` variants for head
    dim <= 64) > autotuner winner (``veles_tpu.tuner``, keyed by
    kernel/shape-bucket/dtype/mesh) > built-in default.  The built-in
    forward default is 128 (d>64) or the d64 cap; the backward kernels
    default to the *forward's resolved* geometry — the pre-split
    behavior — so an untuned, unconfigured launch is unchanged.

    d<=64 halves the k/v/q VMEM slabs vs the d=128 the flashtune grid
    swept, so blocks up to 1024 fit: forward, dQ and dK/dV at
    1024x1024 all compile under Mosaic's default scoped-VMEM limit on
    a v5e with JAX 0.9.0 / libtpu 0.0.34 and match the XLA reference
    at the flagship's (16,12,1024,64) shape (chip_smoke.py, PR 21).
    That they also WIN was measured 2026-08-01 and not re-measured on
    this machine (then: fwd+bwd 16.57 ms vs 17.44 at the d=128-baked
    (512,512) and 20.77 XLA-naive; 1.9x at (2,8,8192,64))."""
    from veles_tpu.config import root
    fcfg = root.common.engine.flash
    small = d <= 64
    sfx = "_d64" if small else ""

    def cfg(key):
        v = fcfg.get(key + sfx)
        return None if v is None else int(v)

    tuned = {}
    need_tuner = any(b is None and cfg(key) is None for b, key in (
        (block_q, "block_q"), (block_k, "block_k"),
        (block_q_dq, "block_q_dq"), (block_k_dq, "block_k_dq"),
        (block_q_dkv, "block_q_dkv"), (block_k_dkv, "block_k_dkv")))
    if need_tuner:
        try:
            from veles_tpu import tuner
            # fwd/dq grids are q-major (their sweep sizes with tq);
            # the dkv grid walks the KEY axis, so in cross-attention
            # (tq != tk) its winner comes from the tk bucket
            for kern, alias, t in (("flash.fwd", "", tq),
                                   ("flash.bwd_dq", "_dq", tq),
                                   ("flash.bwd_dkv", "_dkv", tk)):
                win = tuner.lookup(kern, tuner.flash_shape_key(t, d),
                                   dtype)
                if win:
                    for wk in ("block_q", "block_k"):
                        if wk in win:
                            tuned[wk + alias] = int(win[wk])
        except Exception:  # noqa: BLE001 — tuning is advisory, never fatal
            pass

    def pick(explicit, key, default):
        if explicit is not None:
            return int(explicit)
        v = cfg(key)
        if v is not None:
            return v
        return int(tuned.get(key, default))

    block_q = pick(block_q, "block_q",
                   _d64_cap(tq) if small else 128)
    block_k = pick(block_k, "block_k",
                   _d64_cap(tk) if small else 128)
    return (block_q, block_k,
            pick(block_q_dq, "block_q_dq", block_q),
            pick(block_k_dq, "block_k_dq", block_k),
            pick(block_q_dkv, "block_q_dkv", block_q),
            pick(block_k_dkv, "block_k_dkv", block_k))


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=None, backward="fused",
                    window=None, block_q_dq=None, block_k_dq=None,
                    block_q_dkv=None, block_k_dkv=None):
    """q, k, v: [B, H, T, D] → [B, H, T, D].  ``scale=None`` → 1/√D (same
    default as every entry point in ops.attention).

    Block sizes resolve per kernel — forward (``block_q``/``block_k``),
    dQ (``block_q_dq``/``block_k_dq``) and dK/dV (``block_q_dkv``/
    ``block_k_dkv``) grids are independent: explicit argument > site
    config (``root.common.engine.flash.*``, ``*_d64`` keys for head
    dim <= 64) > autotuner winner (``veles_tpu.tuner``; populate with
    ``veles-tpu-tune sweep`` or ``bench.py --phase flashtune``) >
    built-in default (see :func:`_resolve_blocks`).  Unset backward
    blocks inherit the forward's resolved geometry.

    Differentiable both ways: ``backward="fused"`` (default) runs the
    Pallas dQ and dK/dV kernels against the forward's saved log-sum-exp
    residual (the FlashAttention-2 recipe — no T² matrix, two extra
    passes over K/V); ``backward="recompute"`` differentiates through the
    pure-jnp online-softmax (ops.attention.blockwise_attention) instead —
    slower, kept as the cross-check oracle for the kernel tests."""
    if causal and q.shape[-2] != k.shape[-2]:
        raise ValueError("causal flash kernel assumes tq == tk")
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError("window must be >= 1")
        window = int(window)
    if not (q.dtype == k.dtype == v.dtype):
        # dot operands keep their storage dtype (MXU-native); mixed
        # inputs must be reconciled by the caller, not silently upcast
        raise ValueError(
            "flash_attention needs matching q/k/v dtypes, got %s/%s/%s"
            % (q.dtype, k.dtype, v.dtype))
    if backward not in ("fused", "recompute"):
        raise ValueError("backward must be 'fused' or 'recompute'")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    blocks = _resolve_blocks(
        q.shape[-2], k.shape[-2], q.shape[-1], q.dtype,
        block_q=block_q, block_k=block_k,
        block_q_dq=block_q_dq, block_k_dq=block_k_dq,
        block_q_dkv=block_q_dkv, block_k_dkv=block_k_dkv)
    return _flash_fn(causal, float(scale), blocks,
                     autodetect_interpret(interpret), backward,
                     window)(q, k, v)


@functools.lru_cache(maxsize=None)
def _flash_fn(causal, scale, blocks, interpret, backward,
              window=None):
    from veles_tpu.ops import attention as att
    block_q, block_k = blocks[:2]
    bwd_blocks = blocks[2:]

    @jax.custom_vjp
    def f(q, k, v):
        out, _ = _forward(q, k, v, causal, scale, block_q, block_k,
                          interpret, window)
        return out

    def fwd(q, k, v):
        out, lse = _forward(q, k, v, causal, scale, block_q, block_k,
                            interpret, window)
        # the recompute oracle only re-derives from q/k/v — saving
        # (out, lse) there would hold an extra [B,H,T,D] + [B,H,T]
        # activation per attention call for nothing
        res = (q, k, v, out, lse) if backward == "fused" else (q, k, v)
        return out, res

    def bwd(res, g):
        if backward == "fused":
            q, k, v, out, lse = res
            return _backward(q, k, v, out, lse, g, causal, scale,
                             bwd_blocks, interpret, window)
        q, k, v = res
        _, vjp = jax.vjp(
            lambda q_, k_, v_: att.blockwise_attention(
                q_, k_, v_, causal=causal, scale=scale,
                window=window), q, k, v)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return jax.jit(f)


def _blocks(q, k, v, block_q, block_k):
    b, h, tq, d = q.shape
    tk = k.shape[-2]
    block_q = min(block_q, max(tq, 8))
    block_k = min(block_k, max(tk, 8))
    qp = _pad_to(q.reshape(b * h, tq, d), 1, block_q)
    kp = _pad_to(k.reshape(b * h, tk, d), 1, block_k)
    vp = _pad_to(v.reshape(b * h, tk, d), 1, block_k)
    return (qp, kp, vp, block_q, block_k,
            qp.shape[1] // block_q, kp.shape[1] // block_k)


#: bh and q/k-blocks carry no cross-iteration state (scratch resets at
#: inner index 0) — declaring them parallel lets Mosaic re-order /
#: parallelize them; only the innermost sweep is a sequential reduction
_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _kv_index_map(block_q, block_k, causal, window, nk):
    """BlockSpec index map for k/v on a (bh, q-block, k-inner) grid:
    resolves the shrunken window span to true k blocks, and clamps dead
    causal tiles onto the diagonal block so the pipeline elides their
    HBM→VMEM copies (same index on consecutive steps = no copy)."""
    def index_map(bh, qi, kj):
        ki = (kj if window is None
              else _k_lo(qi, block_q, block_k, window) + kj)
        if causal:
            hi = (qi * block_q + block_q - 1) // block_k
            ki = jnp.minimum(ki, jnp.minimum(hi, nk - 1))
        return (bh, ki, 0)
    return index_map


def _forward(q, k, v, causal, scale, block_q, block_k, interpret,
             window=None):
    b, h, tq, d = q.shape
    tk = k.shape[-2]
    qp, kp, vp, block_q, block_k, nq, nk = _blocks(q, k, v, block_q,
                                                   block_k)
    nk_grid = _k_span(block_q, block_k, window, nk) if causal else nk

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, nk=nk, nk_grid=nk_grid,
        tk=tk, window=window)

    kv_map = _kv_index_map(block_q, block_k, causal, window, nk)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk_grid),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qp.shape, q.dtype),
            jax.ShapeDtypeStruct(qp.shape[:2] + (_LANES,), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name=KERNEL_NAMES["forward"][0],
    )(qp, kp, vp)
    # residual kept lean: drop the lane copies (the backward re-broadcasts)
    return out[:, :tq].reshape(b, h, tq, d), lse[:, :, 0]


def _backward(q, k, v, out, lse, g, causal, scale, blocks, interpret,
              window=None):
    """FlashAttention-2 backward: Δ = rowsum(dO⊙O) in plain XLA (one
    fused elementwise+reduce), then the dQ kernel (k innermost) and the
    dK/dV kernel (q innermost).  ``blocks`` carries each kernel's OWN
    (block_q, block_k) pair — the dq and dkv grids are independent of
    the forward's geometry and of each other (dq streams K/V per q
    tile, dkv streams Q/dO per k tile; their optimal tile trade-offs
    differ, see veles_tpu/tuner).  Each launch pads its operands to its
    own block multiple.  Gradients come back in the inputs' dtype; all
    accumulation is f32."""
    bq_dq, bk_dq, bq_dkv, bk_dkv = blocks
    b, h, tq, d = q.shape
    tk = k.shape[-2]
    # residuals at full resolution, padded per launch below.  Per-row
    # residuals enter the kernels lane-broadcast — Mosaic wants
    # (sublane % 8, lane % 128) block minors, which (1, block_q) row
    # tiles violate; one fused XLA broadcast each, tiny next to the
    # kernels' K/V traffic
    do = g.reshape(b * h, tq, d).astype(q.dtype)
    delta = jnp.sum(do.astype(jnp.float32)
                    * out.reshape(b * h, tq, d).astype(jnp.float32),
                    axis=-1)
    lse = jnp.broadcast_to(lse[:, :, None], lse.shape + (_LANES,))
    delta = jnp.broadcast_to(delta[:, :, None], delta.shape + (_LANES,))

    # ---------------------------------------------------- dQ launch
    qp, kp, vp, block_q, block_k, nq, nk = _blocks(q, k, v, bq_dq,
                                                   bk_dq)
    dop = _pad_to(do, 1, block_q)
    lse_p = _pad_to(lse, 1, block_q)
    delta_p = _pad_to(delta, 1, block_q)
    nk_grid = _k_span(block_q, block_k, window, nk) if causal else nk
    kv_map = _kv_index_map(block_q, block_k, causal, window, nk)
    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, a, i: (bh, a, 0))
    r_spec = pl.BlockSpec((1, block_q, _LANES),
                          lambda bh, a, i: (bh, a, 0))
    kv_spec = pl.BlockSpec((1, block_k, d), kv_map)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk,
                          nk_grid=nk_grid, tk=tk, window=window),
        grid=(b * h, nq, nk_grid),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, r_spec, r_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name=KERNEL_NAMES["bwd_dq"][0],
    )(qp, kp, vp, dop, lse_p, delta_p)

    # --------------------------------------------------- dK/dV launch
    # q innermost: swap the roles of the two block axes in the specs;
    # the q/do/residual index map mirrors _kv_index_map (window span
    # shrink + clamp of the dead below-diagonal tiles onto the first
    # live q block for copy elision)
    qp, kp, vp, block_q, block_k, nq, nk = _blocks(q, k, v, bq_dkv,
                                                   bk_dkv)
    dop = _pad_to(do, 1, block_q)
    lse_p = _pad_to(lse, 1, block_q)
    delta_p = _pad_to(delta, 1, block_q)
    nq_grid = _q_span(block_q, block_k, window, nq) if causal else nq

    def q_map3(bh, ki, qj):
        qi = (qj if window is None
              else _q_lo(ki, block_q, block_k) + qj)
        if causal:
            lo = _q_lo(ki, block_q, block_k)
            qi = jnp.minimum(jnp.maximum(qi, lo), nq - 1)
        return (bh, qi, 0)

    q_spec2 = pl.BlockSpec((1, block_q, d), q_map3)
    r_spec2 = pl.BlockSpec((1, block_q, _LANES), q_map3)
    kv_spec2 = pl.BlockSpec((1, block_k, d), lambda bh, a, i: (bh, a, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq,
                          nq_grid=nq_grid, tk=tk, window=window),
        grid=(b * h, nk, nq_grid),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, r_spec2, r_spec2],
        out_specs=[kv_spec2, kv_spec2],
        out_shape=[jax.ShapeDtypeStruct(kp.shape, k.dtype),
                   jax.ShapeDtypeStruct(vp.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name=KERNEL_NAMES["bwd_dkv"][0],
    )(qp, kp, vp, dop, lse_p, delta_p)

    return (dq[:, :tq].reshape(b, h, tq, d),
            dk[:, :tk].reshape(b, h, tk, d),
            dv[:, :tk].reshape(b, h, tk, d))


# --------------------------------------------------------------------------
# VP6xx launch-audit hook (analysis.numerics_audit): the SAME geometry
# the pallas_calls above launch — block tiles per in/out spec, VMEM
# scratch per scratch_shapes, grid divisibility — described as data.
# Pure arithmetic: nothing is traced, compiled, or dispatched.
# --------------------------------------------------------------------------

def audit_launch(tq, tk, d, dtype=jnp.bfloat16, causal=False,
                 block_q=None, block_k=None, window=None, masked=True,
                 checked=(), block_q_dq=None, block_k_dq=None,
                 block_q_dkv=None, block_k_dkv=None, kernels=None):
    """Launch descriptions for one flash configuration — forward, dQ
    and dK/dV kernels, each at its OWN (block_q, block_k) geometry
    (unset backward blocks inherit the forward's, the same rule
    ``_resolve_blocks`` applies).  ``kernels`` optionally restricts the
    output to a subset of ``{"forward", "bwd_dq", "bwd_dkv"}`` — the
    autotuner audits one candidate kernel at a time.  ``masked=True``
    reflects what the kernels actually do (``_pad_to`` + validity mask
    — the VP601 escape hatch); the tests pin a ``masked=False``
    description to prove VP601 fires when a kernel does not."""
    if block_q is None:
        block_q = 128
    if block_k is None:
        block_k = 128
    # the lane dim of every head-dim tile IS the model's head dim —
    # geometry, not a tunable block choice (full_lane exempts it from
    # VP600; d=64 models are real and the kernel handles the half-tile)
    hd = {"full_lane": True}

    def geom(bq, bk):
        bq = min(int(bq), max(tq, 8))
        bk = min(int(bk), max(tk, 8))
        qkv = [("q", (1, bq, d), dtype, hd),
               ("k", (1, bk, d), dtype, hd),
               ("v", (1, bk, d), dtype, hd)]
        grid = [("q-blocks", tq, bq), ("k-blocks", tk, bk)]
        resid = [("do", (1, bq, d), dtype, hd),
                 ("lse", (1, bq, _LANES), jnp.float32),
                 ("delta", (1, bq, _LANES), jnp.float32)]
        return bq, bk, qkv, grid, resid

    launches = []
    if kernels is None or "forward" in kernels:
        bq, bk, qkv, grid, _ = geom(block_q, block_k)
        launches.append({
            "kernel": KERNEL_NAMES["forward"][1], "masked": masked,
            "checked": checked,
            "blocks": qkv + [("o", (1, bq, d), dtype, hd),
                             ("lse", (1, bq, _LANES), jnp.float32)],
            "scratch": [("acc", (bq, d), jnp.float32),
                        ("m", (bq, _LANES), jnp.float32),
                        ("l", (bq, _LANES), jnp.float32)],
            "grid_axes": grid,
        })
    if kernels is None or "bwd_dq" in kernels:
        bq, bk, qkv, grid, resid = geom(
            block_q if block_q_dq is None else block_q_dq,
            block_k if block_k_dq is None else block_k_dq)
        launches.append({
            "kernel": KERNEL_NAMES["bwd_dq"][1], "masked": masked,
            "checked": checked,
            "blocks": qkv + resid + [("dq", (1, bq, d), dtype, hd)],
            "scratch": [("dq_acc", (bq, d), jnp.float32)],
            "grid_axes": grid,
        })
    if kernels is None or "bwd_dkv" in kernels:
        bq, bk, qkv, grid, resid = geom(
            block_q if block_q_dkv is None else block_q_dkv,
            block_k if block_k_dkv is None else block_k_dkv)
        launches.append({
            "kernel": KERNEL_NAMES["bwd_dkv"][1], "masked": masked,
            "checked": checked,
            "blocks": qkv + resid + [("dk", (1, bk, d), dtype, hd),
                                     ("dv", (1, bk, d), dtype, hd)],
            "scratch": [("dk_acc", (bk, d), jnp.float32),
                        ("dv_acc", (bk, d), jnp.float32)],
            "grid_axes": grid,
        })
    return launches


@register_kernel_audit("flash")
def _configured_launches():
    """The block sizes ``flash_attention`` would actually pick — the
    full resolution chain (site config > tuner winners > defaults,
    exactly ``_resolve_blocks``), audited at both head-dim regimes (the
    d=128 flashtune keys and the d<=64 ``*_d64`` keys) in the
    MXU-native bf16.  A tuned-but-over-budget winner therefore fails
    ``veles-tpu-lint --numerics`` the same way a hand-misconfigured
    site key always has."""
    launches = []
    t = 1024
    for d in (128, 64):
        bq, bk, bq_dq, bk_dq, bq_dkv, bk_dkv = _resolve_blocks(
            t, t, d, jnp.bfloat16)
        launches += audit_launch(
            t, t, d, causal=True, block_q=bq, block_k=bk,
            block_q_dq=bq_dq, block_k_dq=bk_dq,
            block_q_dkv=bq_dkv, block_k_dkv=bk_dkv)
    return launches
