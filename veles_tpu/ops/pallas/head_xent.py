"""The LM head and its loss statistics in one kernel
(``ops.losses.lm_head_xent``'s forward on the TPU).

One grid step is a tile of rows x a tile of the vocabulary: the tile of
``h @ Wᵀ`` on the MXU (operands in the compute dtype, float32
accumulation), written ONCE to HBM as the backward's residual, and —
while it is still in VMEM — folded into each row's running statistics:
the online maximum and rescaled sum of exponentials (so the last tile
yields ``lse``), the label's logit, and the first-index argmax.
Columns past the vocabulary's end (its last tile is ragged) are masked
out of all three.  No pass reads the logits back; the per-row vectors
``lse``, ``z[label]`` and ``argmax`` are all the loss needs.

The statistics are kept per LANE, not per row: lane ``c`` of a row
holds the maximum, the sum of exponentials under it, the label's logit
and the first index of that maximum over the columns ``≡ c (mod 128)``
seen so far.  A tile's update is then elementwise over whole vregs, a
chunk of rows at a time (the chunk's logits and statistics stay in
registers), and the one reduction across lanes is made once a row tile,
after its last vocabulary tile: ``lse = M + log Σ_c l_c e^{m_c - M}``
with ``M = max_c m_c``, and the argmax is the least index among the
lanes whose maximum is ``M`` (each lane keeps its first, so the least of
them is the row's first — ``jnp.argmax``'s tie rule).

Rows are the outer grid axis and the vocabulary the inner one: a row
tile's statistics live in scratch across the vocabulary, and ``W`` is
streamed once a row tile — so the row tile is tall, which bounds
``W``'s re-reads."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.pallas import autodetect_interpret, register_kernel_audit

#: (``pallas_call`` name, VP6xx audit display name).  The first is the
#: HLO instruction's name in a device trace (``%veles_head_xent.1 =
#: ... custom-call``): a contract recorded in PERF.md that any later
#: implementation of this layer keeps.
KERNEL_NAMES = {"forward": ("veles_head_xent", "head_xent.forward")}

NEG_INF = -1e30
_LANES = 128

#: the tallest row tile and the widest vocabulary tile.  Measured on
#: the v5e, the head and loss forward and backward at 16 x 1,024 rows,
#: d 768 (ms): 1024 x 2048 25.7, 512 x 2048 25.6, 1024 x 1024 26.5,
#: 2048 x 1024 26.5 (XLA 49.4); at 4 x 2,048, d 2,048: 512 x 2048 34.2,
#: 1024 x 1024 34.8 (XLA 42.3) (PERF.md, section 6)
_ROW_TILE = 1024
_VOCAB_TILE = 2048
#: the least row tile: a bf16 operand's sublane tile
_ROW_MIN = 16
#: rows whose statistics one step of the chunk loop updates in
#: registers (at 1024 x 1024 above: 8 rows 27.4 ms, 32 26.5, 64 25.6)
_ROW_CHUNK = 32

#: VMEM a launch may plan for (:func:`_vmem_bytes`); the kernel asks
#: the compiler for this much scoped VMEM and a margin, under the v5e's
#: 128 MiB
VMEM_BUDGET = 40 << 20
VMEM_LIMIT = VMEM_BUDGET + (8 << 20)


def _vmem_bytes(tm, tv, d, itemsize):
    """What one grid step holds: the h and W blocks and the float32
    logits block twice each (Mosaic double-buffers an operand), the
    label and three per-row output blocks twice each (a ``[tm, 1]``
    column takes whole lanes) and the four lane-wide statistics."""
    blocks = 2 * (tm * d * itemsize + tv * d * itemsize + tm * tv * 4
                  + 4 * tm * _LANES * 4)
    return blocks + 4 * tm * _LANES * 4


def head_tiles(n, v, d, itemsize=2):
    """``(tm, tv)`` for ``n`` rows over a vocabulary of ``v`` at width
    ``d``: the widest vocabulary tile (to ``_VOCAB_TILE``, no wider
    than the vocabulary's lane-rounded size) and the tallest row tile
    (to ``_ROW_TILE``, no taller than ``n`` rounded to the sublane
    tile) that fit ``VMEM_BUDGET``; over it the row tile halves to 256
    rows, then the vocabulary tile to two lane tiles, then the row tile
    to its least."""
    tv = _VOCAB_TILE
    while tv > _LANES and tv // 2 >= v:
        tv //= 2
    tm = _ROW_TILE
    while tm > _ROW_MIN and tm // 2 >= n:
        tm //= 2
    while _vmem_bytes(tm, tv, d, itemsize) > VMEM_BUDGET:
        if tm > 256:
            tm //= 2
        elif tv > 2 * _LANES:
            tv //= 2
        elif tm > _ROW_MIN:
            tm //= 2
        else:
            break
    return tm, tv


def _kernel(h_ref, w_ref, lab_ref, *refs, v, tv, transposed, keep_logits):
    """One grid step = one row tile x one vocabulary tile."""
    if keep_logits:
        z_ref, lse_ref, zl_ref, am_ref, m_ref, l_ref, y_ref, i_ref = refs
    else:
        lse_ref, zl_ref, am_ref, z_ref, m_ref, l_ref, y_ref, i_ref = refs
    tm = h_ref.shape[0]
    rc = min(_ROW_CHUNK, tm)
    j = pl.program_id(1)
    last = pl.num_programs(1) - 1

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        y_ref[...] = jnp.zeros_like(y_ref)
        i_ref[...] = jnp.zeros_like(i_ref)

    z_ref[...] = jax.lax.dot_general(
        h_ref[...], w_ref[...],
        (((1,), (0 if transposed else 1,)), ((), ())),
        preferred_element_type=jnp.float32)               # [tm, tv]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rc, _LANES), 1)

    def update(n_cols):
        """Fold the tile's first ``n_cols`` columns into the lane
        statistics, ``rc`` rows at a time."""
        slices = range(pl.cdiv(n_cols, _LANES))
        start = j * tv

        def logits(rows, k):
            s = z_ref[rows, pl.ds(k * _LANES, _LANES)]
            if (k + 1) * _LANES > n_cols:
                s = jnp.where(lane < n_cols - k * _LANES, s, NEG_INF)
            return s

        def chunk(r, carry):
            rows = pl.ds(pl.multiple_of(r * rc, rc), rc)
            m, am = m_ref[rows, :], i_ref[rows, :]
            m_new = m
            for k in slices:
                # columns of a lane come in increasing order: a strictly
                # larger value moves its first index, a tie does not
                s = logits(rows, k)
                am = jnp.where(s > m_new, lane + (start + k * _LANES), am)
                m_new = jnp.maximum(m_new, s)
            l = l_ref[rows, :] * jnp.exp(m - m_new)
            y = y_ref[rows, :]
            lab = jnp.broadcast_to(lab_ref[rows, :] - start, (rc, _LANES))
            for k in slices:
                s = logits(rows, k)
                l = l + jnp.exp(s - m_new)
                y = y + jnp.where(lane + k * _LANES == lab, s, 0.0)
            m_ref[rows, :] = m_new
            l_ref[rows, :] = l
            y_ref[rows, :] = y
            i_ref[rows, :] = am
            return carry

        jax.lax.fori_loop(0, tm // rc, chunk, 0)

    if v % tv:
        # the last tile runs past the vocabulary: what lies there is
        # not a logit (an edge block's padding), masked out of all three
        @pl.when(j < last)
        def _():
            update(tv)

        @pl.when(j == last)
        def _():
            update(v % tv)
    else:
        update(tv)

    @pl.when(j == last)
    def _():
        m = m_ref[...]
        top = jnp.max(m, axis=1, keepdims=True)
        lse_ref[...] = top + jnp.log(jnp.sum(
            l_ref[...] * jnp.exp(m - top), axis=1, keepdims=True))
        zl_ref[...] = jnp.sum(y_ref[...], axis=1, keepdims=True)
        am_ref[...] = jnp.min(jnp.where(m == top, i_ref[...], v), axis=1,
                              keepdims=True)


def head_xent(h, w, labels, transposed=False, keep_logits=True,
              interpret=None):
    """Per-row statistics of ``z = h @ Wᵀ``: ``h`` [N, d], ``w``
    [V, d] (``transposed``: [d, V]), both in the compute dtype,
    ``labels`` [N] int.  Returns ``(lse, z_label, argmax)``, each [N]
    (float32, float32, int32), and with ``keep_logits`` the float32
    logits [N, V] too, over the tiles :func:`head_tiles` sizes."""
    n, d = h.shape
    v = w.shape[1] if transposed else w.shape[0]
    tiles = tuple(head_tiles(n, v, d, h.dtype.itemsize))
    out = _head_fn(tiles, bool(transposed), bool(keep_logits),
                   autodetect_interpret(interpret))(
        h, w, labels.astype(jnp.int32).reshape(n, 1))
    lse, zl, am = (x[:, 0] for x in out[-3:])
    return (lse, zl, am) + ((out[0],) if keep_logits else ())


@functools.lru_cache(maxsize=None)
def _head_fn(tiles, transposed, keep_logits, interpret):
    """The launch for one resolved configuration, jitted."""
    tm, tv = tiles

    @jax.jit
    def launch(h, w, labels):
        n, d = h.shape
        v = w.shape[1] if transposed else w.shape[0]
        f32 = jnp.float32
        row = pl.BlockSpec((tm, 1), lambda i, j: (i, 0))
        w_spec = (pl.BlockSpec((d, tv), lambda i, j: (0, j)) if transposed
                  else pl.BlockSpec((tv, d), lambda i, j: (j, 0)))
        out_shape = [jax.ShapeDtypeStruct((n, 1), f32),
                     jax.ShapeDtypeStruct((n, 1), f32),
                     jax.ShapeDtypeStruct((n, 1), jnp.int32)]
        out_specs = [row, row, row]
        lanes = [pltpu.VMEM((tm, _LANES), f32)] * 3 \
            + [pltpu.VMEM((tm, _LANES), jnp.int32)]
        if keep_logits:
            out_shape.insert(0, jax.ShapeDtypeStruct((n, v), f32))
            out_specs.insert(0, pl.BlockSpec((tm, tv), lambda i, j: (i, j)))
            scratch = lanes
        else:
            scratch = [pltpu.VMEM((tm, tv), f32)] + lanes
        return pl.pallas_call(
            functools.partial(_kernel, v=v, tv=tv, transposed=transposed,
                              keep_logits=keep_logits),
            grid=(pl.cdiv(n, tm), pl.cdiv(v, tv)),
            in_specs=[pl.BlockSpec((tm, d), lambda i, j: (i, 0)), w_spec,
                      row],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
            name=KERNEL_NAMES["forward"][0],
        )(h, w, labels)

    return launch


# --------------------------------------------------------------------------
# VP6xx launch-audit hook (analysis.numerics_audit): the kernel's launch
# geometry as data — pure arithmetic, nothing traced.
# --------------------------------------------------------------------------

def audit_launch(n, v, d, dtype=jnp.bfloat16, checked=()):
    """Launch description of one forward over ``n`` rows, a vocabulary
    of ``v`` at width ``d``, at the tiles :func:`head_tiles` gives the
    shapes (the ragged last vocabulary tile is masked)."""
    import numpy as np
    tm, tv = head_tiles(n, v, d, np.dtype(dtype).itemsize)
    lane = {"full_lane": True}
    return [{
        "kernel": KERNEL_NAMES["forward"][1],
        "masked": True, "checked": checked,
        "vmem_kib": VMEM_LIMIT // 1024,
        "blocks": [("h", (tm, d), dtype, lane),
                   ("w", (tv, d), dtype, lane),
                   ("labels", (tm, _LANES), jnp.int32),
                   ("z", (tm, tv), jnp.float32),
                   ("lse", (tm, _LANES), jnp.float32),
                   ("z_label", (tm, _LANES), jnp.float32),
                   ("argmax", (tm, _LANES), jnp.int32)],
        "scratch": [("m", (tm, _LANES), jnp.float32),
                    ("l", (tm, _LANES), jnp.float32),
                    ("y", (tm, _LANES), jnp.float32),
                    ("first", (tm, _LANES), jnp.int32)],
        "grid_axes": [("row-tiles", n, tm), ("vocab-tiles", v, tv)],
    }]


@register_kernel_audit("head_xent")
def _configured_launches():
    """The forwards of the two training shapes: 16 x 1,024 rows at
    d 768 and 4 x 2,048 at d 2,048, over GPT-2's 50,257 tokens."""
    return (audit_launch(16 * 1024, 50257, 768)
            + audit_launch(4 * 2048, 50257, 2048))
