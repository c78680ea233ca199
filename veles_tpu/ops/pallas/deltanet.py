"""The gated-delta-rule decode kernel (``ops.deltanet``): per row and
block of value heads ONE pass over the float32 state S [dk, dv] a head —
decay it by the gate, read what it says for the key (``S^T k``), write
the β-scaled difference to the value back as a rank-1 correction, read
the output ``S^T q`` from the corrected state, and write S back INTO THE
SAME BUFFER (``input_output_aliases``; the tick donates the state).

Rows whose slot is inactive are SKIPPED, not computed and masked: the
active rows' ids come first in a scalar-prefetched list, the grid's
steps past their count keep the last active step's block indices (so
nothing is fetched or written back for them) and do nothing.

A head's token arrives as ONE [8, d] tile ``x`` (d = dk = dv): row 0 is
q, row 1 k, row 2 v, row 3 β and row 4 ``e^g`` (each broadcast over the
lanes), the rest zeros.  The two reads are MXU matmuls of that tile
against S (float32 at the highest precision); the correction ``k δ^T``
is k's column — the tile's transpose — times δ's row on the VPU."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.pallas import (autodetect_interpret,
                                  register_kernel_audit)

#: the kernel's name in the compiled module and the device trace
#: (``%veles_deltanet_decode = ... custom-call``) — what the benchmark's
#: ``deltanet_decode_roofline_pct`` sums; a contract recorded in PERF.md
KERNEL_NAMES = {"decode": ("veles_deltanet_decode", "deltanet.decode")}

#: value heads a grid step holds: 16 heads of [128, 128] float32 are 1
#: MiB of state, in and out double-buffered 4 MiB of the 16 MiB a v5e
#: kernel scopes — and 32 rows x 2 steps a launch, not 1,024 tiny ones
HEADS_PER_STEP = 16

_ROWS = 8


def heads_per_step(n_heads):
    """The widest divisor of ``n_heads`` within ``HEADS_PER_STEP``."""
    return max(d for d in range(1, min(n_heads, HEADS_PER_STEP) + 1)
               if n_heads % d == 0)


def _kernel(rows_ref, n_ref, s_ref, x_ref, out_ref, o_ref, *, heads):
    del rows_ref
    hi = jax.lax.Precision.HIGHEST

    @pl.when(pl.program_id(0) < n_ref[0])
    def _():
        def head(h, carry):
            x = x_ref[h]                                  # [8, d]
            s = s_ref[h] * x[4:5, :]                      # the decay
            read = jnp.dot(x, s, precision=hi,
                           preferred_element_type=jnp.float32)
            delta = (x[2:3, :] - read[1:2, :]) * x[3:4, :]
            s = s + x.T[:, 1:2] * delta                   # k delta^T
            out_ref[h] = s
            o_ref[h] = jnp.dot(x, s, precision=hi,
                               preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, heads, head, 0)

    # no row is active: the one block the grid holds goes back as it came
    @pl.when(n_ref[0] == 0)
    def _():
        out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode(s, q, k, v, beta, g, active, interpret):
    b, hv, dk, dv = s.shape
    if dk != dv:
        raise ValueError("the kernel takes dk == dv (%d, %d)" % (dk, dv))
    hb = heads_per_step(hv)
    nb = hv // hb
    f32 = jnp.float32
    lanes = (b, hv, dk)
    x = jnp.stack([q.astype(f32), k.astype(f32), v.astype(f32),
                   jnp.broadcast_to(beta.astype(f32)[..., None], lanes),
                   jnp.broadcast_to(jnp.exp(g.astype(f32))[..., None],
                                    lanes)]
                  + [jnp.zeros(lanes, f32)] * (_ROWS - 5), axis=2)
    # the active rows' ids first (a stable sort keeps their order)
    n = jnp.sum(active, dtype=jnp.int32)[None]
    rows = jnp.argsort(jnp.logical_not(active), stable=True).astype(
        jnp.int32)

    def index(bi, h, rows_ref, n_ref):
        live = bi < n_ref[0]
        row = rows_ref[jnp.clip(bi, 0, jnp.maximum(n_ref[0] - 1, 0))]
        return (row, jnp.where(live, h, nb - 1), 0, 0)

    state_spec = pl.BlockSpec((None, hb, dk, dv), index)
    tile_spec = pl.BlockSpec((None, hb, _ROWS, dk), index)
    out, o = pl.pallas_call(
        functools.partial(_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nb),
            in_specs=[state_spec, tile_spec],
            out_specs=[state_spec, tile_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct(s.shape, f32),
                   jax.ShapeDtypeStruct(x.shape, f32)],
        # the state's argument (after the two prefetched scalars) IS its
        # output: updated in place under the caller's donation
        input_output_aliases={2: 0},
        interpret=interpret,
        name=KERNEL_NAMES["decode"][0],
    )(rows, n, s, x)
    return jnp.where(active[:, None, None], o[:, :, 0], 0.0), out


def deltanet_decode(s, q, k, v, beta, g, active, interpret=None):
    """``s`` [B, Hv, d, d] float32, q / k / v [B, Hv, d] (a key head's
    q and k repeated over its value heads), ``beta`` / ``g`` [B, Hv],
    ``active`` [B] bool -> ``(o [B, Hv, d], S')``: ``ops.deltanet.
    deltanet_step`` for the active rows; an inactive row's state is
    untouched and its output nought."""
    return _decode(s, q, k, v, beta, g, active,
                   interpret=autodetect_interpret(interpret))


# --------------------------------------------------------------------------
# VP6xx launch-audit hook (analysis.numerics_audit): the kernel's launch
# geometry as data — pure arithmetic, nothing traced.
# --------------------------------------------------------------------------

def audit_launch(rows, heads, d):
    """Launch description of one decode step over ``rows`` rows of
    ``heads`` value heads of [d, d] float32 state."""
    hb = heads_per_step(heads)
    f32 = jnp.float32
    return [{
        "kernel": KERNEL_NAMES["decode"][1], "masked": True,
        "blocks": [("s", (hb, d, d), f32), ("x", (hb, _ROWS, d), f32),
                   ("s_out", (hb, d, d), f32), ("o", (hb, _ROWS, d), f32)],
        "grid_axes": [("rows", rows, 1), ("head-blocks", heads, hb)],
    }]


@register_kernel_audit("deltanet")
def _configured_launches():
    """The decode step of ``qwen3next.serve_long``: 32 rows of 32 value
    heads of [128, 128]."""
    return audit_launch(32, 32, 128)
