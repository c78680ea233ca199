"""The power-retention decode kernel (``ops.retention``): per row and KV
head ONE pass over the float32 state ``s`` [hd, Dp] in tiles of lanes —
scale by the gate, add ``v phi(k)^T``, accumulate ``S phi(q_i)`` for
the group's query heads, write the tile back INTO THE SAME BUFFER
(``input_output_aliases``; the tick donates the state).  XLA's ``g * S
+ outer`` then ``phi(q)^T S`` reads the state twice and may copy it;
at sixteen slots of eight layers the state is 4.4 GB beside 8.4 GB of
weights, so in-place is a matter of fitting, not only of speed.

Rows whose slot is inactive are SKIPPED, not computed and masked: the
active rows' ids come first in a scalar-prefetched list, the grid's
steps past their count keep the last active step's block indices (so
nothing is fetched or written back for them) and do nothing.

The features arrive as ONE operand ``f`` [B, Hkv, Gp, Dp]: rows [0, G)
are ``phi(q_i / sqrt hd)`` of the group's query heads, row G is
``phi(k)``, the rest (to the sublane minimum) zeros; ``v`` and the gate
as columns ``vg`` [B, Hkv, hd, 2].  The state's tile is [hd, TILE]:
features along the lanes, so the update is two broadcasts on the VPU
and the read one ``q k^T``-shaped matmul on the MXU."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.pallas import autodetect_interpret

#: the kernel's name in the compiled module and the device trace
#: (``%veles_retention_decode = ... custom-call``) — what the benchmark's
#: ``retention_decode_roofline_pct`` sums; a contract recorded in PERF.md
KERNEL_NAMES = {"decode": ("veles_retention_decode", "retention.decode")}

_LANES = 128
_SUBLANES = 8

#: lanes a tile of the state holds at most: [128, 1664] float32 is 852
#: KB, in and out double-buffered 3.4 MB of the 16 MB a v5e kernel scopes
MAX_TILE_LANES = 16 * _LANES


def tile_lanes(dp):
    """The widest whole-lane divisor of ``dp`` within ``MAX_TILE_LANES``
    (8,320 = 65 x 128 -> 13 x 128 = 1,664)."""
    n = dp // _LANES
    best = max(d for d in range(1, n + 1)
               if n % d == 0 and d * _LANES <= MAX_TILE_LANES)
    return best * _LANES


def _kernel(rows_ref, n_ref, s_ref, f_ref, vg_ref, out_ref, y_ref, *,
            key_row):
    del rows_ref
    live = pl.program_id(0) < n_ref[0]
    first = pl.program_id(2) == 0

    @pl.when(live)
    def _():
        f = f_ref[...]                                   # [Gp, TILE]
        vg = vg_ref[...]                                 # [hd, 2]
        s = s_ref[...] * vg[:, 1:2] \
            + vg[:, 0:1] * f[key_row:key_row + 1, :]
        out_ref[...] = s
        part = jax.lax.dot_general(
            f, s, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)         # [Gp, hd]

        @pl.when(first)
        def _():
            y_ref[...] = part

        @pl.when(jnp.logical_not(first))
        def _():
            y_ref[...] += part

    # no row is active: the one block the grid holds goes back as it came
    @pl.when(n_ref[0] == 0)
    def _():
        out_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def feature_rows(g):
    """Rows of the features' operand for a group of ``g`` query heads:
    the queries, the key, zeros to the sublane minimum."""
    return -(-(g + 1) // _SUBLANES) * _SUBLANES


@functools.partial(jax.jit, static_argnames=("g", "interpret"))
def _decode(s, f, g, v, decay, active, interpret):
    b, hkv, hd, dp = s.shape
    gp = f.shape[2]
    tile = tile_lanes(dp)
    nt = dp // tile
    f32 = jnp.float32
    f = f.astype(f32)
    vg = jnp.stack([v.astype(f32), jnp.broadcast_to(
        decay.astype(f32)[..., None], v.shape)], axis=-1)
    # the active rows' ids first (a stable sort keeps their order)
    n = jnp.sum(active, dtype=jnp.int32)[None]
    rows = jnp.argsort(jnp.logical_not(active), stable=True).astype(
        jnp.int32)

    def at(tiled):
        def index(bi, h, t, rows_ref, n_ref):
            live = bi < n_ref[0]
            row = rows_ref[jnp.clip(bi, 0, jnp.maximum(n_ref[0] - 1, 0))]
            return (row, jnp.where(live, h, hkv - 1), 0,
                    jnp.where(live, t, nt - 1) if tiled else 0)
        return index

    state_spec = pl.BlockSpec((None, None, hd, tile), at(True))
    out, y = pl.pallas_call(
        functools.partial(_kernel, key_row=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv, nt),
            in_specs=[state_spec,
                      pl.BlockSpec((None, None, gp, tile), at(True)),
                      pl.BlockSpec((None, None, hd, 2), at(False))],
            out_specs=[state_spec,
                       pl.BlockSpec((None, None, gp, hd), at(False))],
        ),
        out_shape=[jax.ShapeDtypeStruct(s.shape, f32),
                   jax.ShapeDtypeStruct((b, hkv, gp, hd), f32)],
        # the state's argument (after the two prefetched scalars) IS
        # its output: updated in place under the caller's donation
        input_output_aliases={2: 0},
        interpret=interpret,
        name=KERNEL_NAMES["decode"][0],
    )(rows, n, s, f, vg)
    return y[:, :, :g], out


def retention_decode(s, f, g, v, decay, active, interpret=None):
    """``s`` [B, Hkv, hd, Dp] float32, ``f`` [B, Hkv, feature_rows(g),
    Dp] (``ops.retention.phi`` of the token's ``g`` queries a KV head,
    then of its key, then zeros), ``v`` [B, Hkv, hd], ``decay``
    [B, Hkv] (the gate), ``active`` [B] bool -> ``(S' phi(q)
    [B, Hkv, g, hd], S')`` with ``S' = decay S + v phi(k)^T`` for the
    active rows; an inactive row's state is untouched and its product
    unspecified."""
    return _decode(s, f, g, v, decay, active,
                   interpret=autodetect_interpret(interpret))
