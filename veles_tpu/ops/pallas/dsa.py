"""A prefill pass's masked attention as a Pallas TPU kernel.

``ops.attention.dsa_attend`` scores a pass's queries against the index
keys, selects every query's ``topk`` keys (``dsa_select_blocks``: a
mask, block-major) and then attends the selected keys only.  In XLA the
last step goes through every live key block densely — Q.K^T, the mask,
the online softmax, P.V — as three fusions round a float32
``[Hkv, G x Tq, block]`` score tensor, written once and read twice:
1.24 ms a block of 1,024 keys at 32 query heads x 2,048 queries where
the MXU needs 0.18 (PERF.md, PR 30).  This kernel is the same loop with
the scores kept in VMEM — flash attention under an arbitrary mask.

One grid step is one KV head x one tile of queries x one tile of keys.
The G query heads that share the KV head ride the step together: their
``[G, tq_tile, hd]`` block stays in VMEM with its float32 ``acc``,
``m``, ``l`` while the key tiles stream past, and ONE int8 mask tile
``[tq_tile, kt]`` serves all G of them (the selection is a query's, not
a head's).  The arithmetic is the reference loop's, so a served token's
logits keep their margin: operands as they come, float32 accumulation,
float32 maximum / ``exp`` / sum, ``p`` cast to V's dtype for P.V, a
masked entry contributes exactly 0, ``o = acc / max(l, 1e-30)``.

Nothing is fetched or computed past the live key blocks: ``n_live``
(``ops.attention.dsa_live_blocks``, traced — one program a pass length)
and ``q_start`` ride in as SCALAR-PREFETCH arguments; the grid is static
over all key tiles, but a tile at or past the live ones — or one whose
keys all lie after the tile's queries, which the mask would zero anyway
— keeps the index of the last tile the step needed (same index on
consecutive steps = no copy) and skips its compute.

Layout contract (matches ``ops.attention._dsa_chunk``):
  q       [B, Hkv, G, Tq, hd]   queries at q_start .. q_start + Tq - 1
  k, v    [B, Hkv, Tk, hd]      a cache row (or the sequence itself)
  mask    [Tk // kb, B, Tq, kb] int8, nonzero = selected; blocks at or
                                past ``n_live`` are never read
  n_live  int32                 live key blocks of ``kb`` keys
  -> out  [B, Hkv, G, Tq, hd]

Ground truth: ``ops.attention.dsa_attend_blocks`` (the XLA loop) — the
tests pin kernel == reference; off-TPU the kernel runs in interpret
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

#: (``pallas_call`` name, VP6xx audit display name).  The first is the
#: HLO instruction's name in a device trace (``%veles_dsa_prefill.1 =
#: ... custom-call``): a contract recorded in PERF.md that any later
#: implementation of this layer keeps.
KERNEL_NAMES = {"prefill": ("veles_dsa_prefill", "dsa.prefill")}

#: the mask's sublane tile (int8: 32 rows): the least query tile
_TQ_MIN = 32

#: the largest tiles: queries a step (each with its G heads) and keys a
#: step.  Measured on the v5e at [1, 32 q / 4 kv heads, 2048, 128] over
#: 18 live blocks of 1,024 keys (PERF.md, PR 32), ms a block: 256 x 1024
#: 0.276, 128 x 1024 0.352, 128 x 512 0.428, 512 x 512 0.438, 256 x 512
#: 0.458 (the XLA loop 1.178); 512 x 1024 asks 28 MiB of VMEM.
_TQ_TILE = 256
_KEY_TILE = 1024

#: VMEM a launch may plan for: the double-buffered q, o, K, V and mask
#: blocks, the float32 accumulators and the score tile's temporaries
#: (:func:`_vmem_bytes`), inside the 16 MiB a v5e kernel may scope by
#: default.
_VMEM_BUDGET = 12 << 20


def _vmem_bytes(g, tq_tile, kt, hd, itemsize):
    """What one grid step holds in VMEM: q and o blocks and the K, V and
    mask tiles twice each (Mosaic double-buffers an operand), ``acc``,
    the lane-wide ``m`` and ``l``, and four float32 ``[tq_tile, kt]``
    temporaries of the one head being computed (scores, ``p``, the mask
    as the select reads it, ``p`` cast).  The compiler's own count at
    256 x 1024, G 8, bf16 is within the 16 MiB, at 512 x 1024 28 MiB."""
    blocks = 2 * (2 * g * tq_tile * hd * itemsize     # q, o
                  + 2 * kt * hd * itemsize            # k, v
                  + tq_tile * kt)                     # mask
    scratch = g * tq_tile * (hd + 2 * _LANES) * 4
    return blocks + scratch + 4 * tq_tile * kt * 4


def prefill_tiles(tq, tk, hd, kb, g=8, itemsize=2):
    """``(tq_tile, kt)`` the kernel runs a ``[G, tq, hd]`` pass over
    ``tk`` keys in blocks of ``kb`` at, or None where the shapes do not
    tile: the head dim fills the lanes, ``kb`` divides ``tk`` (the
    reference loop alone handles an overlapping last block) and is
    lane-wide, and the queries fill the mask's sublane tile.  The tiles
    are the largest powers of two under ``_TQ_TILE`` / ``_KEY_TILE`` that
    divide ``tq`` / ``kb`` and fit ``_VMEM_BUDGET``.  Over the budget
    (a group of 16 heads: its q, o and accumulators are twice a group
    of 8's) the q tile halves once before the key tile does — on the
    v5e at [1, 128 q / 8 kv heads, 2048, 128] over 18 live blocks, ms a
    block: 128 x 1024 1.29, 128 x 512 1.55, 256 x 512 1.74, 64 x 1024
    1.78, 256 x 256 1.87 (PR 33's probe; PERF.md section 6) — then the
    key tile (a smaller q tile re-reads K and V), and 32 x 128 is the
    floor whatever the group's size (the launch audit prices it)."""
    if hd % _LANES or tk % kb or kb % _LANES or tq % _TQ_MIN:
        return None
    tq_tile, kt = _TQ_TILE, _KEY_TILE
    while tq % tq_tile:
        tq_tile //= 2
    while kb % kt:
        kt //= 2
    if _vmem_bytes(g, tq_tile, kt, hd, itemsize) > _VMEM_BUDGET \
            and tq_tile > _TQ_TILE // 2:
        tq_tile //= 2
    while _vmem_bytes(g, tq_tile, kt, hd, itemsize) > _VMEM_BUDGET:
        if kt > _LANES:
            kt //= 2
        elif tq_tile > _TQ_MIN:
            tq_tile //= 2
        else:
            break
    return tq_tile, kt


def _last_tile(qi, n_tiles, q_start, tq_tile, kt):
    """The last key tile the q tile ``qi`` needs: the last live one, or
    the one that holds the tile's last query if that comes first."""
    last_q = q_start + (qi + 1) * tq_tile - 1
    return jnp.minimum(n_tiles - 1, last_q // kt)


def _prefill_kernel(live_ref, start_ref, q_ref, k_ref, v_ref, mask_ref,
                    o_ref, acc, m, l, *, scale, tq_tile, kt):
    """One grid step = one KV head x one q tile x one key tile: the
    online-softmax update of the G heads' rows over the tile's keys,
    under the tile's mask."""
    qi, kj = pl.program_id(2), pl.program_id(3)
    g = q_ref.shape[2]

    @pl.when(kj == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, NEG_INF)
        l[...] = jnp.zeros_like(l)

    @pl.when(kj <= _last_tile(qi, live_ref[0], start_ref[0], tq_tile, kt))
    def _():
        keep = mask_ref[0, 0].astype(jnp.int32) != 0
        k, v = k_ref[0, 0], v_ref[0, 0]

        # the heads are a loop traced once and unrolled at lowering:
        # straight-line code Mosaic can interleave (ops/pallas/paged.py)
        def head(h, carry):
            s = jax.lax.dot_general(
                q_ref[0, 0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m[h][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # a masked entry is exp(NEG_INF - a finite maximum), exactly
            # 0 with no second select; a row that has selected nothing
            # yet (a query selects itself, so only before its own tile)
            # has no finite maximum, and 0 stands in for it
            p = jnp.exp(s - jnp.where(m_new <= NEG_INF / 2, 0.0, m_new))
            corr = jnp.exp(m_prev - m_new)
            l[h] = l[h] * corr + jnp.broadcast_to(
                jnp.sum(p, axis=-1, keepdims=True), l.shape[1:])
            m[h] = jnp.broadcast_to(m_new, m.shape[1:])
            acc[h] = acc[h] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, g, head, 0, unroll=True)

    @pl.when(kj == pl.num_programs(3) - 1)
    def _():
        o_ref[0, 0] = (acc[...] / jnp.maximum(l[...][:, :, :1], 1e-30)
                       ).astype(o_ref.dtype)


def dsa_prefill_attention(q, k, v, mask, q_start, n_live, scale,
                          tiles=None, interpret=None):
    """Softmax attention of every query over the keys its mask selects
    (see the module docstring for the layout contract).  ``q_start`` and
    ``n_live`` may be traced.  ``tiles`` — ``(tq_tile, kt)``; unset,
    :func:`prefill_tiles` sizes them from the shapes (the caller has
    checked that they tile)."""
    g, tq, hd = q.shape[2:]
    tk, kb = k.shape[2], mask.shape[3]
    tiles = tiles or prefill_tiles(tq, tk, hd, kb, g, q.dtype.itemsize)
    if tiles is None:
        raise ValueError("shapes do not tile: q %s over %d keys in "
                         "blocks of %d" % (q.shape, tk, kb))
    scalars = [jnp.asarray(x, jnp.int32).reshape(1)
               for x in (n_live, q_start)]
    return _prefill_fn(float(scale), tuple(tiles),
                       autodetect_interpret(interpret))(*scalars, q, k, v,
                                                        mask)


@functools.lru_cache(maxsize=None)
def _prefill_fn(scale, tiles, interpret):
    """The launch for one resolved configuration, jitted: a model's
    layers all call the same one, so a process that traces a pass traces
    and lowers the kernel once, not once a layer."""
    tq_tile, kt = tiles

    @jax.jit
    def prefill(n_live, q_start, q, k, v, mask):
        b, hkv, g, tq, hd = q.shape
        tk, kb = k.shape[2], mask.shape[3]
        per = kb // kt                        # key tiles a mask block

        # ``live``: the live key TILES, as the kernel reads them (a
        # row with none computes nothing and still fetches in bounds)
        def tile(qi, kj, live, start):
            return jnp.minimum(kj, jnp.maximum(
                _last_tile(qi, live[0], start[0], tq_tile, kt), 0))

        def at_q(bi, hi, qi, kj, live, start):
            return (bi, hi, 0, qi, 0)

        def at_kv(bi, hi, qi, kj, live, start):
            return (bi, hi, tile(qi, kj, live, start), 0)

        def at_mask(bi, hi, qi, kj, live, start):
            j = tile(qi, kj, live, start)
            return (j // per, bi, qi, j % per)

        return pl.pallas_call(
            functools.partial(_prefill_kernel, scale=scale,
                              tq_tile=tq_tile, kt=kt),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(b, hkv, tq // tq_tile, tk // kt),
                in_specs=[
                    pl.BlockSpec((1, 1, g, tq_tile, hd), at_q),
                    pl.BlockSpec((1, 1, kt, hd), at_kv),
                    pl.BlockSpec((1, 1, kt, hd), at_kv),
                    pl.BlockSpec((1, 1, tq_tile, kt), at_mask),
                ],
                out_specs=pl.BlockSpec((1, 1, g, tq_tile, hd), at_q),
                scratch_shapes=[
                    pltpu.VMEM((g, tq_tile, hd), jnp.float32),
                    pltpu.VMEM((g, tq_tile, _LANES), jnp.float32),
                    pltpu.VMEM((g, tq_tile, _LANES), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
            name=KERNEL_NAMES["prefill"][0],
        )(n_live * per, q_start, q, k, v, mask)

    return prefill


# --------------------------------------------------------------------------
# VP6xx launch-audit hook (analysis.numerics_audit): the kernel's launch
# geometry as data — pure arithmetic, nothing traced.
# --------------------------------------------------------------------------

def audit_launch(tq, tk, hd, g=8, kb=1024, dtype=jnp.bfloat16,
                 tiles=None, masked=True, checked=()):
    """Launch description for one pass: ``tq`` queries of ``g`` heads a
    KV head over ``tk`` keys in mask blocks of ``kb``, at ``tiles`` or
    what :func:`prefill_tiles` gives the shapes."""
    import numpy as np
    tq_tile, kt = tiles or prefill_tiles(tq, tk, hd, kb, g,
                                         np.dtype(dtype).itemsize)
    lane = {"full_lane": True}
    return [{
        "kernel": KERNEL_NAMES["prefill"][1],
        "masked": masked, "checked": checked,
        "blocks": [("q", (1, 1, g, tq_tile, hd), dtype, lane),
                   ("k", (1, 1, kt, hd), dtype, lane),
                   ("v", (1, 1, kt, hd), dtype, lane),
                   ("mask", (1, 1, tq_tile, kt), jnp.int8),
                   ("o", (1, 1, g, tq_tile, hd), dtype, lane)],
        # the last: the score tile's float32 temporaries, Mosaic's own
        # (:func:`_vmem_bytes`) — priced with the scratch
        "scratch": [("acc", (g, tq_tile, hd), jnp.float32),
                    ("m", (g, tq_tile, _LANES), jnp.float32),
                    ("l", (g, tq_tile, _LANES), jnp.float32),
                    ("scores", (4, tq_tile, kt), jnp.float32)],
        "grid_axes": [("q-tiles", tq, tq_tile), ("k-tiles", tk, kt)],
    }]


@register_kernel_audit("dsa")
def _configured_launches():
    """What a 2,048-token staged pass of the long-context serving shape
    launches (32 query / 4 KV heads of 128 over a row of 34,816 keys,
    bf16), at the tiles :func:`prefill_tiles` gives it."""
    return audit_launch(2048, 34816, 128)
