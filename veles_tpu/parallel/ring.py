"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

New capability beyond the reference (SURVEY.md §5: long-context and
sequence parallelism are absent there — it predates them); mandated
first-class for the TPU build.  Two strategies over a named mesh axis
(``seq``), both SPMD via ``shard_map``:

- **Ring attention** (Liu et al.): every device holds a sequence shard of
  q, k, v.  The k/v shard rotates around the ring with ``lax.ppermute``
  (XLA lowers this to ICI neighbor exchange) while each device folds the
  visiting shard into its online-softmax accumulator
  (ops.attention.blockwise_attention carry) — full attention with O(T/n)
  activations per chip and communication overlapped with compute by XLA's
  latency-hiding scheduler.  Exact, not approximate: the online-softmax
  merge is associative.

- **Ulysses** (all-to-all): resharding [B, H, T/n, D] → [B, H/n, T, D]
  with ``lax.all_to_all``, full attention on the head shard, then the
  inverse all-to-all.  Cheaper collectives for moderate T when
  n_heads % n_devices == 0.

Both take already-sharded per-device arrays inside ``shard_map``; the
``*_sharded`` wrappers build the shard_map over a Mesh for callers holding
global arrays."""

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from veles_tpu.ops import attention as att


def ring_attention(q, k, v, axis_name, causal=False, scale=None,
                   block_k=512):
    """Inside shard_map: q, k, v are the local [B, H, T/n, D] shards,
    sequence-sharded over ``axis_name``.  Returns the local output shard.
    """
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    t_local = q.shape[-2]
    q_offset = me * t_local
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(i, state):
        acc, m, l, kk, vv = state
        # after i rotations we hold the shard originally on device me - i
        src = (me - i) % n

        def fold(carry):
            return att.blockwise_attention(
                q, kk, vv, causal=causal, scale=scale, block_k=block_k,
                q_offset=q_offset, k_offset=src * t_local,
                carry=carry, return_carry=True)

        if causal:
            # a visiting shard entirely in the future (src > me) is fully
            # masked — skip its einsums, pass the carry through (saves
            # ~half the attention FLOPs per step on average)
            acc, m, l = lax.cond(src > me, lambda c: c, fold, (acc, m, l))
        else:
            acc, m, l = fold((acc, m, l))
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        return acc, m, l, kk, vv

    b, h, _, d = q.shape
    acc = jnp.zeros((b, h, t_local, d), jnp.float32)
    m = jnp.full((b, h, t_local), att.NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, t_local), jnp.float32)
    acc, m, l, _, _ = lax.fori_loop(
        0, n, step, (acc, m, l, k, v), unroll=True)
    return att.finalize_attention((acc, m, l)).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name, causal=False, scale=None):
    """Inside shard_map: all-to-all seq-sharded → head-sharded, full
    attention, inverse.  Requires n_heads % axis_size == 0."""
    n = lax.psum(1, axis_name)
    if q.shape[1] % n:
        raise ValueError("ulysses needs n_heads (%d) %% axis size (%d) == 0"
                         % (q.shape[1], n))
    def a2a_fwd(x):   # [B, H, T/n, D] -> [B, H/n, T, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)
    def a2a_bwd(x):   # [B, H/n, T, D] -> [B, H, T/n, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)
    o = att.blockwise_attention(a2a_fwd(q), a2a_fwd(k), a2a_fwd(v),
                                causal=causal, scale=scale)
    return a2a_bwd(o)


def _sharded(fn, mesh, seq_axis, **kw):
    spec = P(None, None, seq_axis, None)
    wrapped = functools.partial(fn, axis_name=seq_axis, **kw)
    return shard_map(wrapped, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)


def ring_attention_sharded(q, k, v, mesh, seq_axis="seq", causal=False,
                           scale=None, block_k=512):
    """Global [B, H, T, D] arrays; shard over ``seq_axis`` and run the
    ring.  jit-compatible (shard_map composes with jit/grad)."""
    return _sharded(ring_attention, mesh, seq_axis, causal=causal,
                    scale=scale, block_k=block_k)(q, k, v)


def ulysses_attention_sharded(q, k, v, mesh, seq_axis="seq", causal=False,
                              scale=None):
    return _sharded(ulysses_attention, mesh, seq_axis, causal=causal,
                    scale=scale)(q, k, v)
