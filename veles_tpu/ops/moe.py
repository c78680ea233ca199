"""Mixture-of-experts feed-forward with expert parallelism.

New capability beyond the reference (SURVEY.md §2.6: EP absent there),
mandated first-class for the TPU build.  The design is the canonical
TPU MoE (GShard / Switch): routing produces a *dense* dispatch tensor
[tokens, experts, capacity] so every shape is static and the dispatch/
combine contractions run on the MXU — no sorting, no dynamic shapes.

Two execution paths share the math:

* single-device: the dispatch einsum materializes [E, C, D] expert
  batches locally.
* expert-parallel (inside ``shard_map`` over an ``expert`` axis):
  tokens are sharded over the axis; after local dispatch,
  ``lax.all_to_all`` swaps the expert dim for the shard dim so each
  device runs only its local experts, then the inverse all-to-all
  brings expert outputs home for the combine.  The two all-to-alls ride
  ICI — the standard GShard dance.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax


def moe_init(rng, d_model, d_ff, n_experts, dtype=jnp.float32):
    """Router + per-expert MLP params (experts stacked on axis 0)."""
    std = 1.0 / math.sqrt(d_model)
    return {
        "router": jnp.asarray(rng.normal(0.0, std, (d_model, n_experts)),
                              dtype),
        "w1": jnp.asarray(rng.normal(0.0, std, (n_experts, d_model, d_ff)),
                          dtype),
        "b1": jnp.zeros((n_experts, d_ff), dtype),
        "w2": jnp.asarray(
            rng.normal(0.0, 1.0 / math.sqrt(d_ff), (n_experts, d_ff,
                                                    d_model)), dtype),
        "b2": jnp.zeros((n_experts, d_model), dtype),
    }


def _routing(x2d, router, n_experts, capacity, top_k):
    """Dense dispatch/combine tensors (GShard §3.2, Switch §2.2).

    Returns (dispatch [N, E, C] one-hot, combine [N, E, C] weighted) plus
    the load-balancing auxiliary loss (Switch eq. 4)."""
    logits = x2d.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)          # [N, E]

    gates = jnp.zeros_like(probs)
    masked = probs
    for _ in range(top_k):
        idx = jnp.argmax(masked, axis=-1)
        onehot = jax.nn.one_hot(idx, n_experts, dtype=probs.dtype)
        gates = gates + onehot * probs
        masked = masked * (1.0 - onehot)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # position of each token within its expert's capacity buffer
    chosen = gates > 0.0                             # [N, E]
    position = (jnp.cumsum(chosen, axis=0) - 1.0) * chosen
    fits = chosen & (position < capacity)
    pos_onehot = jax.nn.one_hot(position.astype(jnp.int32), capacity,
                                dtype=probs.dtype)   # [N, E, C]
    dispatch = pos_onehot * fits[..., None]
    combine = dispatch * gates[..., None]

    # Switch load-balancing aux loss: E * Σ_e fraction_tokens_e · mean_prob_e
    frac = chosen.astype(jnp.float32).mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = n_experts * jnp.sum(frac * mean_prob) / top_k
    return dispatch, combine, aux


def _expert_mlp(w1, b1, w2, b2, h):
    """h: [E(, ...), C, D] with matching leading expert dims on w/b."""
    h = jnp.einsum("...cd,...df->...cf", h, w1) + b1[..., None, :]
    h = jax.nn.gelu(h)
    return jnp.einsum("...cf,...fd->...cd", h, w2) + b2[..., None, :]


def moe_forward(params, x, top_k=2, capacity_factor=2.0, axis_name=None,
                policy=None):
    """x: [B, T, D] → ([B, T, D], aux_loss).

    ``axis_name``: inside shard_map, run expert-parallel over that mesh
    axis (n_experts must be divisible by the axis size; tokens arrive
    sharded over the same axis via the batch dim)."""
    b, t, d = x.shape
    n_experts = params["router"].shape[-1]
    x2d = x.reshape(b * t, d)
    n = b * t
    try:
        capacity = max(1, int(capacity_factor * n * top_k / n_experts))
    except (TypeError, jax.errors.ConcretizationTypeError):
        # jax.export symbolic batch: ``n`` is a dimension expression —
        # float math on it concretizes.  Keep the capacity a dim expr
        # via integer arithmetic (capacity_factor rationalized /1000)
        # so one artifact still serves any batch size.
        import jax.core as jcore
        num = int(round(capacity_factor * 1000))
        capacity = jcore.max_dim(
            (n * top_k * num) // (1000 * n_experts), 1)
    cast = (lambda a: a) if policy is None else policy.cast_in

    dispatch, combine, aux = _routing(x2d, params["router"], n_experts,
                                      capacity, top_k)
    # [N, E, C] x [N, D] -> [E, C, D] expert input batches
    expert_in = jnp.einsum("nec,nd->ecd", cast(dispatch), cast(x2d),
                           preferred_element_type=jnp.float32)

    if axis_name is None:
        expert_out = _expert_mlp(params["w1"], params["b1"], params["w2"],
                                 params["b2"], expert_in)
    else:
        shards = lax.psum(1, axis_name)
        e_local = n_experts // shards
        w1, b1, w2, b2 = (params["w1"], params["b1"], params["w2"],
                          params["b2"])
        if w1.shape[0] == n_experts:   # replicated params: take my slice
            me = lax.axis_index(axis_name)
            w1 = lax.dynamic_slice_in_dim(w1, me * e_local, e_local)
            b1 = lax.dynamic_slice_in_dim(b1, me * e_local, e_local)
            w2 = lax.dynamic_slice_in_dim(w2, me * e_local, e_local)
            b2 = lax.dynamic_slice_in_dim(b2, me * e_local, e_local)
        # device-transpose: [S_owner, e_local, C, D] of MY tokens becomes
        # [S_source, e_local, C, D] of MY experts (axis0 slice i goes to
        # device i; received slices stack back on axis0 keyed by sender)
        grouped = expert_in.reshape(shards, e_local, capacity, d)
        recv = lax.all_to_all(grouped, axis_name, 0, 0)
        h = recv.transpose(1, 0, 2, 3).reshape(e_local, shards * capacity,
                                               d)
        out = _expert_mlp(w1, b1, w2, b2, h)
        out = out.reshape(e_local, shards, capacity, d).transpose(1, 0, 2, 3)
        expert_out = lax.all_to_all(out, axis_name, 0, 0).reshape(
            n_experts, capacity, d)

    y = jnp.einsum("ecd,nec->nd", expert_out.astype(jnp.float32),
                   combine.astype(jnp.float32))
    return y.reshape(b, t, d).astype(x.dtype), aux


def moe_forward_sharded(params, x, mesh, expert_axis="expert", top_k=2,
                        capacity_factor=2.0, policy=None):
    """Global [B, T, D] arrays → expert-parallel MoE over ``expert_axis``.

    Expert weights shard over the axis (each device computes only its
    experts), the batch shards over the same axis (tokens all_to_all to
    their experts and back), router/aux replicate.  Composes with
    jit/grad like every shard_map here."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    axis_size = mesh.shape[expert_axis]
    n_experts = params["w1"].shape[0]
    if x.shape[0] % axis_size:
        raise ValueError("batch %d not divisible by %s axis size %d"
                         % (x.shape[0], expert_axis, axis_size))
    if n_experts % axis_size:
        raise ValueError("n_experts %d not divisible by %s axis size %d"
                         % (n_experts, expert_axis, axis_size))
    e = P(expert_axis)
    param_specs = {"router": P(), "w1": e, "b1": e, "w2": e, "b2": e}
    xspec = P(expert_axis)          # batch dim sharded over the axis

    def fn(p, xs):
        y, aux = moe_forward(p, xs, top_k=top_k,
                             capacity_factor=capacity_factor,
                             axis_name=expert_axis, policy=policy)
        return y, lax.pmean(aux, expert_axis)

    return shard_map(fn, mesh=mesh, in_specs=(param_specs, xspec),
                     out_specs=(xspec, P()), check_vma=False)(params, x)
