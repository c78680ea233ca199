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

A third path is DROPLESS (``moe_dropless_forward``): token–expert
pairs are sorted by expert and the experts run as a grouped matmul over
row tiles, each tile one expert's, so no token is ever dropped and no
[N, E, C] tensor exists — what a 128-expert top-8 layer over a 32k
prefill needs.  Its experts are gated SiLU MLPs without biases
(``w_gate``, ``w_up``, ``w_down``); the layer is told which experts it
holds and computes their part of the result.
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


# ---------------------------------------------------------------------------
# dropless routing: sort by expert, grouped matmul over row tiles

#: largest row tile of the grouped matmul: one tile reads one expert's
#: three matrices once, so the tile is what amortizes that read
DROPLESS_TILE_MAX = 256


def moe_dropless_init(rng, d_model, d_expert, n_experts, dtype=jnp.float32,
                      n_held=None):
    """Router over ``n_experts`` + the gated-SiLU matrices of the
    ``n_held`` experts this layer holds (default all), stacked on axis
    0.  No biases."""
    n_held = n_experts if n_held is None else n_held
    std = 1.0 / math.sqrt(d_model)

    def w(shape, s):
        return jnp.asarray(rng.normal(0.0, s, shape), dtype)

    return {
        "router": w((d_model, n_experts), std),
        "w_gate": w((n_held, d_model, d_expert), std),
        "w_up": w((n_held, d_model, d_expert), std),
        "w_down": w((n_held, d_expert, d_model),
                    1.0 / math.sqrt(d_expert)),
    }


def _router_logits(x2d, router):
    return jnp.matmul(x2d.astype(jnp.float32), router.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST)


def route_topk(x2d, router, top_k):
    """softmax over ALL experts in float32, the ``top_k`` largest
    renormalised to sum 1 (ties towards the lower expert id).  Returns
    ``(gates [N, k] f32, experts [N, k] int32)``."""
    probs = jax.nn.softmax(_router_logits(x2d, router), axis=-1)
    gates, experts = lax.top_k(probs, top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, experts.astype(jnp.int32)


def route_sigmoid_topk(x2d, router, top_k):
    """``route_topk`` with a sigmoid of each expert's own logit where
    that one takes a softmax over all: the ``top_k`` largest scores in
    float32, divided by their sum (ties towards the lower expert id)."""
    scores = jax.nn.sigmoid(_router_logits(x2d, router))
    gates, experts = lax.top_k(scores, top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, experts.astype(jnp.int32)


#: ``router`` of a dropless layer -> the name of its routing function in
#: this module (looked up at call time)
DROPLESS_ROUTERS = {"softmax_topk_renorm": "route_topk",
                    "sigmoid_topk_renorm": "route_sigmoid_topk"}

#: pairs the buffers of a layer that holds a SHARE of the experts are
#: sized for, as a multiple of the share's mean (``n_pairs x n_held /
#: n_experts``); a call whose pairs outnumber it takes the buffers sized
#: for every pair, so no pair is ever dropped
DROPLESS_SHARE_SLACK = 2


def dropless_tile(n_pairs, n_held):
    """Rows a tile of the grouped matmul holds: about one expert's mean
    share of the pairs, a power of two from 8 to ``DROPLESS_TILE_MAX``
    — follows from the shapes alone (a decode tick's 64 pairs get 8, a
    2,048-token prefill's 16,384 get 128)."""
    mean = max(1, n_pairs // max(1, n_held))
    return int(min(DROPLESS_TILE_MAX, max(8, 1 << (mean - 1).bit_length())))


def dropless_pair_bound(n_pairs, n_held, n_experts):
    """Pairs the sorted buffers of a call are sized for first: all of
    them where every expert is held, else ``DROPLESS_SHARE_SLACK`` times
    the share's mean (the pairs that can land here are ``n_pairs x
    n_held / n_experts`` on average) and a tile an expert."""
    if n_held >= n_experts:
        return n_pairs
    mean = -(-n_pairs * n_held // n_experts)
    return min(n_pairs, DROPLESS_SHARE_SLACK * mean + 8 * n_held)


def moe_dropless_forward(params, x, top_k=8, first=0, policy=None,
                         router="softmax_topk_renorm", counts=None):
    """x: [B, T, D] → ([B, T, D], experts_touched).

    Routing (``router``: a key of ``DROPLESS_ROUTERS``) is over the
    router's whole width; the experts held here
    are ``first .. first + n_held - 1`` (``n_held`` = the expert
    leaves' leading dim) and only their part of the result is computed:
    what an absent expert would have added is left out (the chip's
    share of an expert-parallel deployment; all shares add up to the
    whole layer).  Every pair routed to a held expert is computed —
    there is no capacity.  ``experts_touched``: held experts that got
    at least one pair (int32 scalar).  ``counts``: a dict that takes
    ``expert_pairs`` — the pairs that landed on held experts (int32
    scalar) — where the layer holds a share (with all held it is
    ``N x top_k``, and nothing is counted).

    Pairs are sorted by expert (stable), each expert's group padded to
    whole tiles of ``dropless_tile`` rows, and a ``lax.scan`` walks the
    tiles: one tile = one ``dynamic_slice`` of the expert's three
    matrices and three small matmuls, empty tiles skipped by
    ``lax.cond`` — so a step reads an expert only if a row chose it.
    A layer that holds a share sizes tile and buffers for the pairs
    that can land here (``dropless_pair_bound``), and a call in which
    more did — every token may choose held experts — runs the same
    walk at the size of all pairs, under a ``lax.cond``."""
    b, t, d = x.shape
    n = b * t
    n_held = params["w_gate"].shape[0]
    n_experts = params["router"].shape[-1]
    x2d = x.reshape(n, d)
    cast = (lambda a: a) if policy is None else policy.cast_in
    accum = jnp.float32 if policy is None else policy.accum
    gates, experts = globals()[DROPLESS_ROUTERS[router]](
        x2d, params["router"], top_k)

    m = n * top_k
    local = experts.reshape(m) - first
    held = (local >= 0) & (local < n_held)
    # pairs of absent experts sort last (group ``n_held``) and land in
    # a tile of their own that never runs
    group = jnp.where(held, local, n_held)
    order = jnp.argsort(group, stable=True)
    sorted_group = group[order]
    sizes = jnp.sum(group[:, None] == jnp.arange(n_held + 1)[None, :],
                    axis=0, dtype=jnp.int32)

    def grouped(m_cap):
        """The walk with buffers for ``m_cap`` pairs on held experts."""
        tm = dropless_tile(m_cap, n_held)
        tiles_of = -(-sizes // tm)
        tile_start = jnp.cumsum(tiles_of) - tiles_of
        n_tiles = -(-m_cap // tm) + n_held      # static upper bound
        offsets = jnp.cumsum(sizes) - sizes
        rank = jnp.arange(m, dtype=jnp.int32) - offsets[sorted_group]
        dest_sorted = jnp.where(sorted_group < n_held,
                                tile_start[sorted_group] * tm + rank,
                                n_tiles * tm)   # absent: the spare row
        dest = jnp.zeros((m,), jnp.int32).at[order].set(dest_sorted)

        rows = jnp.zeros((n_tiles * tm + 1, d), cast(x2d).dtype)
        rows = rows.at[dest].set(cast(jnp.repeat(x2d, top_k, axis=0)))
        # tile -> its expert: the last expert whose first tile is <= tile
        tile_ids = jnp.arange(n_tiles, dtype=jnp.int32)
        tile_expert = jnp.clip(
            jnp.searchsorted(tile_start[:n_held], tile_ids,
                             side="right") - 1,
            0, n_held - 1).astype(jnp.int32)
        used_tiles = jnp.sum(tiles_of[:n_held])

        def one_tile(_, i):
            e = tile_expert[i]

            def run(_):
                xt = lax.dynamic_slice_in_dim(rows, i * tm, tm)

                def w(name):
                    return cast(lax.dynamic_index_in_dim(
                        params[name], e, keepdims=False))

                g = jnp.matmul(xt, w("w_gate"),
                               preferred_element_type=accum)
                u = jnp.matmul(xt, w("w_up"), preferred_element_type=accum)
                h = cast(jax.nn.silu(g) * u)
                return jnp.matmul(h, w("w_down"),
                                  preferred_element_type=accum)

            return None, lax.cond(i < used_tiles, run,
                                  lambda _: jnp.zeros((tm, d), accum), None)

        _, ys = lax.scan(one_tile, None, tile_ids)
        ys = jnp.concatenate([ys.reshape(n_tiles * tm, d),
                              jnp.zeros((1, d), accum)])
        picked = ys[dest].reshape(n, top_k, d)
        w_pair = jnp.where(held.reshape(n, top_k), gates, 0.0)
        return jnp.sum(picked.astype(jnp.float32) * w_pair[..., None],
                       axis=1)

    # a share's pairs (all of them where every expert is held: not counted)
    pairs = jnp.sum(sizes[:n_held]) if n_held < n_experts else None
    bound = dropless_pair_bound(m, n_held, n_experts)
    if bound >= m:
        y = grouped(m)
    else:
        y = lax.cond(pairs <= bound, lambda: grouped(bound),
                     lambda: grouped(m))
    if counts is not None and pairs is not None:
        counts["expert_pairs"] = pairs
    touched = jnp.sum(sizes[:n_held] > 0, dtype=jnp.int32)
    return y.reshape(b, t, d).astype(x.dtype), touched


def shared_experts_init(rng, d_model, d_expert, n_shared,
                        dtype=jnp.float32):
    """The gated-SiLU matrices of ``n_shared`` experts that every token
    takes, side by side as ONE gated MLP of width ``n_shared x
    d_expert`` (the sum of the experts' results is that MLP's)."""
    std = 1.0 / math.sqrt(d_model)
    width = n_shared * d_expert

    def w(shape, s):
        return jnp.asarray(rng.normal(0.0, s, shape), dtype)

    return {"w_gate": w((d_model, width), std),
            "w_up": w((d_model, width), std),
            "w_down": w((width, d_model), 1.0 / math.sqrt(d_expert))}


def shared_experts_forward(params, x, scale=1.0, policy=None):
    """``scale x sum_s E_s(x)`` of the shared experts of
    ``shared_experts_init`` (``scale`` 1 / n_shared averages them): a
    dense gated-SiLU MLP, float32 accumulation, x's dtype out."""
    cast = (lambda a: a) if policy is None else policy.cast_in
    accum = jnp.float32 if policy is None else policy.accum
    xc = cast(x)
    g = jnp.matmul(xc, cast(params["w_gate"]), preferred_element_type=accum)
    u = jnp.matmul(xc, cast(params["w_up"]), preferred_element_type=accum)
    y = jnp.matmul(cast(jax.nn.silu(g) * u), cast(params["w_down"]),
                   preferred_element_type=accum)
    return (y * scale).astype(x.dtype)
