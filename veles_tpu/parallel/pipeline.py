"""Pipeline parallelism: GPipe-style microbatch pipelining over a
``pipe`` mesh axis.

New capability beyond the reference (SURVEY.md §2.6: PP absent there).
Each device holds ONE stage's parameters; activations flow stage-to-stage
with ``lax.ppermute`` (one ICI neighbor hop per tick) while microbatches
stream through, so all stages compute concurrently after the fill phase —
the classic GPipe schedule with bubble fraction (S-1)/(M+S-1).

GPipe constraint: every stage maps activations to the SAME shape (the
transformer-block regime pipelining is used for); embed/head layers live
outside the pipelined segment.  The whole schedule is a ``lax.scan``, so
it jits, differentiates (reverse-mode re-runs the scan), and composes
with the other mesh axes.

``pipeline_train_1f1b`` lifts both GPipe limits for training: the 1F1B
schedule (steady state: one forward + one backward sub-tick per tick)
keeps only O(S) stashed microbatch inputs per device instead of the
O(M) residuals reverse-mode stores through the GPipe scan, and the
first/last stages may differ from the middle ones (``first_fn`` embeds
int tokens, ``last_fn`` runs the head + loss), so embed→blocks→head
pipelines end-to-end.  Backward recomputes each stage forward from the
stashed input (``jax.vjp``) — the same FLOPs-for-memory trade as
full remat, but scheduled so the bubble stays (S-1)/(M+S-1)."""

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, x, axis_name, n_microbatches):
    """Inside shard_map over ``axis_name``: run the pipeline.

    stage_fn(stage_params, h) -> h (same shape); ``stage_params`` are THIS
    device's stage weights; ``x`` [B, ...] is the full batch (meaningful on
    stage 0, replicated elsewhere).  Returns [B, ...] outputs of the last
    stage, broadcast to every stage."""
    s = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    b = x.shape[0]
    if b % n_microbatches:
        raise ValueError("batch %d %% n_microbatches %d != 0"
                         % (b, n_microbatches))
    mb = b // n_microbatches
    xs = x.reshape((n_microbatches, mb) + x.shape[1:])
    n_ticks = n_microbatches + s - 1
    fwd = [(i, i + 1) for i in range(s - 1)]   # no wraparound

    def tick(carry, t):
        outputs, recv = carry
        mb_idx = jnp.clip(t, 0, n_microbatches - 1)
        inp = jnp.where(me == 0, xs[mb_idx], recv)
        out = stage_fn(stage_params, inp)
        # the last stage finishes microbatch t-(s-1) at tick t
        out_idx = jnp.clip(t - (s - 1), 0, n_microbatches - 1)
        write = (me == s - 1) & (t >= s - 1)
        outputs = outputs.at[out_idx].set(
            jnp.where(write, out, outputs[out_idx]))
        recv = lax.ppermute(out, axis_name, fwd)
        return (outputs, recv), None

    outputs = jnp.zeros_like(xs)
    recv0 = jnp.zeros_like(xs[0])
    (outputs, _), _ = lax.scan(tick, (outputs, recv0),
                               jnp.arange(n_ticks))
    # broadcast the last stage's outputs to every device
    y = lax.psum(jnp.where(me == s - 1, outputs, 0.0), axis_name)
    return y.reshape(x.shape)


def pipeline_apply_sharded(stage_fn, stacked_params, x, mesh,
                           pipe_axis="pipe", n_microbatches=4,
                           batch_axis=None):
    """Global entry: ``stacked_params`` has a leading stage axis [S, ...]
    on every leaf, sharded over ``pipe_axis``.  With S == pipe size each
    device keeps one stage; with S == k * pipe size each device keeps k
    consecutive stages and runs them as one scanned "superstage" (fewer
    ICI hops, same math).  jit/grad-composable.

    ``batch_axis``: on a combined {data, pipe} mesh, shard x's batch dim
    over the data axis — each data slice streams ITS OWN microbatches
    through an independent pipeline (the ppermute hops stay within each
    data row of the mesh); without it x replicates and the data axis
    would redundantly recompute the full batch."""
    pipe_size = mesh.shape[pipe_axis]
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        if leaf.shape[0] % pipe_size:
            raise ValueError(
                "stacked stage dim %d not divisible by %s axis size %d"
                % (leaf.shape[0], pipe_axis, pipe_size))
    pspec = jax.tree_util.tree_map(lambda _: P(pipe_axis), stacked_params)
    xspec = P(batch_axis) if batch_axis else P()

    def fn(params, xs):
        def superstage(p, h):
            return lax.scan(lambda hh, pk: (stage_fn(pk, hh), None),
                            h, p)[0]
        return pipeline_apply(superstage, params, xs, pipe_axis,
                              n_microbatches)

    return shard_map(fn, mesh=mesh, in_specs=(pspec, xspec),
                     out_specs=xspec, check_vma=False)(stacked_params, x)

def pipeline_train_1f1b(stage_fn, first_fn, last_fn, params, x, y,
                        axis_name, n_microbatches):
    """Inside shard_map over ``axis_name``: one 1F1B training step.

    ``params = (p_first, p_blocks, p_last)``: ``p_blocks`` is THIS
    device's stacked block segment [k, ...] (run sequentially as a
    superstage); ``p_first`` / ``p_last`` are replicated but *computed*
    only on the boundary devices (``lax.cond`` keeps the untaken branch
    off the device's critical path).  ``first_fn(p, x_mb) -> h`` maps
    raw microbatch input (e.g. int tokens) to the inter-stage activation
    shape; ``stage_fn(p_block, h) -> h``; ``last_fn(p, h, y_mb) ->
    scalar mean-over-microbatch loss``.

    Schedule: fwd microbatch ``f = t - me`` and bwd microbatch
    ``j = t - 2(S-1) + me`` per tick — the last stage backpropagates a
    microbatch the same tick its forward finishes, cotangents hop one
    stage per tick, so device ``me`` holds at most ``2(S-1-me)``
    stashed inputs (O(S), vs O(M) for autodiff-through-GPipe).
    Returns ``(mean_loss, (g_first, g_blocks, g_last))``; boundary
    grads are psum'd (every device returns the true value)."""
    p_first, p_blocks, p_last = params
    s = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    m = n_microbatches
    if x.shape[0] % m:
        raise ValueError("batch %d %% n_microbatches %d != 0"
                         % (x.shape[0], m))
    mb = x.shape[0] // m
    xs = x.reshape((m, mb) + x.shape[1:])
    ys = y.reshape((m, mb) + y.shape[1:])
    fwd_pairs = [(i, i + 1) for i in range(s - 1)]
    bwd_pairs = [(i, i - 1) for i in range(1, s)]
    n_stash = 2 * (s - 1) + 1
    n_ticks = m + 2 * (s - 1)

    def seg_fwd(pf, pb, x_mb, h_recv):
        """This device's segment: embed on stage 0, then its blocks."""
        h0 = lax.cond(me == 0, lambda: first_fn(pf, x_mb),
                      lambda: h_recv)
        return lax.scan(lambda h, pk: (stage_fn(pk, h), None), h0, pb)[0]

    # probe the inter-stage activation shape without running the scan
    h_shape = jax.eval_shape(first_fn, p_first, xs[0])

    def tick(carry, t):
        stash, recv_fwd, recv_bwd, acc, loss_sum = carry
        gf, gb, gl = acc

        # ---- forward sub-tick: microbatch f = t - me ----
        f = t - me
        f_ok = (f >= 0) & (f < m)
        f_idx = jnp.clip(f, 0, m - 1)
        h_out = seg_fwd(p_first, p_blocks, xs[f_idx], recv_fwd)
        stash = stash.at[f_idx % n_stash].set(
            jnp.where(f_ok, recv_fwd, stash[f_idx % n_stash]))

        # ---- backward sub-tick: microbatch j = t - 2(S-1) + me ----
        j = t - 2 * (s - 1) + me
        j_ok = (j >= 0) & (j < m)
        j_idx = jnp.clip(j, 0, m - 1)
        h_in = stash[j_idx % n_stash]
        out_j, pull = jax.vjp(
            lambda pf, pb, hr: seg_fwd(pf, pb, xs[j_idx], hr),
            p_first, p_blocks, h_in)

        def last_cotangent():
            loss_j, lpull = jax.vjp(
                lambda pl, ho: last_fn(pl, ho, ys[j_idx]), p_last, out_j)
            dpl, g_out = lpull(jnp.float32(1.0 / m))
            return loss_j / m, dpl, g_out

        def mid_cotangent():
            zl = jax.tree_util.tree_map(jnp.zeros_like, p_last)
            return jnp.float32(0.0), zl, recv_bwd

        loss_j, dpl, g_out = lax.cond(me == s - 1, last_cotangent,
                                      mid_cotangent)
        dpf, dpb, dh = pull(g_out)

        ok = j_ok.astype(jnp.float32)
        gf = jax.tree_util.tree_map(lambda a, d: a + ok * d, gf, dpf)
        gb = jax.tree_util.tree_map(lambda a, d: a + ok * d, gb, dpb)
        gl = jax.tree_util.tree_map(lambda a, d: a + ok * d, gl, dpl)
        loss_sum = loss_sum + jnp.where(j_ok, loss_j, 0.0)

        recv_fwd = lax.ppermute(h_out, axis_name, fwd_pairs)
        recv_bwd = lax.ppermute(dh, axis_name, bwd_pairs)
        return (stash, recv_fwd, recv_bwd, (gf, gb, gl), loss_sum), None

    zeros_like = jax.tree_util.tree_map(jnp.zeros_like, (p_first, p_blocks,
                                                         p_last))
    stash0 = jnp.zeros((n_stash,) + h_shape.shape, h_shape.dtype)
    recv0 = jnp.zeros(h_shape.shape, h_shape.dtype)
    carry0 = (stash0, recv0, recv0, zeros_like, jnp.float32(0.0))
    (_, _, _, (gf, gb, gl), loss_sum), _ = lax.scan(
        tick, carry0, jnp.arange(n_ticks))

    # boundary grads / loss live on one device each — broadcast
    loss = lax.psum(jnp.where(me == s - 1, loss_sum, 0.0), axis_name)
    gf = jax.tree_util.tree_map(
        lambda g: lax.psum(jnp.where(me == 0, g, 0.0), axis_name), gf)
    gl = jax.tree_util.tree_map(
        lambda g: lax.psum(jnp.where(me == s - 1, g, 0.0), axis_name), gl)
    return loss, (gf, gb, gl)


def pipeline_train_1f1b_sharded(stage_fn, first_fn, last_fn, params, x, y,
                                mesh, pipe_axis="pipe", n_microbatches=4,
                                batch_axis=None, block_specs=None):
    """Global 1F1B entry: ``params = (p_first, p_blocks_stacked,
    p_last)`` with the block leaves stacked [n_blocks, ...] and sharded
    over ``pipe_axis`` (k = n_blocks / pipe_size consecutive blocks per
    device, like ``pipeline_apply_sharded``); first/last replicated.
    Returns ``(mean_loss, grads)`` in the params structure — block
    grads sharded over ``pipe_axis``, ready for the optimizer.

    ``batch_axis``: shard the batch dim over a data axis too; grads are
    pmean'd and the loss averaged across data slices.

    ``block_specs``: per-leaf PartitionSpecs for the block stack when a
    stage is ALSO tensor-parallel — e.g. ``{"w": P("pipe", None,
    "model"), "b": P("pipe")}`` column-shards each block's matrix over
    a ``model`` axis; ``stage_fn`` then uses the model axis's
    collectives (all_gather/psum) exactly as a Megatron layer would,
    and block grads come back in the same sharding."""
    p_first, p_blocks, p_last = params
    pipe_size = mesh.shape[pipe_axis]
    for leaf in jax.tree_util.tree_leaves(p_blocks):
        if leaf.shape[0] % pipe_size:
            raise ValueError(
                "stacked stage dim %d not divisible by %s axis size %d"
                % (leaf.shape[0], pipe_axis, pipe_size))
    if block_specs is not None:
        for spec in jax.tree_util.tree_leaves(
                block_specs, is_leaf=lambda s: isinstance(s, P)):
            if not spec or spec[0] != pipe_axis:
                # a spec that misses pipe on the stage dim would make
                # shard_map replicate the FULL block stack to every
                # device — each stage then runs the whole network:
                # silently wrong numbers, so fail loudly instead
                raise ValueError(
                    "block_specs leaf %s must shard its leading "
                    "(stage) dim over %r" % (spec, pipe_axis))
    bspec = (block_specs if block_specs is not None else
             jax.tree_util.tree_map(lambda _: P(pipe_axis), p_blocks))
    rspec_f = jax.tree_util.tree_map(lambda _: P(), p_first)
    rspec_l = jax.tree_util.tree_map(lambda _: P(), p_last)
    xspec = P(batch_axis) if batch_axis else P()

    def fn(pf, pb, pl, xx, yy):
        loss, (gf, gb, gl) = pipeline_train_1f1b(
            stage_fn, first_fn, last_fn, (pf, pb, pl), xx, yy,
            pipe_axis, n_microbatches)
        if batch_axis:
            loss = lax.pmean(loss, batch_axis)
            gf, gb, gl = jax.tree_util.tree_map(
                lambda g: lax.pmean(g, batch_axis), (gf, gb, gl))
        return loss, (gf, gb, gl)

    return shard_map(
        fn, mesh=mesh,
        in_specs=(rspec_f, bspec, rspec_l, xspec, xspec),
        out_specs=(P(), (rspec_f, bspec, rspec_l)),
        check_vma=False)(p_first, p_blocks, p_last, x, y)


def pipeline_train_1f1b_interleaved(stage_fn, first_fn, last_fn, params,
                                    x, y, axis_name, n_microbatches,
                                    n_chunks):
    """Interleaved 1F1B (Megatron virtual stages, Narayanan et al.
    2021) inside shard_map: each device holds ``n_chunks`` block
    chunks spaced S apart (stage k = chunk*S + device), shrinking the
    pipeline bubble below plain 1F1B for the same microbatch count at
    the cost of ~v x ppermute traffic.  The schedule is NOT derived
    inline: ``interleave.build_schedule`` simulates and VERIFIES the
    tick-by-tick unit/recv-slot timing host-side and this function
    merely replays its [D, T] tables (``table[me, t]`` lookups), so a
    scheduling bug is a loud build-time exception.

    ``params = (p_first, p_blocks, p_last)`` with p_blocks the
    device's [v, k_per_chunk, ...] chunk stack; first_fn/last_fn are
    cond-gated onto virtual stage 0 / S*v-1 exactly as in
    ``pipeline_train_1f1b``.  Returns (mean_loss, grads)."""
    from veles_tpu.parallel.interleave import build_schedule

    p_first, p_blocks, p_last = params
    s = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    m, v = n_microbatches, n_chunks
    if x.shape[0] % m:
        raise ValueError("batch %d %% n_microbatches %d != 0"
                         % (x.shape[0], m))
    tab = build_schedule(s, v, m)
    T, ns = tab["n_ticks"], tab["n_stash"]
    pad = jnp.full((s, 1), -1, jnp.int32)
    fwd_c = jnp.asarray(tab["fwd_chunk"])
    fwd_m = jnp.asarray(tab["fwd_mb"])
    bwd_c = jnp.asarray(tab["bwd_chunk"])
    bwd_m = jnp.asarray(tab["bwd_mb"])
    # shifted so slot_x[me, t] = where the value received at the END of
    # tick t (consumable from t+1) lands; final-tick sends discard
    store_f = jnp.concatenate([jnp.asarray(tab["store_f"])[:, 1:], pad],
                              axis=1)
    store_b = jnp.concatenate([jnp.asarray(tab["store_b"])[:, 1:], pad],
                              axis=1)

    mb = x.shape[0] // m
    xs = x.reshape((m, mb) + x.shape[1:])
    ys = y.reshape((m, mb) + y.shape[1:])
    ring_f = [(i, (i + 1) % s) for i in range(s)]
    ring_b = [(i, (i - 1) % s) for i in range(s)]
    h_shape = jax.eval_shape(first_fn, p_first, xs[0])

    def seg_fwd(pf, pb, c, x_mb, h_in):
        h0 = lax.cond((me == 0) & (c == 0),
                      lambda: first_fn(pf, x_mb), lambda: h_in)
        chunk = jax.tree_util.tree_map(lambda a: a[c], pb)
        return lax.scan(lambda h, pk: (stage_fn(pk, h), None), h0,
                        chunk)[0]

    def tick(carry, t):
        stash, recv_f, recv_b, acc, loss_sum = carry
        gf, gb, gl = acc

        # ---- forward sub-tick: unit (fwd_c, fwd_m)[me, t] ----
        c_f = fwd_c[me, t]
        m_f = fwd_m[me, t]
        f_ok = m_f >= 0
        ci = jnp.clip(c_f, 0, v - 1)
        mi = jnp.clip(m_f, 0, m - 1)
        h_in = recv_f[ci]
        h_out = seg_fwd(p_first, p_blocks, ci, xs[mi], h_in)
        stash = stash.at[ci, mi % ns].set(
            jnp.where(f_ok, h_in, stash[ci, mi % ns]))

        # ---- backward sub-tick: unit (bwd_c, bwd_m)[me, t] ----
        c_b = bwd_c[me, t]
        m_b = bwd_m[me, t]
        b_ok = m_b >= 0
        cbi = jnp.clip(c_b, 0, v - 1)
        mbi = jnp.clip(m_b, 0, m - 1)
        h_in_b = stash[cbi, mbi % ns]
        out_b, pull = jax.vjp(
            lambda pf, pb, hr: seg_fwd(pf, pb, cbi, xs[mbi], hr),
            p_first, p_blocks, h_in_b)

        def last_cotangent():
            loss_j, lpull = jax.vjp(
                lambda pl, ho: last_fn(pl, ho, ys[mbi]), p_last, out_b)
            dpl, g_out = lpull(jnp.float32(1.0 / m))
            return loss_j / m, dpl, g_out

        def mid_cotangent():
            zl = jax.tree_util.tree_map(jnp.zeros_like, p_last)
            return jnp.float32(0.0), zl, recv_b[cbi]

        loss_j, dpl, g_out = lax.cond(
            (me == s - 1) & (cbi == v - 1), last_cotangent,
            mid_cotangent)
        dpf, dpb, dh = pull(g_out)
        ok = b_ok.astype(jnp.float32)
        gf = jax.tree_util.tree_map(lambda a, d: a + ok * d, gf, dpf)
        gb = jax.tree_util.tree_map(lambda a, d: a + ok * d, gb, dpb)
        gl = jax.tree_util.tree_map(lambda a, d: a + ok * d, gl, dpl)
        loss_sum = loss_sum + jnp.where(b_ok, loss_j, 0.0)

        # ---- ring hops + verified recv-slot stores ----
        got_f = lax.ppermute(h_out, axis_name, ring_f)
        got_b = lax.ppermute(dh, axis_name, ring_b)
        sf = store_f[me, t]
        sb = store_b[me, t]
        recv_f = recv_f.at[jnp.clip(sf, 0, v - 1)].set(
            jnp.where(sf >= 0, got_f,
                      recv_f[jnp.clip(sf, 0, v - 1)]))
        recv_b = recv_b.at[jnp.clip(sb, 0, v - 1)].set(
            jnp.where(sb >= 0, got_b,
                      recv_b[jnp.clip(sb, 0, v - 1)]))
        return (stash, recv_f, recv_b, (gf, gb, gl), loss_sum), None

    zeros = jax.tree_util.tree_map(jnp.zeros_like,
                                   (p_first, p_blocks, p_last))
    stash0 = jnp.zeros((v, ns) + h_shape.shape, h_shape.dtype)
    recv0 = jnp.zeros((v,) + h_shape.shape, h_shape.dtype)
    carry0 = (stash0, recv0, recv0, zeros, jnp.float32(0.0))
    (_, _, _, (gf, gb, gl), loss_sum), _ = lax.scan(
        tick, carry0, jnp.arange(T))
    loss = lax.psum(jnp.where(me == s - 1, loss_sum, 0.0), axis_name)
    gf = jax.tree_util.tree_map(
        lambda g: lax.psum(jnp.where(me == 0, g, 0.0), axis_name), gf)
    gl = jax.tree_util.tree_map(
        lambda g: lax.psum(jnp.where(me == s - 1, g, 0.0), axis_name),
        gl)
    return loss, (gf, gb, gl)


def pipeline_train_interleaved_sharded(stage_fn, first_fn, last_fn,
                                       params, x, y, mesh,
                                       pipe_axis="pipe",
                                       n_microbatches=4, n_chunks=2,
                                       batch_axis=None):
    """Global interleaved-1F1B entry: block leaves stacked
    [n_blocks, ...] with n_blocks = pipe * n_chunks * k; device d's
    chunk c holds blocks [(c*pipe + d) * k : ...] — the round-robin
    layout that puts stage k on device k %% pipe.  Returns
    (mean_loss, grads) with block grads sharded over ``pipe_axis``."""
    p_first, p_blocks, p_last = params
    pipe = mesh.shape[pipe_axis]
    for leaf in jax.tree_util.tree_leaves(p_blocks):
        if leaf.shape[0] % (pipe * n_chunks):
            raise ValueError(
                "stacked stage dim %d not divisible by pipe*chunks %d"
                % (leaf.shape[0], pipe * n_chunks))

    # reorder blocks so each device's shard is its [v, kpc] chunk
    # stack: global block (c*pipe + d)*kpc + j  ->  shard index
    # d*(v*kpc) + c*kpc + j
    def to_chunks(leaf):
        n = leaf.shape[0]
        kpc = n // (pipe * n_chunks)
        a = leaf.reshape((n_chunks, pipe, kpc) + leaf.shape[1:])
        a = jnp.moveaxis(a, 1, 0)       # [pipe, v, kpc, ...]
        return a.reshape((pipe * n_chunks * kpc,) + leaf.shape[1:])

    def from_chunks(leaf):
        n = leaf.shape[0]
        kpc = n // (pipe * n_chunks)
        a = leaf.reshape((pipe, n_chunks, kpc) + leaf.shape[1:])
        a = jnp.moveaxis(a, 0, 1)
        return a.reshape((n,) + leaf.shape[1:])

    pb_r = jax.tree_util.tree_map(to_chunks, p_blocks)
    bspec = jax.tree_util.tree_map(lambda _: P(pipe_axis), p_blocks)
    rspec_f = jax.tree_util.tree_map(lambda _: P(), p_first)
    rspec_l = jax.tree_util.tree_map(lambda _: P(), p_last)
    xspec = P(batch_axis) if batch_axis else P()

    def fn(pf, pb, pl, xx, yy):
        # local shard [v*kpc, ...] -> [v, kpc, ...]
        pb_local = jax.tree_util.tree_map(
            lambda a: a.reshape((n_chunks, a.shape[0] // n_chunks)
                                + a.shape[1:]), pb)
        loss, (gf, gb, gl) = pipeline_train_1f1b_interleaved(
            stage_fn, first_fn, last_fn, (pf, pb_local, pl), xx, yy,
            pipe_axis, n_microbatches, n_chunks)
        gb = jax.tree_util.tree_map(
            lambda a: a.reshape((a.shape[0] * a.shape[1],)
                                + a.shape[2:]), gb)
        if batch_axis:
            loss = lax.pmean(loss, batch_axis)
            gf, gb, gl = jax.tree_util.tree_map(
                lambda g: lax.pmean(g, batch_axis), (gf, gb, gl))
        return loss, (gf, gb, gl)

    loss, (gf, gb, gl) = shard_map(
        fn, mesh=mesh,
        in_specs=(rspec_f, bspec, rspec_l, xspec, xspec),
        out_specs=(P(), (rspec_f, bspec, rspec_l)),
        check_vma=False)(p_first, pb_r, p_last, x, y)
    return loss, (gf, jax.tree_util.tree_map(from_chunks, gb), gl)
