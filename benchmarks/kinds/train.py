"""Traffic kind ``train``: ``StandardWorkflow`` -> ``StagedTrainer``'s
fused sweep of ``steps_per_dispatch`` steps, over seeded token rows.

Set-up builds ONE workflow, drives it through its first sweep with the
window's own call and feed (``feed_sweep``), reads what that sweep left
(the sweep's loss, the optimizer's first moment, the parameters' change
— per-leaf norms, a few hundred floats) and hands the same object to
the window.  After the window closes and the program's state is freed,
the plain reference follows the same steps on the same rows and the
numbers are compared (``benchmarks/check.py``)."""

import statistics
import time

import numpy as np

from benchmarks import build, check, harness, reference

#: sweeps the host may have dispatched beyond the one the device last
#: finished (the program's own loop runs ahead freely; unbounded, the
#: window could not be closed on time)
IN_FLIGHT = 2


class Feed:
    """The window's call and feed: ``steps_per_dispatch`` x
    (``loader.run``, ``trainer.run``); the last ``trainer.run`` fires
    the fused dispatch.  Records every minibatch's row indices and the
    host time of each ``loader.run``."""

    def __init__(self, wf, spd):
        self.wf, self.spd = wf, spd
        self.indices, self.loader_ms = [], []

    def sweep(self, record=False):
        for _ in range(self.spd):
            t0 = time.perf_counter()
            self.wf.loader.run()
            self.loader_ms.append((time.perf_counter() - t0) * 1e3)
            if record:
                self.indices.append(
                    np.array(self.wf.loader.minibatch_indices))
            self.wf.trainer.run()

    def marker(self):
        """A small fresh array that is ready when every sweep
        dispatched so far is (the accumulators themselves are donated
        to the next sweep and cannot be waited on)."""
        from veles_tpu.loader.base import TRAIN
        return self.wf.trainer.class_stats[TRAIN]["count"] + 0


def program_norms(trainer, cfg, seed):
    """Per-leaf norms of the optimizer's first moment and of the
    parameters' change from the seeded weights, named as the reference
    names leaves."""
    import jax
    import jax.numpy as jnp

    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    @jax.jit
    def norms(params, slot1, key):
        w0 = build.to_program_tree(
            trainer, reference.weights_from_key(cfg, key))
        change = jax.tree_util.tree_map(lambda a, b: norm(a - b),
                                        params, w0)
        moment = jax.tree_util.tree_map(norm, slot1)
        return (build.from_program_tree(trainer, moment),
                build.from_program_tree(trainer, change))

    moment, change = jax.device_get(norms(
        trainer.params, trainer.velocity["slot1"],
        reference.seed_key(seed)))
    return reference.flat_norms(moment), reference.flat_norms(change)


def reference_norms(cfg, traffic, seed, rows, precision="f32",
                    fault=None):
    """The reference through the same ``steps_per_dispatch`` steps on
    the same rows: (mean loss over the sweep, first-moment norms,
    change norms, first-step gradient norms).  ``fault`` plants one of
    the faults the checker must catch (benchmarks/tests, PERF.md):
    ``half_batch`` leaves half of each batch out and takes the mean over
    the rest."""
    import jax
    import jax.numpy as jnp
    opt = traffic["optimizer"]
    rpb = traffic["reference_rows_per_block"]
    if fault == "half_batch":
        rows = [r[:len(r) // 2] for r in rows]
        rpb = min(rpb, len(rows[0]))
    step = reference.make_train_step(cfg, opt, rpb, precision)
    w = reference.make_weights(cfg, seed)
    adam = reference.init_adam(w)
    losses, first = [], None
    for r in rows:
        w, adam, loss = step(w, adam, jnp.asarray(r))
        losses.append(float(loss))
        if first is None:
            first = reference.flat_norms(jax.device_get(
                reference.leaf_norms(adam["m"], cfg)))
    w0 = reference.make_weights(cfg, seed)
    change = jax.jit(lambda a, b: reference.leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b), cfg))(w, w0)
    moment = reference.leaf_norms(adam["m"], cfg)
    moment, change = jax.device_get((moment, change))
    del w, w0, adam
    return (statistics.fmean(losses), reference.flat_norms(moment),
            reference.flat_norms(change), first)


def compare(prog, ref):
    """The numbers of a training cell, from ``(loss, moment norms,
    change norms[, first-step gradient norms])`` of the program and of
    the reference."""
    skip = check.nought_leaves(ref[3])
    moment_gap, moment_leaf = check.worst_leaf_gap(prog[1], ref[1])
    change_gap, change_leaf = check.worst_leaf_gap(prog[2], ref[2], skip)
    numbers = {
        "loss_gap": abs(prog[0] - ref[0]) / abs(ref[0]),
        "moment_gap": moment_gap,
        "change_gap": change_gap,
    }
    notes = {"moment_leaf": moment_leaf, "change_leaf": change_leaf,
             "nought_leaves": len(skip), "loss_program": prog[0],
             "loss_reference": ref[0]}
    return numbers, notes


def setup(ctx):
    """Build, seed, compile, first sweep, snapshot.  Returns the state
    the window runs on."""
    import jax
    from veles_tpu.loader.base import TRAIN
    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    spd, batch = traffic["steps_per_dispatch"], traffic["batch"]
    rows = build.token_rows(cfg, batch * spd * traffic["epoch_sweeps"],
                            traffic["seq"], ctx.seed)
    wf = build.build_workflow(cfg, rows, batch, spd, traffic["optimizer"],
                              traffic["remat"], name="bench-train")
    tr = wf.trainer
    ctx.phases.mark("build_program")
    build.install_weights(tr, cfg, ctx.seed)
    jax.block_until_ready(tr.params)
    ctx.phases.mark("seeded_weights")
    # the sweep's one compile (or its load from the cache): the first
    # dispatch below finds this executable in the jit's own cache
    # (StagedTrainer.lower_train_sweep), so reading memory_analysis()
    # here costs no second compile
    compiled = tr.lower_train_sweep().compile()
    ctx.phases.mark("compile_sweep")
    mem = compiled.memory_analysis()
    program_bytes = sum(int(getattr(mem, k, 0) or 0) for k in (
        "argument_size_in_bytes", "temp_size_in_bytes",
        "output_size_in_bytes")) - int(
            getattr(mem, "alias_size_in_bytes", 0) or 0)
    del compiled
    feed = Feed(wf, spd)
    feed.sweep(record=True)
    jax.block_until_ready(tr.class_stats)
    stats = tr.read_class_stats(TRAIN)
    moment, change = program_norms(tr, cfg, ctx.seed)
    prog = (stats["loss"] / stats["count"], moment, change)
    tr.reset_epoch_stats()
    first_rows = [rows[idx] for idx in feed.indices]
    # the marker's own small program; with it every program the window
    # uses has run once
    jax.block_until_ready(feed.marker())
    ctx.phases.mark("first_sweep_and_snapshot")
    return wf, feed, prog, first_rows, program_bytes


def window(ctx, wf, feed):
    """Sweeps until ``ctx.seconds`` have passed; at most ``IN_FLIGHT``
    dispatched and not finished.  The window closes when the last sweep
    it started is ready.  Returns ``(sweeps, wall seconds, times at
    which sweeps became ready, tracer, seconds the profiler's own start
    and stop held the loop)``."""
    import jax
    done_at, markers = [], []
    tracer = harness.TraceWindow() if ctx.trace else None
    trace_from = ctx.seconds * 0.3
    trace_for = min(ctx.cell.traffic["trace_seconds"], ctx.seconds * 0.5)
    held = 0.0
    t0 = time.perf_counter()
    sweeps = 0
    while True:
        now = time.perf_counter() - t0
        if now >= ctx.seconds:
            break
        if tracer and tracer.t0 is None and now >= trace_from:
            held -= time.perf_counter()
            tracer.start()
            held += time.perf_counter()
        with harness.span("bench.feed_sweep"):
            feed.sweep()
        sweeps += 1
        markers.append(feed.marker())
        if len(markers) >= IN_FLIGHT:
            with harness.span("bench.wait_device"):
                jax.block_until_ready(markers.pop(0))
            done_at.append(time.perf_counter())
        if tracer and tracer.t1 is None and tracer.t0 is not None and \
                time.perf_counter() - tracer.t0 >= trace_for:
            # the capture ends on an empty device queue (the drain is
            # sweeps finishing: the step's time); the profiler's own
            # stop then holds the loop, which is the tracing's cost
            jax.block_until_ready(markers)
            held -= time.perf_counter()
            tracer.stop()
            held += time.perf_counter()
    for m in markers:
        jax.block_until_ready(m)
        done_at.append(time.perf_counter())
    jax.block_until_ready(wf.trainer.params)
    wall = time.perf_counter() - t0
    if tracer and tracer.t1 is None and tracer.t0 is not None:
        tracer.stop()
    return sweeps, wall, done_at, tracer, held


def run(ctx):
    import jax
    from veles_tpu.loader.base import TRAIN
    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    spd = traffic["steps_per_dispatch"]
    wf, feed, prog, first_rows, program_bytes = setup(ctx)
    tr = wf.trainer
    compiles = harness.compile_events()
    feed.loader_ms.clear()
    setup_s = time.perf_counter() - ctx.t0
    sweeps, wall, done_at, tracer, held = window(ctx, wf, feed)
    ctx.phases.mark("window")
    compiles = harness.compile_events() - compiles
    tr.read_class_stats(TRAIN)      # also brings the health counters over
    health = tr._health_host or {}
    failed = int(float(health.get("anomalies", 0.0))
                 + float(health.get("skipped", 0.0)))
    tokens = sweeps * spd * traffic["batch"] * traffic["seq"]
    # memory_stats() counts live buffers and leaves out a program's
    # temporaries (PR 21); the sweep's own memory_analysis() has them
    stats_peak = harness.memory_peak_bytes(ctx.cell.chips)
    peak = max(stats_peak, program_bytes)
    collected = {
        "cfg": cfg, "traffic": traffic, "peaks": ctx.peaks,
        # a traced run: the window less what the profiler's own start
        # and stop held the loop (0 in an untraced run)
        "tokens_per_s": tokens / (wall - held),
        "loader_ms": list(feed.loader_ms),
        "sweep_ms": [(b - a) * 1e3 for a, b in zip(done_at, done_at[1:])],
        "steps_per_dispatch": spd,
    }
    if tracer is not None:
        collected["trace"] = tracer.reduce(ctx.cell.chips)
    # free the program's state before the reference takes the chip
    wf.trainer.params = wf.trainer.velocity = None
    del wf, tr, feed
    import gc
    gc.collect()
    ctx.phases.mark("read_and_free")
    t_ref = time.perf_counter()
    ref = reference_norms(cfg, traffic, ctx.seed, first_rows)
    numbers, notes = compare(prog, ref)
    ctx.phases.mark("reference")
    numbers["compiles_in_window"] = float(compiles)
    notes.update(reference_s=time.perf_counter() - t_ref,
                 memory_stats_peak_bytes=stats_peak,
                 sweep_program_bytes=program_bytes)
    return {
        "end_to_end": {"train_tokens_per_s": tokens / wall,
                       "setup_s": setup_s},
        "attempted": sweeps * spd, "failed": failed,
        "memory_peak_bytes": peak, "numbers": numbers, "notes": notes,
        "collected": collected,
    }
