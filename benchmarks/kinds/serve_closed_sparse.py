"""Traffic kind ``serve_closed_sparse``: the Keye-VL-2.0 language model
(expert layers, a sparse-attention indexer, a pool of three leaves)
through ``LMGenerator`` -> ``PagedContinuousBatcher`` -> ``RESTfulAPI``
with segmented prefill, driven by the closed loop of
``benchmarks/client_closed.py``.

The window, its edges on deliveries, the pool polling, the record
reduction and the sample of checked answers are ``serve_closed``'s; the
build (``build_keye``: parameters bfloat16 from the build on), the
reference call (``reference_keye``: one layer's weights at a time) and
the FLOP count (``flops_keye``) are this kind's own."""

import gc
import os
import subprocess
import sys
import time

from benchmarks import build_keye, client_closed, flops_keye, harness, \
    kernel_work_keye, reference_keye
from benchmarks.kinds import serve_closed
from benchmarks.kinds.serve_closed import (
    check_sample, malformed, median, on_deliveries, reduce_records, warm,
    window)


def start_server(ctx):
    """The server up on seeded bfloat16 weights.  Returns the workflow
    (it holds the weights), the API, and the bytes the serving state is
    made of."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.models.generate import LMGenerator
    from veles_tpu.services.restful import RESTfulAPI
    cfg, tf = ctx.cell.config, ctx.cell.traffic
    wf = build_keye.build_workflow(cfg, tf["max_len"])
    wf.trainer.velocity = None      # serving holds no optimizer state
    ctx.phases.mark("build_program")
    build_keye.install_weights(wf.trainer, cfg, ctx.seed)
    jax.block_until_ready(wf.trainer.params)
    ctx.phases.mark("seeded_weights")
    cache_dtype = getattr(jnp, tf["cache_dtype"])
    gen = LMGenerator(wf.trainer, max_len=tf["max_len"],
                      cache_dtype=cache_dtype)
    api = RESTfulAPI(lambda x: x, (tf["max_len"],), port=0, generator=gen,
                     continuous_slots=tf["slots"],
                     paged_block=tf["paged_block"],
                     pool_tokens=tf["pool_tokens"],
                     prefill_segment=tf["prefill_segment"])
    cb = api.engine.cb
    if cb.fused is not True:
        raise RuntimeError("the batcher did not take the fused tick")
    leaves = jax.tree_util.tree_leaves(gen.params)
    if any(a.dtype == jnp.float32 and a.size > 1 << 20 for a in leaves):
        raise RuntimeError("the generator holds a float32 weight")
    footprint = {
        "weights_bytes": sum(int(a.nbytes) for a in leaves),
        "block_bytes": cb.block * flops_keye.state_bytes_per_token(
            cfg, jnp.dtype(cache_dtype).itemsize),
        "pool_blocks": cb.pool_blocks}
    api.start()
    ctx.phases.mark("server_up")
    return wf, api, footprint


class ClosedLoop(serve_closed.ClosedLoop):
    """``benchmarks/client_closed.py`` as a process of its own."""

    def __init__(self, api, ctx):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(client_closed.__file__),
             api.host, str(api.port), api.path, ctx.cell.traffic_file,
             str(ctx.cell.config["vocab_size"]), str(ctx.seed)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)


def wait_served(engine, n, limit_s, poll_s=0.05):
    """Until the engine has finished ``n`` requests, ``limit_s`` seconds
    at most; whether it got there."""
    t0 = time.monotonic()
    while engine.metrics()["served"] < n:
        if time.monotonic() - t0 >= limit_s:
            return False
        time.sleep(poll_s)
    return True


def serve(ctx):
    """Server up, warm-up, the closed loop over one window, server down,
    the program's state freed.  Returns what the window left."""
    cfg, tf = ctx.cell.config, ctx.cell.traffic
    wf, api, footprint = start_server(ctx)
    loop = None
    try:
        warm(api, cfg, tf, ctx.seed)
        ctx.phases.mark("warm_requests")
        served = api.engine.metrics()["served"]
        loop = ClosedLoop(api, ctx)
        # the window opens when the loop's ``warm_finished``-th request
        # has finished (``warm_seconds`` at most): the slots are full
        # and turning over, and every run's window opens at the same
        # point of the fixed order of sizes — a window holds under ten
        # requests of this mix, so one opened on the clock held another
        # part of the order from run to run, and other work
        opened_on_count = wait_served(
            api.engine, served + tf["warm_finished"], tf["warm_seconds"])
        compiles = harness.compile_events()
        ctx.phases.mark("warm_loop")
        setup_s = time.perf_counter() - ctx.t0
        t_open, t_close, tracer, blocks = window(ctx, api.engine.cb)
        ctx.phases.mark("window")
        compiles = harness.compile_events() - compiles
        records = loop.finish()
        engine = api.engine.metrics()
    finally:
        if loop is not None:
            loop.kill()
        api.stop()
    stats_peak = harness.memory_peak_bytes(ctx.cell.chips)
    # free the program's state before the reference takes the chip
    api.engine = api.generator = None
    wf.trainer.params = None
    del wf, api
    gc.collect()
    ctx.phases.mark("cut_stop_and_free")
    return {"records": records, "t_open": t_open, "t_close": t_close,
            "tracer": tracer, "compiles": compiles, "setup_s": setup_s,
            "opened_on_count": opened_on_count, "engine": engine,
            "blocks_in_use": blocks,
            "footprint": footprint, "memory_stats_peak_bytes": stats_peak}


#: the controls of ``logit_gap``, each a lesser reference whose first
#: choices take the served tokens' place: every matmul operand on a
#: per-tensor int8 grid; the selection switched off (every key
#: attended); one expert of the 128 left out of every layer
CONTROLS = {
    "int8": dict(probe_precision="int8"),
    "selection_off": dict(select=False),
    "expert_dropped": dict(drop_expert=0),
}


def check(ctx, got, red, controls=()):
    """The numbers that decide ``correct``, and what was compared; and
    the readings of ``logit_gap`` under each of ``controls`` (names of
    ``CONTROLS``; ``benchmarks/calibrate_keye.py``)."""
    cfg, tf = ctx.cell.config, ctx.cell.traffic
    records = got["records"]
    sample = check_sample(records, tf["check_requests"], ctx.seed)
    logits = reference_keye.reference_logits(cfg, ctx.seed, sample) \
        if sample else None
    gap, n_tokens = reference_keye.logit_gaps(
        cfg, ctx.seed, sample, reference=logits) if sample \
        else (float("inf"), 0)
    numbers = {"logit_gap": gap,
               "malformed": float(malformed(records)),
               "unanswered": float(len(red["failed"])),
               "compiles_in_window": float(got["compiles"])}
    notes = {"checked_answers": len(sample), "checked_tokens": n_tokens,
             "checked_last_position": max(
                 (len(r["result"]) for r in sample), default=0)}
    for name in controls if sample else ():
        notes["control_%s_logit_gap" % name] = reference_keye.logit_gaps(
            cfg, ctx.seed, sample, reference=logits, **CONTROLS[name])[0]
    return numbers, notes


def run(ctx):
    cfg, tf = ctx.cell.config, ctx.cell.traffic
    got = serve(ctx)
    records = got["records"]
    red = reduce_records(records, *on_deliveries(
        records, got["t_open"], got["t_close"]))
    collected = dict(red, cfg=cfg, traffic=tf, peaks=ctx.peaks,
                     engine=got["engine"])
    if got["tracer"] is not None:
        # the conditional's whole span, before ``reduce`` deletes the file
        collected["sparse_decode"] = kernel_work_keye.sparse_decode_seconds(
            got["tracer"], cfg, tf, ctx.cell.chips)
        collected["trace"] = got["tracer"].reduce(ctx.cell.chips)
    ctx.phases.mark("reduce")
    t_ref = time.perf_counter()
    numbers, notes = check(ctx, got, red)
    ctx.phases.mark("reference")
    # the serving state the window held: the bfloat16 weights and the
    # pool blocks in use at their fullest reading (K, V and index keys)
    fp, blocks = got["footprint"], got["blocks_in_use"]
    live = fp["weights_bytes"] + max(blocks, default=0) * fp["block_bytes"]
    engine = got["engine"]
    notes.update(
        reference_s=time.perf_counter() - t_ref,
        window_opened_on_count=got["opened_on_count"],
        requests_finished=sum(r["outcome"] == "ok" for r in records),
        requests_cut_at_the_close=sum(r["outcome"] == "cut"
                                      for r in records),
        outcomes_failed=sorted({r["outcome"] for r in red["failed"]}),
        # no metric: a window starts about one request a second
        ttft_ms_p50=median(red["ttft_ms"]),
        queue_ms_p50=median([p["queue"] for p in red["phases"]
                             if "queue" in p]),
        delivered_by_second=red["delivered_by_second"],
        weights_bytes=fp["weights_bytes"],
        pool_bytes_reserved=fp["pool_blocks"] * fp["block_bytes"],
        pool_blocks=fp["pool_blocks"],
        pool_blocks_in_use_max=max(blocks, default=0),
        pool_blocks_in_use_at_close=blocks[-1] if blocks else 0,
        memory_stats_peak_bytes=got["memory_stats_peak_bytes"],
        tick={k: engine.get(k) for k in (
            "ticks_total", "p50_tick_ms", "p50_tick_wait_ms",
            "p50_tick_host_ms", "p50_tick_fetch_ms", "p50_tick_admit_ms",
            "p50_tick_kv_tokens", "p50_tick_sel_keys",
            "p50_tick_experts_touched", "prefill_segments_total")})
    return {
        "end_to_end": {"out_tokens_per_s": red["out_tokens_per_s"],
                       "setup_s": got["setup_s"]},
        "attempted": len(red["sent"]),
        "failed": sum(1 for r in red["sent"]
                      if r["outcome"] not in ("ok", "cut")),
        "memory_peak_bytes": live, "numbers": numbers, "notes": notes,
        "collected": collected,
    }
