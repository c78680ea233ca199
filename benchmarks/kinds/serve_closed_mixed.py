"""Traffic kind ``serve_closed_mixed``: command-a-plus-05-2026's
language model (sliding-window and full-attention layers in one paged
pool of two groups, a parallel block, a sigmoid router over 128 experts
of which this chip holds 16, four shared experts) through
``LMGenerator`` -> ``PagedContinuousBatcher`` -> ``RESTfulAPI`` with
segmented prefill, driven by the closed loop of
``benchmarks/client_closed.py`` — short and long prompts in one queue.

The window, its edges on deliveries, the record reduction and the
sample of checked answers are ``serve_closed``'s; the loop, the wait
for the loop's ``warm_finished``-th request and the run's shape are
``serve_closed_sparse``'s; the build (``build_cmda``: parameters
bfloat16 from the build on), the reference (``reference_cmda``: one
layer's weights at a time), the FLOP count (``flops_cmda``) and the
reading of BOTH groups' blocks in use are this kind's own."""

import gc
import time

from benchmarks import build_cmda, flops_cmda, harness, reference_cmda
from benchmarks.kinds.serve_closed import (
    check_sample, malformed, median, on_deliveries, reduce_records, warm,
    window)
from benchmarks.kinds.serve_closed_sparse import ClosedLoop, wait_served

#: the controls of ``logit_gap``, each a lesser reference whose first
#: choices take the served tokens' place: every matmul operand on a
#: per-tensor int8 grid; the sliding layers attending the whole context;
#: the full layers rotating q and k; the shared experts left out
CONTROLS = {
    "int8": dict(probe_precision="int8"),
    "window_off": dict(window_off=True),
    "rope_on_full": dict(rope_on_full=True),
    "shared_dropped": dict(shared_dropped=True),
}


class LiveBytes:
    """What ``serve_closed.window`` polls, ``pool_blocks -
    free_blocks()`` every 50 ms, made to read the BYTES that the blocks
    in use hold in both groups of the pool."""

    pool_blocks = 0

    def __init__(self, cb, full_block_bytes, ring_block_bytes):
        self.cb = cb
        self.bytes = (full_block_bytes, ring_block_bytes)
        self.blocks = []            # (full, window) at each reading

    def free_blocks(self):
        in_use = self.cb.blocks_in_use()
        self.blocks.append(in_use)
        return -sum(n * size for n, size in zip(in_use, self.bytes))


def start_server(ctx):
    """The server up on seeded bfloat16 weights.  Returns the workflow
    (it holds the weights), the API, and the bytes the serving state is
    made of."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.models.generate import LMGenerator
    from veles_tpu.services.restful import RESTfulAPI
    cfg, tf = ctx.cell.config, ctx.cell.traffic
    wf = build_cmda.build_workflow(cfg, tf["max_len"])
    wf.trainer.velocity = None      # serving holds no optimizer state
    ctx.phases.mark("build_program")
    build_cmda.install_weights(wf.trainer, cfg, ctx.seed)
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
    leaves = jax.tree_util.tree_leaves(gen.params)
    if any(a.dtype == jnp.float32 and a.size > 1 << 20 for a in leaves):
        raise RuntimeError("the generator holds a float32 weight")
    sliding, full = flops_cmda.layer_kinds(cfg)
    token = flops_cmda.kv_bytes_per_token_layer(
        cfg, jnp.dtype(cache_dtype).itemsize)
    footprint = {
        "weights_bytes": sum(int(a.nbytes) for a in leaves),
        "full_block_bytes": cb.block * full * token,
        "window_block_bytes": cb.block * sliding * token,
        "pool_blocks": cb.pool_blocks,
        "ring_blocks": list(cb.ring_blocks),
        "window_pool_blocks": tf["slots"] * sum(cb.ring_blocks)}
    api.start()
    ctx.phases.mark("server_up")
    return wf, api, footprint


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
        # point of the fixed order of sizes
        opened_on_count = wait_served(
            api.engine, served + tf["warm_finished"], tf["warm_seconds"])
        compiles = harness.compile_events()
        ctx.phases.mark("warm_loop")
        setup_s = time.perf_counter() - ctx.t0
        live = LiveBytes(api.engine.cb, footprint["full_block_bytes"],
                         footprint["window_block_bytes"])
        t_open, t_close, tracer, live_bytes = window(ctx, live)
        ctx.phases.mark("window")
        compiles = harness.compile_events() - compiles
        records = loop.finish()
        engine = api.engine.metrics()
    finally:
        if loop is not None:
            loop.kill()
        api.stop()
    stats_peak = harness.memory_peak_bytes(ctx.cell.chips)
    # free the program's state before the reference takes the chip (the
    # gauge holds the batcher: its readings stay, it goes)
    blocks_in_use = live.blocks
    api.engine = api.generator = None
    wf.trainer.params = None
    del wf, api, live
    gc.collect()
    ctx.phases.mark("cut_stop_and_free")
    return {"records": records, "t_open": t_open, "t_close": t_close,
            "tracer": tracer, "compiles": compiles, "setup_s": setup_s,
            "opened_on_count": opened_on_count, "engine": engine,
            "live_bytes": live_bytes, "blocks_in_use": blocks_in_use,
            "footprint": footprint, "memory_stats_peak_bytes": stats_peak}


def check(ctx, got, red, controls=()):
    """The numbers that decide ``correct``, and what was compared; and
    the readings of ``logit_gap`` under each of ``controls`` (names of
    ``CONTROLS``; ``benchmarks/calibrate_cmda.py``).  A run none of
    whose checked answers has passed ``sliding_window +
    prefill_segment`` positions — where the ring has wrapped under a
    pass — has not checked the mechanism and is not correct."""
    cfg, tf = ctx.cell.config, ctx.cell.traffic
    records = got["records"]
    sample = check_sample(records, tf["check_requests"], ctx.seed)
    last = max((len(r["result"]) for r in sample), default=0)
    wrapped = last > cfg["sliding_window"] + tf["prefill_segment"]
    logits = reference_cmda.reference_logits(cfg, ctx.seed, sample) \
        if wrapped else None
    gap, n_tokens = reference_cmda.logit_gaps(
        cfg, ctx.seed, sample, reference=logits) if wrapped \
        else (float("inf"), 0)
    numbers = {"logit_gap": gap,
               "malformed": float(malformed(records)),
               "unanswered": float(len(red["failed"])),
               "compiles_in_window": float(got["compiles"])}
    notes = {"checked_answers": len(sample), "checked_tokens": n_tokens,
             "checked_lengths": [len(r["result"]) for r in sample],
             "checked_last_position": last}
    for name in controls if wrapped else ():
        notes["control_%s_logit_gap" % name] = reference_cmda.logit_gaps(
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
        collected["trace"] = got["tracer"].reduce(ctx.cell.chips)
    ctx.phases.mark("reduce")
    t_ref = time.perf_counter()
    numbers, notes = check(ctx, got, red)
    ctx.phases.mark("reference")
    # the serving state the window held: the bfloat16 weights and the
    # blocks in use, both groups', at their fullest reading
    fp, blocks = got["footprint"], got["blocks_in_use"]
    live = fp["weights_bytes"] + max(got["live_bytes"], default=0)
    engine = got["engine"]
    notes.update(
        reference_s=time.perf_counter() - t_ref,
        window_opened_on_count=got["opened_on_count"],
        requests_finished=sum(r["outcome"] == "ok" for r in records),
        requests_cut_at_the_close=sum(r["outcome"] == "cut"
                                      for r in records),
        outcomes_failed=sorted({r["outcome"] for r in red["failed"]}),
        # no metric: a window starts a few requests a second
        ttft_ms_p50=median(red["ttft_ms"]),
        queue_ms_p50=median([p["queue"] for p in red["phases"]
                             if "queue" in p]),
        delivered_by_second=red["delivered_by_second"],
        weights_bytes=fp["weights_bytes"],
        parameters=flops_cmda.parameters(cfg),
        pool_bytes_reserved=fp["pool_blocks"] * fp["full_block_bytes"]
        + fp["window_pool_blocks"] * fp["window_block_bytes"],
        pool_blocks=fp["pool_blocks"], ring_blocks=fp["ring_blocks"],
        pool_blocks_full_in_use_max=max((b[0] for b in blocks), default=0),
        pool_blocks_window_in_use_max=max((b[1] for b in blocks),
                                          default=0),
        memory_stats_peak_bytes=got["memory_stats_peak_bytes"],
        # beside the 1.0 pair a token that ``mixed_serve_mfu`` assumes:
        # the pairs that landed on the held experts, a decoded row (the
        # idle slots' rows route too) and a staged token
        expert_pairs_per_row=(engine.get("p50_tick_expert_pairs") or 0.0)
        / tf["slots"],
        expert_pairs_per_staged_token=engine.get(
            "staged_expert_pairs_per_token"),
        routed_experts_assumed=flops_cmda.routed_experts_here(cfg),
        tick={k: engine.get(k) for k in (
            "ticks_total", "p50_tick_ms", "p50_tick_wait_ms",
            "p50_tick_host_ms", "p50_tick_fetch_ms", "p50_tick_admit_ms",
            "p50_tick_kv_tokens", "p50_tick_kv_pages", "p50_tick_sel_keys",
            "p50_tick_win_keys", "p50_tick_experts_touched",
            "p50_tick_expert_pairs", "tick_rows_mean",
            "pool_blocks_full_in_use", "pool_blocks_window_in_use",
            "prefill_segments_total")})
    return {
        "end_to_end": {"out_tokens_per_s": red["out_tokens_per_s"],
                       "setup_s": got["setup_s"]},
        "attempted": len(red["sent"]),
        "failed": sum(1 for r in red["sent"]
                      if r["outcome"] not in ("ok", "cut")),
        "memory_peak_bytes": live, "numbers": numbers, "notes": notes,
        "collected": collected,
    }
