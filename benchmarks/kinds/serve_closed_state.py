"""Traffic kind ``serve_closed_state``: Brumby-14B-Base (attention-free
power-retention layers: a fixed-size float32 state a slot where every
other served model pages keys and values; a dense gated-SiLU MLP)
through ``LMGenerator`` -> ``PagedContinuousBatcher`` -> ``RESTfulAPI``
with segmented prefill, driven by the closed loop of
``benchmarks/client_closed.py`` — decode-heavy: mid-sized prompts, then
hundreds to a thousand generated tokens.

The window, its edges on deliveries, the record reduction and the
sample of checked answers are ``serve_closed``'s; the loop, the wait
for the loop's ``warm_finished``-th request and the run's shape are
``serve_closed_sparse``'s; the build (``build_brumby``: parameters
bfloat16 from the build on), the reference (``reference_brumby``: the
quadratic form, one layer's weights at a time), the FLOP count
(``flops_brumby``), the reading of the STATE in use (there is no pool)
and the count of tokens delivered while the profiler ran are this
kind's own."""

import gc
import time

from benchmarks import build_brumby, flops_brumby, harness, reference_brumby
from benchmarks.kinds.serve_closed import (
    check_sample, malformed, median, on_deliveries, reduce_records, warm,
    window)
from benchmarks.kinds.serve_closed_sparse import ClosedLoop, wait_served

#: the controls of ``logit_gap``, each a lesser reference whose first
#: choices take the served tokens' place: every matmul operand on a
#: per-tensor int8 grid; nothing before the last multiple of
#: ``prefill_segment`` remembered (a program that dropped the state
#: between passes); the gates held at 1; the parent family's
#: exp(q.k / sqrt d) in place of the squared product
CONTROLS = {
    "int8": lambda tf: dict(probe_precision="int8"),
    "state_reset": lambda tf: dict(state_reset=tf["prefill_segment"]),
    "gate_off": lambda tf: dict(gate_off=True),
    "softmax_attention": lambda tf: dict(softmax_attention=True),
}


class LiveBytes:
    """What ``serve_closed.window`` polls, ``pool_blocks -
    free_blocks()`` every 50 ms, made to read the BYTES of fixed-size
    state the requests in the slots hold (the model has no blocks)."""

    pool_blocks = 0

    def __init__(self, cb):
        self.cb = cb
        self.slots = []             # slots holding state, each reading

    def free_blocks(self):
        held, nbytes = self.cb.state_in_use()
        self.slots.append(held)
        return -nbytes


def start_server(ctx):
    """The server up on seeded bfloat16 weights.  Returns the workflow
    (it holds the weights), the API, and the bytes the serving state is
    made of."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.models.generate import LMGenerator
    from veles_tpu.services.restful import RESTfulAPI
    cfg, tf = ctx.cell.config, ctx.cell.traffic
    wf = build_brumby.build_workflow(cfg, tf["max_len"])
    wf.trainer.velocity = None      # serving holds no optimizer state
    ctx.phases.mark("build_program")
    build_brumby.install_weights(wf.trainer, cfg, ctx.seed)
    jax.block_until_ready(wf.trainer.params)
    ctx.phases.mark("seeded_weights")
    gen = LMGenerator(wf.trainer, max_len=tf["max_len"],
                      cache_dtype=getattr(jnp, tf["cache_dtype"]))
    api = RESTfulAPI(lambda x: x, (tf["max_len"],), port=0, generator=gen,
                     continuous_slots=tf["slots"],
                     paged_block=tf["paged_block"],
                     prefill_segment=tf["prefill_segment"])
    cb = api.engine.cb
    leaves = jax.tree_util.tree_leaves(gen.params)
    if any(a.dtype == jnp.float32 and a.size > 1 << 20 for a in leaves):
        raise RuntimeError("the generator holds a float32 weight")
    if cb.pool_blocks or not cb._state_row_bytes:
        raise RuntimeError("a state-only model built a pool")
    footprint = {
        "weights_bytes": sum(int(a.nbytes) for a in leaves),
        "state_row_bytes": cb._state_row_bytes,
        "state_bytes_reserved": tf["slots"] * cb._state_row_bytes}
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
        live = LiveBytes(api.engine.cb)
        # the profiler's interval on the clients' clock (the window's)
        to_monotonic = time.monotonic() - time.perf_counter()
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
    traced = None if tracer is None or tracer.t1 is None else (
        tracer.t0 + to_monotonic, tracer.t1 + to_monotonic)
    # free the program's state before the reference takes the chip (the
    # gauge holds the batcher: its readings stay, it goes)
    slots_in_use = live.slots
    api.engine = api.generator = None
    wf.trainer.params = None
    del wf, api, live
    gc.collect()
    ctx.phases.mark("cut_stop_and_free")
    return {"records": records, "t_open": t_open, "t_close": t_close,
            "tracer": tracer, "traced": traced, "compiles": compiles,
            "setup_s": setup_s, "opened_on_count": opened_on_count,
            "engine": engine, "live_bytes": live_bytes,
            "slots_in_use": slots_in_use, "footprint": footprint,
            "memory_stats_peak_bytes": stats_peak}


def delivered_between(records, t0, t1):
    """Tokens the streams that did not fail delivered in ``[t0, t1)``:
    each is one decode step of one row."""
    return sum(n for r in records if r["outcome"] in ("ok", "cut")
               for t, n in zip(r["line_times"], r["line_tokens"])
               if t0 <= t < t1)


def check(ctx, got, red, controls=()):
    """The numbers that decide ``correct``, and what was compared; and
    the readings of ``logit_gap`` under each of ``controls`` (names of
    ``CONTROLS``; ``benchmarks/calibrate_brumby.py``).  A run none of
    whose checked answers has crossed two pass boundaries — positions
    past ``2 x prefill_segment``, where the state was handed from pass
    to pass twice and then to the slot — has not checked the mechanism
    and is not correct."""
    cfg, tf = ctx.cell.config, ctx.cell.traffic
    records = got["records"]
    sample = check_sample(records, tf["check_requests"], ctx.seed)
    last = max((len(r["result"]) for r in sample), default=0)
    crossed = last > 2 * tf["prefill_segment"] + 1
    logits = reference_brumby.reference_logits(cfg, ctx.seed, sample) \
        if crossed else None
    gap, n_tokens = reference_brumby.logit_gaps(
        cfg, ctx.seed, sample, reference=logits) if crossed \
        else (float("inf"), 0)
    numbers = {"logit_gap": gap,
               "malformed": float(malformed(records)),
               "unanswered": float(len(red["failed"])),
               "compiles_in_window": float(got["compiles"])}
    notes = {"checked_answers": len(sample), "checked_tokens": n_tokens,
             "checked_lengths": [len(r["result"]) for r in sample],
             "checked_last_position": last}
    for name in controls if crossed else ():
        notes["control_%s_logit_gap" % name] = reference_brumby.logit_gaps(
            cfg, ctx.seed, sample, reference=logits,
            **CONTROLS[name](tf))[0]
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
        if got["traced"] is not None:
            collected["traced_decode_tokens"] = delivered_between(
                records, *got["traced"])
    ctx.phases.mark("reduce")
    t_ref = time.perf_counter()
    numbers, notes = check(ctx, got, red)
    ctx.phases.mark("reference")
    # the serving state the window held: the bfloat16 weights and the
    # state of the slots in use at their fullest reading
    fp = got["footprint"]
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
        parameters=flops_brumby.parameters(cfg),
        state_row_bytes=fp["state_row_bytes"],
        state_bytes_reserved=fp["state_bytes_reserved"],
        state_slots_in_use_max=max(got["slots_in_use"], default=0),
        traced_decode_tokens=collected.get("traced_decode_tokens"),
        memory_stats_peak_bytes=got["memory_stats_peak_bytes"],
        tick={k: engine.get(k) for k in (
            "ticks_total", "p50_tick_ms", "p50_tick_wait_ms",
            "p50_tick_host_ms", "p50_tick_fetch_ms", "p50_tick_admit_ms",
            "p50_tick_state_rows", "p50_tick_state_bytes",
            "tick_rows_mean", "state_slots_in_use", "state_bytes_in_use",
            "prefill_segments_total", "prefill_ms_per_tok")})
    return {
        "end_to_end": {"out_tokens_per_s": red["out_tokens_per_s"],
                       "setup_s": got["setup_s"]},
        "attempted": len(red["sent"]),
        "failed": sum(1 for r in red["sent"]
                      if r["outcome"] not in ("ok", "cut")),
        "memory_peak_bytes": live, "numbers": numbers, "notes": notes,
        "collected": collected,
    }
