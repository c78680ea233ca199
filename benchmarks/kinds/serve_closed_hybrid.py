"""Traffic kind ``serve_closed_hybrid``: Qwen3-Next-80B-A3B's language
model (three gated-DeltaNet layers, whose float32 state and conv tail
are fixed-size a slot, to one gated-attention layer over a paged pool;
softmax top-10 routing over 512 experts of which this chip holds 256, a
sigmoid-gated shared expert) through ``LMGenerator`` ->
``PagedContinuousBatcher`` -> ``RESTfulAPI`` with segmented prefill,
driven by the closed loop of ``benchmarks/client_closed.py`` — long
documents, mid-length answers.

The window, its edges on deliveries, the record reduction and the
sample of checked answers are ``serve_closed``'s; the loop, the wait
for the loop's ``warm_finished``-th request and the run's shape are
``serve_closed_sparse``'s; the count of tokens delivered while the
profiler ran is ``serve_closed_state``'s; the build
(``build_qwen3next``: parameters bfloat16 from the build on), the
reference (``reference_qwen3next``: the delta rule token by token, one
layer's weights at a time), the FLOP count (``flops_qwen3next``) and
the reading of BOTH kinds of serving memory in use — the pool's blocks
and the slots' state — are this kind's own."""

import gc
import time

import numpy as np

from benchmarks import (build_qwen3next, flops_qwen3next, harness,
                        reference_qwen3next)
from benchmarks.kinds.serve_closed import (
    check_sample, malformed, median, on_deliveries, reduce_records, warm,
    window)
from benchmarks.kinds.serve_closed_sparse import ClosedLoop, wait_served
from benchmarks.kinds.serve_closed_state import delivered_between

#: the controls of ``logit_gap``, each a lesser reference whose first
#: choices take the served tokens' place: every matmul operand on a
#: per-tensor int8 grid; S zeroed at every pass boundary; the conv's
#: tail dropped at every pass boundary and for every decoded token;
#: plain gated linear attention (no delta correction); the attention's
#: output gate off; rope over all 256 dims; S kept in bfloat16
CONTROLS = {
    "int8": lambda tf: dict(probe_precision="int8"),
    "state_reset": lambda tf: dict(state_reset=tf["prefill_segment"]),
    "conv_dropped": lambda tf: dict(conv_dropped=tf["prefill_segment"]),
    "no_delta": lambda tf: dict(no_delta=True),
    "gate_off": lambda tf: dict(gate_off=True),
    "rope_full": lambda tf: dict(rope_full=True),
    "state_bf16": lambda tf: dict(state_bf16=True),
}
#: the controls the states are read under too: those whose mechanism
#: lies in the DeltaNet layers (the attention layer's gate and rotation
#: come after them, and leave the states as they are)
STATE_CONTROLS = ("int8", "state_reset", "conv_dropped", "no_delta",
                  "state_bf16")


class LiveBytes:
    """What ``serve_closed.window`` polls, ``pool_blocks -
    free_blocks()`` every 50 ms, made to read the BYTES of both kinds of
    serving memory the requests in the slots hold: the attention
    layer's blocks in use and the DeltaNet layers' state."""

    pool_blocks = 0

    def __init__(self, cb, block_bytes):
        self.cb = cb
        self.block_bytes = block_bytes
        self.readings = []          # (blocks in use, state slots)

    def free_blocks(self):
        blocks = sum(self.cb.blocks_in_use())
        slots, state = self.cb.state_in_use()
        self.readings.append((blocks, slots))
        return -(blocks * self.block_bytes + state)


def start_server(ctx):
    """The server up on seeded bfloat16 weights.  Returns the workflow
    (it holds the weights), the API, and the bytes the serving state is
    made of."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.models.generate import LMGenerator
    from veles_tpu.services.restful import RESTfulAPI
    cfg, tf = ctx.cell.config, ctx.cell.traffic
    wf = build_qwen3next.build_workflow(cfg, tf["max_len"])
    wf.trainer.velocity = None      # serving holds no optimizer state
    ctx.phases.mark("build_program")
    build_qwen3next.install_weights(wf.trainer, cfg, ctx.seed)
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
    if not cb.pool_blocks or not cb._state_row_bytes:
        raise RuntimeError("the hybrid built one kind of serving memory")
    footprint = {
        "weights_bytes": sum(int(a.nbytes) for a in leaves),
        "block_bytes": cb.block * flops_qwen3next.kv_bytes_per_token(
            cfg, jnp.dtype(cache_dtype).itemsize),
        "pool_blocks": cb.pool_blocks,
        "state_row_bytes": cb._state_row_bytes,
        "state_bytes_reserved": tf["slots"] * cb._state_row_bytes}
    api.start()
    ctx.phases.mark("server_up")
    return wf, api, footprint


def state_probe(engine, cfg, tf, seed):
    """After the window, the engine stopped: the requests it left in the
    batcher dropped (``reset_pool``), then one request of
    ``tf["state_probe"]`` = (prompt, generated) tokens through the same
    batcher on this thread, which keeps its state when it finishes.
    Returns ``{"prompt", "result", "states"}``: the state each DeltaNet
    layer's slot row held at the end, ``(S, conv tail)`` host
    arrays."""
    if engine.leak_check()["engine_thread_alive"]:
        raise RuntimeError("the engine still ticks: no probe beside it")
    cb = engine.cb
    cb.reset_pool()
    plen, new = tf["state_probe"]
    prompt = np.random.default_rng([int(seed), 4]).integers(
        0, cfg["vocab_size"], plen).tolist()
    rid = cb.submit(prompt, new)
    cb.keep_state(rid)
    while not cb.idle():
        cb.tick()
    states = [tuple(np.asarray(leaf, np.float32) for leaf in kept)
              for kept in cb.kept_state(rid)]
    return {"prompt": prompt, "result": cb.pop_result(rid),
            "states": states}


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
        live = LiveBytes(api.engine.cb, footprint["block_bytes"])
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
    probe = state_probe(api.engine, cfg, tf, ctx.seed)
    ctx.phases.mark("state_probe")
    traced = None if tracer is None or tracer.t1 is None else (
        tracer.t0 + to_monotonic, tracer.t1 + to_monotonic)
    # free the program's state before the reference takes the chip (the
    # gauge holds the batcher: its readings stay, it goes)
    readings = live.readings
    api.engine = api.generator = None
    wf.trainer.params = None
    del wf, api, live
    gc.collect()
    ctx.phases.mark("cut_stop_and_free")
    return {"records": records, "t_open": t_open, "t_close": t_close,
            "tracer": tracer, "traced": traced, "compiles": compiles,
            "setup_s": setup_s, "opened_on_count": opened_on_count,
            "engine": engine, "live_bytes": live_bytes,
            "readings": readings, "footprint": footprint, "probe": probe,
            "memory_stats_peak_bytes": stats_peak}


def check(ctx, got, red, controls=(), control_answers=None):
    """The numbers that decide ``correct``, and what was compared; and
    the readings of ``logit_gap`` under each of ``controls`` (names of
    ``CONTROLS``; ``benchmarks/calibrate_qwen3next.py``) over the
    ``control_answers`` SHORTEST of the checked answers (all where None:
    a control's widest gap over a part of the answers is at most its gap
    over all of them, and the reference's time grows with the length),
    and of ``state_gaps`` (each DeltaNet layer's; ``state_gap`` is the
    first's) under those of them in ``STATE_CONTROLS``.
    A run none of whose checked answers has passed the traffic's
    ``check_past`` has not checked the state over a long context and is
    not correct.  ``state_gap``: the probe's states (``state_probe``)
    against the reference's recurrence over the same tokens."""
    cfg, tf = ctx.cell.config, ctx.cell.traffic
    records = got["records"]
    sample = check_sample(records, tf["check_requests"], ctx.seed)
    last = max((len(r["result"]) for r in sample), default=0)
    crossed = last > tf["check_past"] + 1
    logits = reference_qwen3next.reference_logits(cfg, ctx.seed, sample) \
        if crossed else None
    gap, n_tokens = reference_qwen3next.logit_gaps(
        cfg, ctx.seed, sample, reference=logits) if crossed \
        else (float("inf"), 0)
    probe = got["probe"]
    decode_from = len(probe["prompt"]) - 1

    def states(**controls):
        precision = controls.pop("probe_precision", "f32")
        return reference_qwen3next.final_states(
            cfg, ctx.seed, probe["result"][:-1], decode_from, precision,
            **controls)

    ref_states = states()
    numbers = {"logit_gap": gap,
               "state_gap": reference_qwen3next.state_gap(
                   probe["states"], ref_states),
               "malformed": float(malformed(records)),
               "unanswered": float(len(red["failed"])),
               "compiles_in_window": float(got["compiles"])}
    notes = {"checked_answers": len(sample), "checked_tokens": n_tokens,
             "checked_lengths": [len(r["result"]) for r in sample],
             "checked_last_position": last,
             "state_probe_tokens": len(probe["result"]) - 1,
             "state_gaps_by_layer": reference_qwen3next.state_gaps(
                 probe["states"], ref_states)}
    part = sorted(range(len(sample)),
                  key=lambda i: len(sample[i]["result"]))[:control_answers]
    for name in controls if crossed else ():
        notes["control_%s_logit_gap" % name] = \
            reference_qwen3next.logit_gaps(
                cfg, ctx.seed, [sample[i] for i in part],
                reference=[logits[i] for i in part],
                **CONTROLS[name](tf))[0]
        if name in STATE_CONTROLS:
            notes["control_%s_state_gaps_by_layer" % name] = \
                reference_qwen3next.state_gaps(
                    states(**CONTROLS[name](tf)), ref_states)
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
            collected["traced_token_ranges"] = reduce_records(
                records, *got["traced"])["token_ranges"]
    ctx.phases.mark("reduce")
    t_ref = time.perf_counter()
    numbers, notes = check(ctx, got, red)
    ctx.phases.mark("reference")
    # the serving state the window held: the bfloat16 weights, and the
    # blocks and the slots' state in use at their fullest reading
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
        parameters=flops_qwen3next.parameters(cfg),
        block_bytes=fp["block_bytes"], pool_blocks=fp["pool_blocks"],
        state_row_bytes=fp["state_row_bytes"],
        state_bytes_reserved=fp["state_bytes_reserved"],
        blocks_in_use_max=max((b for b, _ in got["readings"]), default=0),
        state_slots_in_use_max=max((s for _, s in got["readings"]),
                                   default=0),
        traced_decode_tokens=collected.get("traced_decode_tokens"),
        memory_stats_peak_bytes=got["memory_stats_peak_bytes"],
        tick={k: engine.get(k) for k in (
            "ticks_total", "p50_tick_ms", "p50_tick_wait_ms",
            "p50_tick_host_ms", "p50_tick_fetch_ms", "p50_tick_admit_ms",
            "p50_tick_state_rows", "p50_tick_state_bytes",
            "p50_tick_state_launches", "admit_state_bytes",
            "admit_blocks", "p50_tick_experts_touched",
            "p50_tick_expert_pairs", "p50_tick_kv_tokens",
            "tick_rows_mean", "state_slots_in_use", "state_bytes_in_use",
            "pool_blocks_full_in_use", "prefill_segments_total",
            "prefill_ms_per_tok")})
    return {
        "end_to_end": {"out_tokens_per_s": red["out_tokens_per_s"],
                       "setup_s": got["setup_s"]},
        "attempted": len(red["sent"]),
        "failed": sum(1 for r in red["sent"]
                      if r["outcome"] not in ("ok", "cut")),
        "memory_peak_bytes": live, "numbers": numbers, "notes": notes,
        "collected": collected,
    }
