"""Traffic kind ``serve_closed``: ``LMGenerator`` ->
``PagedContinuousBatcher`` -> ``RESTfulAPI`` in the run's own process,
driven over HTTP NDJSON by a closed loop of streaming clients (each
sends its next request when its last completes) that runs as a process
of its own, ``benchmarks/client.py``.

Set-up builds the model through the same workflow as training (no
training), seeds the weights, starts the server, sends one request per
prefill bucket the mix can reach, then lets the closed loop run
``warm_seconds`` so that the window opens on full slots.  The window
opens on the first delivery after set-up and closes on the first
delivery after ``--seconds`` more (``on_deliveries``), on the clock the
clients stamp their lines with; while it is open the harness reads the
pool's blocks in use.  After it closes the answers still coming are
cut (a request of some hundreds of tokens outlasts any window); then
the server is stopped, the program's state freed, and the plain
reference runs once over a sample of the requests that finished (the
longest among them) and over what the longest cut answer had said so
far: the number compared is the widest gap by which a served token's
logit lies below the reference's best."""

import gc
import json
import os
import statistics
import subprocess
import sys
import time

import numpy as np

from benchmarks import build, client, flops, harness, reference

#: seconds between two readings of the pool's blocks in use
POLL_S = 0.05

#: seconds the closed loop runs on past ``--seconds``, so that a tick
#: delivers after them and the window can close on it (``on_deliveries``)
CLOSE_GRACE_S = 1.0


def median(values):
    return statistics.median(values) if values else None


def start_server(ctx):
    """The server up on seeded weights.  Returns the workflow (it holds
    the weights), the API, and the bytes the serving state is made of:
    the weights as the generator holds them, and one pool block."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.models.generate import LMGenerator
    from veles_tpu.services.restful import RESTfulAPI
    cfg, tf = ctx.cell.config, ctx.cell.traffic
    rows = build.token_rows(cfg, 1, tf["max_len"], ctx.seed, stream=9)
    wf = build.build_workflow(cfg, rows, 1, name="bench-serve",
                              solver="adafactor")
    wf.trainer.velocity = None      # serving holds no optimizer state
    ctx.phases.mark("build_program")
    build.install_weights(wf.trainer, cfg, ctx.seed)
    jax.block_until_ready(wf.trainer.params)
    ctx.phases.mark("seeded_weights")
    cache_dtype = getattr(jnp, tf["cache_dtype"])
    gen = LMGenerator(wf.trainer, max_len=tf["max_len"],
                      cache_dtype=cache_dtype)
    api = RESTfulAPI(lambda x: x, (tf["max_len"],), port=0, generator=gen,
                     continuous_slots=tf["slots"],
                     paged_block=tf["paged_block"],
                     pool_tokens=tf["pool_tokens"])
    cb = api.engine.cb
    if cb.fused is not True:
        raise RuntimeError("the batcher did not take the fused tick")
    footprint = {
        "weights_bytes": sum(int(a.nbytes) for a in
                             jax.tree_util.tree_leaves(gen.params)),
        "block_bytes": cb.block * flops.kv_bytes_per_token(
            cfg, jnp.dtype(cache_dtype).itemsize),
        "pool_blocks": cb.pool_blocks}
    if ctx.trace:
        # the program has no span on this path yet: the benchmark's own,
        # so that an idle gap is charged to the admission prefill, the
        # decode dispatch, the rest of a tick, or the engine's loop
        harness.wrap_in_span(cb, "tick", "bench.tick")
        harness.wrap_in_span(cb, "_admit", "bench.admit_prefill")
        harness.wrap_in_span(cb, "_tick", "bench.decode_dispatch")
    api.start()
    ctx.phases.mark("server_up")
    return wf, api, footprint


class ClosedLoop:
    """``benchmarks/client.py`` as a process of its own (it never touches
    JAX, and its ``clients`` threads do not share the server's
    interpreter lock).  It starts sending at once; ``finish`` tells it
    to stop (it cuts the answers still coming), waits until the process
    has ended, and returns the records."""

    def __init__(self, api, ctx):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(client.__file__), api.host,
             str(api.port), api.path, ctx.cell.traffic_file,
             str(ctx.cell.config["vocab_size"]), str(ctx.seed)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)

    def finish(self, timeout=60.0):
        try:
            out, _ = self.proc.communicate("stop\n", timeout=timeout)
        except subprocess.TimeoutExpired:
            self.kill()
            raise RuntimeError("the load generator did not finish in "
                               "%g s" % timeout) from None
        if self.proc.returncode != 0:
            raise RuntimeError("the load generator exited with %d"
                               % self.proc.returncode)
        return [json.loads(line) for line in out.splitlines()]

    def kill(self):
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


def warm(api, cfg, tf, seed):
    """One request per prefill bucket the mix can reach, one at a time:
    compiles every prefill program, the admission and the tick."""
    rng = np.random.default_rng([int(seed), 2])
    for plen in tf["warm_prompt_lens"]:
        rec = client.stream_request(
            api.host, api.port, api.path,
            rng.integers(0, cfg["vocab_size"], plen).tolist(), 4)
        if rec["outcome"] != "ok":
            raise RuntimeError("warm-up request of %d tokens: %s"
                               % (plen, rec["outcome"]))


def window(ctx, cb):
    """Waits the window out, reading the pool's blocks in use every
    ``POLL_S``; in a traced run the profiler captures a part of it.
    Returns ``(t_open, t_close, tracer, blocks in use at each reading)``
    on the clock the clients stamp their lines with."""
    tf = ctx.cell.traffic
    tracer = harness.TraceWindow() if ctx.trace else None
    trace_from = ctx.seconds * 0.3
    trace_for = min(tf["trace_seconds"], ctx.seconds * 0.5)
    blocks = []
    t_open = time.monotonic()
    while True:
        now = time.monotonic() - t_open
        if now >= ctx.seconds:
            break
        if tracer and tracer.t0 is None and now >= trace_from:
            tracer.start()
        elif tracer and tracer.t0 is not None and tracer.t1 is None \
                and time.perf_counter() - tracer.t0 >= trace_for:
            tracer.stop()
        blocks.append(cb.pool_blocks - cb.free_blocks())
        time.sleep(min(POLL_S, max(0.0, ctx.seconds - now)))
    t_close = time.monotonic()
    if tracer and tracer.t0 is not None and tracer.t1 is None:
        tracer.stop()
    time.sleep(CLOSE_GRACE_S)
    return t_open, t_close, tracer, blocks


def on_deliveries(records, t0, t1):
    """The window's edges: the first token line at or after ``t0`` and
    the first at or after ``t1``.  Every slot's token of one tick
    arrives at once, so a window cut at arbitrary instants holds one
    tick more or fewer from run to run and the rate reads in steps of
    one tick's tokens (0.8% at 32 slots, 160 ms and 20 s: six seeds read
    201.6 five times and 200.0 once, PERF.md); opened and closed on
    deliveries it holds whole ticks, all their tokens and all their
    time.  An edge with no delivery after it stays where it was."""
    times = [t for r in records if r["outcome"] in ("ok", "cut")
             for t in r["line_times"]]
    return (min((t for t in times if t >= t0), default=t0),
            min((t for t in times if t >= t1), default=t1))


def reduce_records(records, t_open, t_close):
    """The end-to-end numbers of the window ``[t_open, t_close)`` and
    what the readers need.  A request of some hundreds of tokens
    outlasts the window, so the rate counts every token DELIVERED in it
    (by a stream that did not fail) over the window's own length, not
    the tokens of requests that happened to end in it."""
    ok = [r for r in records if r["outcome"] in ("ok", "cut")]
    failed = [r for r in records if r["outcome"] not in ("ok", "cut")]
    sent = [r for r in records if t_open <= r["sent"] < t_close]
    window_s = t_close - t_open
    by_second = [0] * max(1, int(window_s + 0.999))
    gaps, ranges, delivered = [], [], 0
    for r in ok:
        lines, plen = r["line_times"], len(r["prompt"])
        gaps.extend((b - a) * 1e3 for a, b in zip(lines, lines[1:])
                    if t_open <= b < t_close)
        if r["first"] is not None and t_open <= r["first"] < t_close:
            ranges.append((0, plen))        # its prefill ran in the window
        at = plen
        for t, n in zip(lines, r["line_tokens"]):
            if t_open <= t < t_close:
                ranges.append((at, at + n))
                delivered += n
                by_second[int(t - t_open)] += n
            at += n
    return {
        "sent": sent, "failed": failed, "gaps_ms": gaps,
        "ttft_ms": [(r["first"] - r["sent"]) * 1e3 for r in sent
                    if r["first"] is not None],
        "out_tokens_per_s": delivered / window_s,
        "window_s": window_s,
        "delivered_by_second": by_second,
        "token_ranges": ranges,
        "phases": [r["phases"] for r in records if r["phases"]
                   and t_open <= r["done"] < t_close],
    }


def check_sample(records, n, seed):
    """What the reference follows: of the finished requests the longest
    and ``n - 1`` more drawn from the seed, and of the answers cut at
    the close the one that had come furthest (prompt + tokens said so
    far), as far as it had come — a request that outlasts the window
    never finishes, and its late positions would go unread."""
    ok = [r for r in records if r["outcome"] == "ok"]
    ok.sort(key=lambda r: (r["sent"]))
    sample = []
    if ok:
        longest = max(range(len(ok)), key=lambda i: len(ok[i]["result"]))
        rng = np.random.default_rng([int(seed), 3])
        others = [i for i in rng.permutation(len(ok)) if i != longest]
        sample = [ok[longest]] + [ok[i] for i in others[:n - 1]]
    cut = [r for r in records if r["outcome"] == "cut" and r["streamed"]]
    if cut:
        far = max(cut, key=lambda r: len(r["prompt"]) + len(r["streamed"]))
        sample.append({"prompt": far["prompt"],
                       "result": far["prompt"] + far["streamed"]})
    return sample


def malformed(records):
    """Finished requests that say the wrong thing by their shape alone:
    the prompt not echoed, or not ``max_new`` tokens after it."""
    bad = 0
    for r in records:
        if r["outcome"] != "ok":
            continue
        res, p = r["result"], r["prompt"]
        if res is None or res[:len(p)] != p \
                or len(res) != len(p) + r["max_new"]:
            bad += 1
    return bad


def pad_bucket(n, cap):
    return min(1 << max(8, (n - 1).bit_length()), cap)


def logit_gap(cfg, tf, seed, sample, control=False):
    """Widest gap, over every served token of the sample, by which its
    reference logit lies below the reference's best.  ``control``: the
    tokens that the int8 reference puts first at the same positions, in
    the served tokens' place."""
    import jax.numpy as jnp
    w = reference.make_weights(cfg, seed)
    gaps_f32 = reference.make_logit_gaps(cfg, "f32")
    gaps_int8 = reference.make_logit_gaps(cfg, "int8") if control else None
    worst, n_tokens = 0.0, 0
    for r in sample:
        plen, res = len(r["prompt"]), r["result"]
        t = pad_bucket(len(res), tf["max_len"])
        toks = np.zeros((1, t), np.int32)
        toks[0, :len(res)] = res
        probe = np.zeros((t,), np.int32)
        probe[:len(res) - 1] = res[1:]
        toks, probe = jnp.asarray(toks), jnp.asarray(probe)
        if control:
            _, probe = gaps_int8(w, toks, probe)
        gap, _ = gaps_f32(w, toks, probe)
        served = np.asarray(gap)[plen - 1:len(res) - 1]
        worst = max(worst, float(served.max()))
        n_tokens += len(served)
    del w
    return worst, n_tokens


def serve(ctx):
    """Server up, warm-up, the closed loop over one window, server down,
    the program's state freed.  Returns what the window left."""
    cfg, tf = ctx.cell.config, ctx.cell.traffic
    wf, api, footprint = start_server(ctx)
    loop = None
    try:
        warm(api, cfg, tf, ctx.seed)
        ctx.phases.mark("warm_requests")
        loop = ClosedLoop(api, ctx)
        # the loop runs ``warm_seconds`` before the window, so that the
        # window opens on full slots
        time.sleep(tf["warm_seconds"])
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
            "engine": engine, "blocks_in_use": blocks,
            "footprint": footprint, "memory_stats_peak_bytes": stats_peak}


def check(ctx, got, red, control=False):
    """The numbers that decide ``correct``, and what was compared.  With
    ``control`` also the control's reading of ``logit_gap``
    (benchmarks/calibrate.py)."""
    cfg, tf = ctx.cell.config, ctx.cell.traffic
    records = got["records"]
    sample = check_sample(records, tf["check_requests"], ctx.seed)
    gap, n_tokens = logit_gap(cfg, tf, ctx.seed, sample) if sample \
        else (float("inf"), 0)
    numbers = {"logit_gap": gap,
               "malformed": float(malformed(records)),
               "unanswered": float(len(red["failed"])),
               "compiles_in_window": float(got["compiles"])}
    notes = {"checked_answers": len(sample), "checked_tokens": n_tokens,
             "checked_last_position": max(
                 (len(r["result"]) for r in sample), default=0)}
    if control:
        notes["control_int8_logit_gap"] = logit_gap(
            cfg, tf, ctx.seed, sample, control=True)[0]
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
    # the serving state the window held: the weights and the pool blocks
    # in use at their fullest reading.  Not the process's lifetime peak
    # (it can hold what building left behind), not the pool as reserved.
    fp, blocks = got["footprint"], got["blocks_in_use"]
    live = fp["weights_bytes"] + max(blocks, default=0) * fp["block_bytes"]
    notes.update(
        reference_s=time.perf_counter() - t_ref,
        requests_finished=sum(r["outcome"] == "ok" for r in records),
        requests_cut_at_the_close=sum(r["outcome"] == "cut"
                                      for r in records),
        outcomes_failed=sorted({r["outcome"] for r in red["failed"]}),
        # no metric: a window sends a few dozen requests (PERF.md)
        ttft_ms_p50=median(red["ttft_ms"]),
        queue_ms_p50=median([p["queue"] for p in red["phases"]
                             if "queue" in p]),
        delivered_by_second=red["delivered_by_second"],
        weights_bytes=fp["weights_bytes"],
        pool_bytes_reserved=fp["pool_blocks"] * fp["block_bytes"],
        pool_blocks=fp["pool_blocks"],
        pool_blocks_in_use_max=max(blocks, default=0),
        pool_blocks_in_use_at_close=blocks[-1] if blocks else 0,
        memory_stats_peak_bytes=got["memory_stats_peak_bytes"])
    return {
        "end_to_end": {"out_tokens_per_s": red["out_tokens_per_s"],
                       "setup_s": got["setup_s"]},
        "attempted": len(red["sent"]),
        "failed": sum(1 for r in red["sent"]
                      if r["outcome"] not in ("ok", "cut")),
        "memory_peak_bytes": live, "numbers": numbers, "notes": notes,
        "collected": collected,
    }
