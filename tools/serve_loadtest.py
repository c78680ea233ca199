#!/usr/bin/env python
"""Chaos load-test harness for the serving survival layer.

Drives a real ``RESTfulAPI`` + ``ContinuousEngine`` (tiny untrained
transformer, CPU-friendly) with hundreds of concurrent streaming
clients under deliberately hostile conditions —

* a configurable fraction DISCONNECTS mid-stream (RST via SO_LINGER,
  the rude way real phones vanish),
* a fraction are SLOWLORIS readers (accept the stream, read a line,
  then crawl),
* the engine tick raises INJECTED FAULTS at a configurable rate
  (the fault-recovery path must evict, reset the pool, keep serving),
* an overload burst pushes queue waits past the SLO so the closed-loop
  shedder must open (503 + Retry-After) and close again,

then audits the wreckage: zero leaked slots, zero leaked paged-KV
blocks, zero stuck client threads, shed-open AND shed-close observed,
and the engine still serves fresh requests afterwards.  Exit code 0
iff every gate passes; ``--json`` writes the full report and
``--flight-dump`` leaves a flight-recorder crashdump for CI artifacts.

    python tools/serve_loadtest.py --clients 200 --disconnect 0.25 \
        --slowloris 0.1 --fault-rate 0.02 --slots 4 --paged-block 4 \
        --slo-ms 250 --json report.json --flight-dump chaos-dump

Scaled-down flavors run inside tier-1 (`tests/test_lifecycle.py`); the
CI `serve-chaos` job runs this CLI with a few hundred clients, plus a
QUANTIZED leg (``--weights int8 --cache-dtype int8``) that drives the
same storm through the int8 weight matmuls and the fused quantized-
pool paged decode kernel.  The report's ``storm_ms_per_tok``
(completed-request token throughput under the storm — not admission
p50) is what the ``--weights {f32,bf16,int8,w4a8}`` legs compare; on
silicon it carries the pre-registered >= 1.5x int8-vs-bf16 target
(docs/perf.md "Quantized serving").

**Fleet chaos mode** (`--fleet N`): spawn N replica subprocesses, put a
`services.router.FleetRouter` in front, storm the ROUTER with streaming
clients, then SIGKILL one replica and SIGTERM-drain another mid-storm.
Gates: every non-shed request completes with the byte-exact full
result (mid-stream failover splices are invisible), the router marks
the killed replica down within one health-check interval, the drained
replica exits 0, and `leak_check()` is clean on every survivor::

    python tools/serve_loadtest.py --fleet 3 --clients 150 \
        --slots 4 --paged-block 4 --pool-tokens 512 \
        --json fleet-report.json --flight-dump fleet-dump

(`--replica` is the internal subprocess entry the fleet mode spawns;
it serves one engine replica on an OS-assigned port — announced via a
`REPLICA_READY port=...` stdout line — and drains on SIGTERM.)
"""

import argparse
import http.client
import json
import os
import random
import signal
import socket
import struct
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from tools import chaos_common as cc   # noqa: E402 — path set above


def build_api(slots=4, paged_block=0, pool_tokens=None, slo_ms=0,
              deadline_ms=0, max_len=24, vocab=11, seed=7,
              generator=None, weights=None, cache_dtype=None,
              prefill_segment=0):
    """A serving endpoint around a tiny UNTRAINED transformer (the
    harness tests the lifecycle, not the language model).  Config
    knobs are set process-globally (root.common.serve) exactly as an
    operator would.  ``weights``: None (f32) / "bf16" / "int8" /
    "w4a8" — the serving weight scheme (``--weights``); the quantized
    legs prove the lifecycle machinery over the quantized decode path
    (payload-in-dot matmuls, QuantCache pools with
    ``cache_dtype="int8"``)."""
    from veles_tpu import prng
    from veles_tpu.config import root
    from veles_tpu.services.restful import RESTfulAPI

    root.common.serve.slo_queue_wait_ms = float(slo_ms)
    root.common.serve.default_deadline_ms = float(deadline_ms)
    if generator is None:
        import numpy as np

        from veles_tpu.loader.fullbatch import FullBatchLoader
        from veles_tpu.models import zoo
        from veles_tpu.models.generate import LMGenerator
        from veles_tpu.models.standard_workflow import StandardWorkflow

        prng.seed_all(seed)
        toks = np.random.RandomState(seed).randint(
            0, vocab, (8, max_len)).astype(np.int32)
        wf = StandardWorkflow(
            layers=zoo.transformer_lm(vocab_size=vocab, d_model=16,
                                      n_heads=2, n_layers=1,
                                      dropout=0.0),
            loader=FullBatchLoader(None, data=toks, labels=toks,
                                   minibatch_size=4,
                                   class_lengths=[0, 4, 4]),
            loss="lm", decision_config={"max_epochs": 1},
            name="chaos-serve")
        wf.initialize()
        generator = LMGenerator(
            wf.trainer, max_len=max_len,
            weights=(None if weights in (None, "", "f32")
                     else str(weights)),
            cache_dtype=cache_dtype)
    api = RESTfulAPI(lambda xx: xx, (generator.max_len,), port=0,
                     generator=generator, continuous_slots=slots,
                     paged_block=paged_block, pool_tokens=pool_tokens,
                     prefill_segment=prefill_segment)
    api.start()
    return api


class FaultInjector(object):
    """Wraps the engine's batcher tick with a probabilistic raise —
    the ``serve.engine_fault`` recovery path under test.  The rate is
    mutable so the recovery phase can switch chaos off."""

    def __init__(self, engine, rate, seed=0):
        self.rate = float(rate)
        self.count = 0
        self._rng = random.Random(seed)
        self._orig = engine.cb.tick
        # instance attribute shadows the bound method; the engine loop
        # resolves self.cb.tick per call, so this takes effect at the
        # next loop iteration
        engine.cb.tick = self._tick

    def _tick(self):
        if self.rate > 0 and self._rng.random() < self.rate:
            self.count += 1
            raise RuntimeError("injected chaos fault #%d" % self.count)
        return self._orig()


def _rst_close(sock):
    """Close with RST (SO_LINGER 0): the peer's next write fails
    immediately instead of draining into a dead buffer — how the
    harness makes 'client vanished' deterministic."""
    try:
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                        struct.pack("ii", 1, 0))
    except OSError:
        pass
    sock.close()


def _client(api, prompt, max_new, behavior, tally, lock,
            slow_delay=0.4, deadline_ms=None, traces=None):
    """One load-test client.  behavior: 'normal' | 'disconnect' |
    'slowloris' | 'buffered'.  ``traces``: ok requests append their
    trace id (the trace-completeness gate's input)."""
    opts = {"max_new": max_new, "stream": behavior != "buffered"}
    if deadline_ms:
        opts["deadline_ms"] = deadline_ms
    body = json.dumps({"input": prompt, "generate": opts})
    outcome, tid = "error", None
    try:
        conn = http.client.HTTPConnection(api.host, api.port,
                                          timeout=120)
        conn.request("POST", api.path, body,
                     {"Content-Type": "application/json"})
        # grab the socket NOW: http.client detaches conn.sock (sets it
        # to None) when the response body is EOF-delimited, and the
        # disconnect behavior needs the raw fd to send a RST
        raw_sock = conn.sock
        resp = conn.getresponse()
        if resp.status == 503:
            resp.read()
            outcome = "shed"
        elif resp.status == 504:
            resp.read()
            outcome = "deadline"
        elif resp.status != 200:
            resp.read()
            outcome = "http_%d" % resp.status
        elif behavior == "buffered":
            tid = json.loads(resp.read()).get("trace")
            outcome = "ok"
        else:
            lines, done = 0, False
            while True:
                if behavior == "disconnect" and lines >= 1:
                    _rst_close(raw_sock)
                    outcome = "disconnected"
                    return
                if behavior == "slowloris" and lines >= 1:
                    time.sleep(slow_delay)
                raw = resp.fp.readline()
                if not raw:
                    break
                lines += 1
                msg = json.loads(raw)
                if msg.get("done"):
                    done = True
                    tid = msg.get("trace")
                    break
                if "error" in msg:
                    outcome = "stream_error"
                    return
            outcome = "ok" if done else "truncated"
        conn.close()
    except Exception:  # noqa: BLE001 — chaos clients absorb anything
        outcome = "error"
    finally:
        with lock:
            tally[outcome] = tally.get(outcome, 0) + 1
            if outcome == "ok" and traces is not None and tid:
                traces.append(tid)


def _wait_idle(engine, timeout=120.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        m = engine.metrics()
        if m["queued"] == 0 and m["in_flight"] == 0:
            return True
        time.sleep(0.05)
    return False


def run(clients=200, disconnect=0.25, slowloris=0.10, buffered=0.15,
        fault_rate=0.02, slots=4, paged_block=0, pool_tokens=None,
        max_new=8, prompt_len=5, slo_ms=250, deadline_ms=0,
        slow_delay=0.4, seed=7, api=None, flight_dump=None,
        weights=None, cache_dtype=None, ramp_s=0.0):
    """Run the chaos scenario; returns the report dict (see gates()).
    Pass ``api`` to reuse a prebuilt endpoint (the tier-1 tests do,
    to share one compiled model across tests).  ``weights`` picks the
    serving weight scheme (f32/bf16/int8/w4a8) for the endpoint this
    harness builds.  ``ramp_s`` spreads client arrivals over that many
    seconds instead of one instantaneous burst: the shed valve opens
    on a MEASURED queue-wait breach one engine-loop update after the
    backlog forms, so when every client submits in the same
    millisecond (small storms on fast hosts) there is nobody left to
    reject — a ramp keeps arrivals flowing past the opening."""
    own_api = api is None
    if own_api:
        # the storm itself runs WITHOUT a default deadline (deadlines
        # at ~the SLO cull the queue before the shed valve can ever
        # open); deadline_ms drives the separate bounded phase below
        api = build_api(slots=slots, paged_block=paged_block,
                        pool_tokens=pool_tokens, slo_ms=slo_ms,
                        deadline_ms=0, seed=seed, weights=weights,
                        cache_dtype=cache_dtype)
    eng = api.engine
    rng = random.Random(seed)
    prompt = [int(1 + i % 7) for i in range(prompt_len)]
    report = {"clients": clients, "tally": {}, "phases": {},
              "weights": weights or "f32"}
    try:
        # ---- warmup: compile every shape OUTSIDE the measured storm
        # (and outside any default deadline — first-dispatch compiles
        # take seconds, and a deadline-cancelled warmup would abort
        # the run before the storm starts)
        t0 = time.monotonic()
        prev_deadline = eng._default_deadline_ms
        eng._default_deadline_ms = 0.0
        eng.wait(eng.submit_async(prompt, max_new))
        eng._default_deadline_ms = prev_deadline
        eng.reset_metrics()
        report["phases"]["warmup_s"] = round(time.monotonic() - t0, 2)

        baseline_threads = set(threading.enumerate())
        chaos = FaultInjector(eng, fault_rate, seed=seed)

        # ---- chaos storm: every behavior at once
        tally, lock = {}, threading.Lock()
        behaviors = []
        for _ in range(clients):
            r = rng.random()
            if r < disconnect:
                behaviors.append("disconnect")
            elif r < disconnect + slowloris:
                behaviors.append("slowloris")
            elif r < disconnect + slowloris + buffered:
                behaviors.append("buffered")
            else:
                behaviors.append("normal")
        t0 = time.monotonic()
        traces = []
        threads = [threading.Thread(
            target=_client,
            args=(api, prompt, max_new, b, tally, lock),
            kwargs={"slow_delay": slow_delay, "traces": traces},
            daemon=True)
            for b in behaviors]
        for th in threads:
            th.start()
            if ramp_s > 0:
                time.sleep(ramp_s / max(1, clients))
        for th in threads:
            th.join(timeout=300)
        stuck_clients = sum(1 for th in threads if th.is_alive())
        storm_s = time.monotonic() - t0
        report["phases"]["storm_s"] = round(storm_s, 2)
        report["tally"] = tally
        report["stuck_client_threads"] = stuck_clients
        # storm-phase ms/tok off COMPLETED requests (ok = fully
        # decoded + delivered): the token throughput the pool actually
        # sustained under the storm, not the admission p50 — the
        # number the quantized-weights legs compare (the pre-
        # registered >= 1.5x int8-vs-bf16 target reads this on
        # silicon; shed/deadline culls don't count, they decoded
        # nothing)
        done_toks = tally.get("ok", 0) * max_new
        report["storm_completed_tokens"] = done_toks
        report["storm_ms_per_tok"] = (round(storm_s * 1e3 / done_toks,
                                            4) if done_toks else None)

        # ---- trace completeness: every ok request reconstructs a
        # gapless timeline from the replica's own span store (the
        # replica is the edge here, so it minted and terminated each
        # trace; docs/services.md "Request tracing")
        from veles_tpu.telemetry import tracing
        time.sleep(0.2)      # let the last handlers' terminal spans land
        tfails, n_gapless, sample_spans = [], 0, None
        for tid in traces:
            try:
                status, payload = cc.http_json(
                    api.host, api.port, api.path + "/trace/" + tid)
            except Exception as e:  # noqa: BLE001 — the audit itself
                tfails.append("trace %s: fetch failed (%r)"
                              % (tid, e))
                continue
            spans = payload.get("spans") or []
            verdict = tracing.validate(spans)
            if status != 200 or not verdict["ok"]:
                tfails.append("trace %s: HTTP %d: %s"
                              % (tid, status,
                                 "; ".join(verdict["problems"])))
                continue
            n_gapless += 1
            if sample_spans is None:
                sample_spans = (tid, spans)
        report["trace_ids"] = len(traces)
        report["trace_gapless"] = n_gapless
        report["trace_fails"] = tfails[:20]
        if sample_spans is not None:
            report["trace_sample"] = sample_spans[0]
            report["trace_sample_timeline"] = tracing.render_timeline(
                sample_spans[1], title="trace %s" % sample_spans[0])

        # ---- recovery: chaos off, drain, the valve must close and
        # fresh requests must succeed
        chaos.rate = 0.0
        report["injected_faults"] = chaos.count
        drained = _wait_idle(eng)
        t0 = time.monotonic()
        recovered = 0
        for _ in range(3):
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                try:
                    out = eng.wait(eng.submit_async(prompt, max_new))
                    assert len(out) == prompt_len + max_new
                    recovered += 1
                    break
                except Exception:  # noqa: BLE001 — shed while closing
                    time.sleep(0.2)
        report["phases"]["recovery_s"] = round(time.monotonic() - t0, 2)
        report["drained"] = drained
        report["recovered_requests"] = recovered

        # ---- audits
        _wait_idle(eng)
        metrics = eng.metrics()
        report["metrics"] = metrics
        # per-phase latency decomposition (docs/services.md "Request
        # tracing"): where a completed request's time actually went —
        # the same queue/prefill/decode split the router rolls up
        # fleet-wide on /metrics
        report["phase_ms"] = {
            phase: {"p50": metrics.get("p50_" + key),
                    "p99": metrics.get("p99_" + key)}
            for phase, key in (("queue", "queue_wait_ms"),
                               ("prefill", "prefill_ms"),
                               ("decode", "pure_decode_ms"))}
        report["leaks"] = eng.leak_check()
        report["shed_cycle"] = bool(
            metrics["shed_total"] > 0
            and metrics["shed_state"] in ("closed", "disabled"))
        # ---- bounded phase (only with --deadline-ms): re-overload
        # with a default deadline ~= the SLO, which culls any request
        # that could not be admitted in time — completed requests'
        # p99 queue wait must then stay under the SLO (the ISSUE
        # acceptance criterion; the raw storm's p99 includes the
        # pre-shed-open backlog, which only deadlines can bound)
        if slo_ms > 0 and deadline_ms:
            # admission deadline = queue-wait budget (80% of
            # --deadline-ms, margin for estimate drift) + the MEASURED
            # decode estimate: the engine's predictive check then
            # refuses any request whose queue wait would overrun the
            # budget, so completed waits stay under the SLO.  History
            # is kept (the estimate feeds off it); the phase's own
            # percentiles come from the finish_ts slice below.
            # short requests: the phase measures QUEUE wait, so decode
            # must fit the budget comfortably or wave-1 completions
            # get culled mid-decode on a slow box and the sample dries
            bounded_new = max(2, max_new // 4)
            # warm the phase's shape BEFORE arming the deadline — a
            # fresh prefill-bucket compile mid-phase would stall past
            # every deadline and dry the completion sample
            eng.wait(eng.submit_async(prompt, bounded_new))
            est_ms = metrics["p50_ms_per_tok"] * bounded_new
            eng._default_deadline_ms = 0.8 * float(deadline_ms) + est_ms
            t_phase = time.monotonic()
            tally2, lock2 = {}, threading.Lock()
            burst = [threading.Thread(
                target=_client,
                args=(api, prompt, bounded_new, "buffered", tally2,
                      lock2),
                daemon=True) for _ in range(max(8, clients // 2))]
            for th in burst:
                th.start()
            for th in burst:
                th.join(timeout=300)
            _wait_idle(eng)
            eng._default_deadline_ms = 0.0
            waits = sorted(h["queue_wait_ms"]
                           for h in list(eng._history)
                           if h["finish_ts"] >= t_phase)
            p99 = (waits[min(len(waits) - 1,
                             int(0.99 * len(waits)))]
                   if waits else None)
            report["bounded_phase"] = {
                "tally": tally2,
                "completed": len(waits),
                "deadline_ms_effective": round(
                    0.8 * float(deadline_ms) + est_ms, 2),
                "p99_queue_wait_ms": (round(p99, 3)
                                      if p99 is not None else None)}
            report["p99_queue_wait_under_slo"] = bool(
                waits and p99 <= float(slo_ms))
            report["leaks"] = eng.leak_check()   # re-audit after it
        else:
            report["p99_queue_wait_under_slo"] = bool(
                slo_ms <= 0
                or metrics["p99_queue_wait_ms"] <= float(slo_ms))
        # server-side threads (per-connection HTTP workers, engine)
        # get a grace window to exit before counting as leaked
        deadline = time.monotonic() + 10
        leftover = []
        while time.monotonic() < deadline:
            leftover = [th.name for th in threading.enumerate()
                        if th not in baseline_threads and th.is_alive()
                        and th not in threads]
            if not leftover:
                break
            time.sleep(0.2)
        report["new_threads"] = leftover
        if flight_dump:
            from veles_tpu.telemetry import flight
            report["flight_dump"] = flight.dump(flight_dump,
                                                reason="loadtest")
    finally:
        if own_api:
            api.stop()
    return report


def gates(report, expect_shed=True, require_slo=False):
    """The pass/fail verdicts the CI job enforces.  Returns a list of
    failure strings (empty = pass).  ``require_slo`` additionally
    gates on completed requests' p99 queue wait staying under the
    SLO — only meaningful with a deadline configured (``--deadline-ms``
    about equal to the SLO), which culls the backlog that piles up
    before the shed valve opens; without one, those early-queued
    requests legitimately wait past the SLO and raw p99 shows it."""
    fails = []
    if require_slo and not report.get("p99_queue_wait_under_slo", True):
        bp = report.get("bounded_phase", {})
        fails.append(
            "admitted p99 queue wait breached the SLO (bounded phase "
            "p99=%s ms over %d completed)"
            % (bp.get("p99_queue_wait_ms"), bp.get("completed", 0)))
    leaks = report.get("leaks", {})
    cc.leak_gate(leaks, fails)
    if not leaks.get("engine_thread_alive", False):
        fails.append("engine thread died")
    if report.get("stuck_client_threads"):
        fails.append("stuck client threads: %d"
                     % report["stuck_client_threads"])
    if report.get("new_threads"):
        fails.append("leaked server-side threads: %r"
                     % report["new_threads"])
    if not report.get("drained"):
        fails.append("engine never drained to idle")
    if report.get("recovered_requests", 0) < 3:
        fails.append("engine not serving after chaos (%d/3 fresh "
                     "requests ok)" % report.get("recovered_requests", 0))
    if expect_shed and not report.get("shed_cycle"):
        fails.append("no shed+recover cycle (shed_total=%r, state=%r)"
                     % (report.get("metrics", {}).get("shed_total"),
                        report.get("metrics", {}).get("shed_state")))
    # trace completeness: every ok-accounted storm request must have
    # yielded a trace id on its done line AND reconstruct a gapless
    # timeline from the replica span store
    fails.extend(report.get("trace_fails", []))
    n_ids = report.get("trace_ids", 0)
    n_ok = report.get("tally", {}).get("ok", 0)
    if n_ids != n_ok:
        fails.append("trace ids captured (%d) != ok requests (%d)"
                     % (n_ids, n_ok))
    if n_ids and report.get("trace_gapless", 0) != n_ids:
        fails.append("only %d/%d traces reconstruct gapless"
                     % (report.get("trace_gapless", 0), n_ids))
    if not n_ids:
        fails.append("storm captured no trace ids")
    return fails


# ------------------------------------------------------- mixed-prompt mode
def _gap_stream_client(api, prompt, max_new, gaps, tally, lock):
    """One streaming client that records the wall gap between
    consecutive token lines — the client-observed inter-chunk decode
    gap the segmented-prefill gate bounds."""
    body = json.dumps({"input": prompt,
                       "generate": {"max_new": max_new,
                                    "stream": True}})
    outcome = "error"
    try:
        conn = http.client.HTTPConnection(api.host, api.port,
                                          timeout=300)
        conn.request("POST", api.path, body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            resp.read()
            outcome = "http_%d" % resp.status
            return
        last = None
        done = False
        while True:
            raw = resp.fp.readline()
            if not raw:
                break
            msg = json.loads(raw)
            if "tokens" in msg:
                now = time.monotonic()
                if last is not None:
                    with lock:
                        gaps.append((now - last) * 1e3)
                last = now
            if msg.get("done"):
                done = True
                break
            if "error" in msg:
                outcome = "stream_error"
                return
        outcome = "ok" if done else "truncated"
        conn.close()
    except Exception:  # noqa: BLE001 — chaos clients absorb anything
        outcome = "error"
    finally:
        with lock:
            tally[outcome] = tally.get(outcome, 0) + 1


def _mixed_generator(max_len, seed=7, vocab=11, d_model=64,
                     n_layers=2):
    """A BEEFIER tiny model for the stall gate: the whole point is
    that a long prompt's one-pass prefill visibly stalls decode
    ticks, so the prefill must cost real milliseconds — the default
    d=16 single-layer harness model prefills 100 tokens in ~6 ms,
    under scheduler noise."""
    import numpy as np

    from veles_tpu import prng
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.models import zoo
    from veles_tpu.models.generate import LMGenerator
    from veles_tpu.models.standard_workflow import StandardWorkflow

    prng.seed_all(seed)
    toks = np.random.RandomState(seed).randint(
        0, vocab, (8, 16)).astype(np.int32)
    wf = StandardWorkflow(
        layers=zoo.transformer_lm(vocab_size=vocab, d_model=d_model,
                                  n_heads=max(2, d_model // 32),
                                  n_layers=n_layers, dropout=0.0,
                                  pos="rope"),
        loader=FullBatchLoader(None, data=toks, labels=toks,
                               minibatch_size=4,
                               class_lengths=[0, 4, 4]),
        loss="lm", decision_config={"max_epochs": 1},
        name="chaos-serve-mixed")
    wf.initialize()
    return LMGenerator(wf.trainer, max_len=max_len)


def _run_mixed_once(prefill_segment, streamers=6, stream_new=48,
                    long_clients=6, long_len=256, long_new=4,
                    short_len=5, slots=4, seed=7, generator=None):
    """One mixed long/short storm against a fresh endpoint with the
    given segmentation; returns the report half (engine decode-stall
    percentiles + client-observed inter-chunk gaps)."""
    api = build_api(slots=slots, slo_ms=0, seed=seed,
                    max_len=long_len + long_new + stream_new,
                    generator=generator,
                    prefill_segment=prefill_segment)
    eng = api.engine
    short = [int(1 + i % 7) for i in range(short_len)]
    longp = [int(1 + i % 7) for i in range(long_len)]
    try:
        # warm every shape OUTSIDE the measurement (prefill buckets,
        # decode scan)
        eng.wait(eng.submit_async(short, stream_new))
        eng.wait(eng.submit_async(longp, long_new))
        eng.reset_metrics()
        gaps, tally, lock = [], {}, threading.Lock()
        threads = [threading.Thread(
            target=_gap_stream_client,
            args=(api, short, stream_new, gaps, tally, lock),
            daemon=True) for _ in range(streamers)]
        for th in threads:
            th.start()
        # long-prompt admissions land WHILE the short streams decode —
        # the head-of-line stall under test
        time.sleep(0.05)
        handles = []
        for _ in range(long_clients):
            handles.append(eng.submit_async(longp, long_new))
            time.sleep(0.02)
        for h in handles:
            eng.wait(h)
        for th in threads:
            th.join(timeout=300)
        m = eng.metrics()

        def pct(vals, q):
            if not vals:
                return None
            vals = sorted(vals)
            return round(vals[min(len(vals) - 1,
                                  int(q / 100.0 * len(vals)))], 3)

        # phase-attribution audit: every completed request's
        # prefill/decode split must partition its admitted→finished
        # span exactly (non-overlapping by construction — a drifted
        # decode-start stamp would show up as residual here)
        hist = [h for h in list(eng._history) if "prefill_ms" in h]
        attr = {
            "n": len(hist),
            "negative": sum(1 for h in hist
                            if h["prefill_ms"] < 0
                            or h["pure_decode_ms"] < 0),
            "max_residual_ms": (round(max(
                abs(h["prefill_ms"] + h["pure_decode_ms"]
                    - h["decode_ms"]) for h in hist), 6)
                if hist else None)}
        return {"prefill_segment": prefill_segment,
                "tally": tally,
                "stuck_streamers": sum(1 for th in threads
                                       if th.is_alive()),
                "p50_decode_stall_ms": m["p50_decode_stall_ms"],
                "p99_decode_stall_ms": m["p99_decode_stall_ms"],
                "prefill_ms_per_tok": m["prefill_ms_per_tok"],
                "prefill_segments_total": m["prefill_segments_total"],
                "p50_prefill_ms": m.get("p50_prefill_ms"),
                "p99_prefill_ms": m.get("p99_prefill_ms"),
                "p50_pure_decode_ms": m.get("p50_pure_decode_ms"),
                "p99_pure_decode_ms": m.get("p99_pure_decode_ms"),
                "phase_attr": attr,
                "client_gap_p50_ms": pct(gaps, 50),
                "client_gap_p99_ms": pct(gaps, 99),
                "client_gaps": len(gaps),
                "leaks": eng.leak_check()}
    finally:
        api.stop()


def run_mixed(prefill_segment=16, long_len=256, stream_new=48,
              long_new=4, seed=7, **kw):
    """The segmented-prefill stall gate: the SAME mixed long/short
    storm twice — segmented vs unsegmented admission — so the bound
    and the strictly-better comparison are measured in one run on one
    box (docs/perf.md "Stall-free serving").  One shared generator:
    both runs decode the same weights through the same compiled
    executables, so the ONLY difference is the admission policy."""
    gen = _mixed_generator(long_len + long_new + stream_new,
                           seed=seed)
    kw.update(long_len=long_len, stream_new=stream_new,
              long_new=long_new, seed=seed, generator=gen)
    report = {"segmented": _run_mixed_once(prefill_segment, **kw),
              "unsegmented": _run_mixed_once(0, **kw),
              "prefill_segment": prefill_segment}
    return report


def _bucket(n):
    return 1 << max(0, int(n) - 1).bit_length()


def mixed_gates(report):
    """Pass/fail for the mixed-prompt leg: the segmented run's p99
    inter-dispatch decode gap must be (a) bounded by the per-tick
    prefill budget — budget-bucket tokens at the run's own measured
    prefill rate, plus the run's baseline cadence and scheduler
    slack — and (b) STRICTLY better than the unsegmented baseline
    measured in the same run.  Plus the usual hygiene."""
    fails = []
    seg = report.get("segmented") or {}
    unseg = report.get("unsegmented") or {}
    for name, half in (("segmented", seg), ("unsegmented", unseg)):
        tally = half.get("tally") or {}
        bad = {k: v for k, v in tally.items() if k != "ok"}
        if bad:
            fails.append("%s run lost requests: %r" % (name, tally))
        if half.get("stuck_streamers"):
            fails.append("%s run stuck streamers: %d"
                         % (name, half["stuck_streamers"]))
        leaks = half.get("leaks") or {}
        cc.leak_gate(leaks, fails, label=name)
        # prefill-vs-decode attribution must be non-overlapping:
        # the two phases partition each request's admitted→finished
        # span, so their sum can never drift off it and neither
        # share can go negative
        attr = half.get("phase_attr") or {}
        if not attr.get("n"):
            fails.append("%s run recorded no phase attribution"
                         % name)
        else:
            if attr.get("negative"):
                fails.append("%s run: %d requests with a negative "
                             "phase share" % (name, attr["negative"]))
            resid = attr.get("max_residual_ms")
            if resid is not None and resid > 0.05:
                fails.append("%s run: prefill+decode attribution "
                             "overlaps/undershoots its span by "
                             "%.3f ms" % (name, resid))
    if not seg.get("prefill_segments_total"):
        fails.append("the segmented run never staged a prefill "
                     "segment (knob not reaching the engine?)")
    p99_seg = seg.get("p99_decode_stall_ms")
    p99_unseg = unseg.get("p99_decode_stall_ms")
    if p99_seg is None or p99_unseg is None:
        fails.append("missing decode-stall percentiles")
        return fails
    # budget-derived bound: one tick may prefill up to the budget
    # (pow2-bucketed) at the measured rate; 4x headroom for dispatch
    # overlap + 25 ms scheduler slack on a shared CI box
    budget = _bucket(report.get("prefill_segment") or 1)
    bound = (4.0 * budget * (seg.get("prefill_ms_per_tok") or 0.0)
             + 4.0 * (seg.get("p50_decode_stall_ms") or 0.0) + 25.0)
    if p99_seg > bound:
        fails.append("segmented p99 decode stall %.3f ms exceeds the "
                     "budget-derived bound %.3f ms" % (p99_seg, bound))
    if not p99_seg < p99_unseg:
        fails.append("segmented p99 decode stall %.3f ms is not "
                     "strictly better than the unsegmented baseline "
                     "%.3f ms" % (p99_seg, p99_unseg))
    return fails


# --------------------------------------------------------------- fleet mode
def replica_main(args):
    """Subprocess entry for one fleet replica: build the tiny model,
    serve it, print READY with the bound port, drain on SIGTERM (exit
    0), die honestly on SIGKILL."""
    from veles_tpu.services.restful import (announce_ready,
                                            install_sigterm_drain)
    from veles_tpu.telemetry import flight

    api = build_api(slots=args.slots, paged_block=args.paged_block,
                    pool_tokens=args.pool_tokens, slo_ms=args.slo_ms,
                    deadline_ms=0, seed=args.seed,
                    max_len=getattr(args, "max_len", 24),
                    prefill_segment=getattr(args, "prefill_segment",
                                            0))
    if getattr(args, "tick_delay_ms", 0):
        # stretch decode so the fleet storm's mid-storm SIGKILL lands
        # while streams are provably in flight (a tiny model on a fast
        # box finishes 8 tokens in microseconds otherwise)
        delay_s = float(args.tick_delay_ms) / 1e3
        orig_tick = api.engine.cb.tick

        def slow_tick():
            time.sleep(delay_s)
            return orig_tick()

        api.engine.cb.tick = slow_tick
    # leave a black box on graceful (drained) exit so the fleet
    # timeline can be merged across processes — the SIGKILLed replica
    # leaves none, which is the point.  The hook rides the drain
    # waiter: os._exit skips atexit handlers.
    install_sigterm_drain(
        api,
        on_drained=(lambda: flight.dump(args.dump_dir,
                                        reason="replica-drain"))
        if args.dump_dir else None)
    # READY handshake: the parent reads the bound port off stdout
    # (the shared handshake every fleet spawner understands —
    # tools/chaos_common.spawn_ready and the pod agent)
    announce_ready(api, force=True)
    while True:
        time.sleep(3600)


def replica_cmd(args, i, dump_dir=None):
    """The replica subprocess command line for fleet chaos — EVERY
    replica builds from the SAME seed: identical weights are what
    make greedy decode — and therefore mid-stream failover splices —
    byte-identical across the fleet."""
    cmd = [sys.executable, os.path.abspath(__file__), "--replica",
           "--slots", str(args.slots),
           "--paged-block", str(args.paged_block),
           "--slo-ms", str(args.slo_ms),
           "--seed", str(args.seed),
           "--max-len", str(getattr(args, "max_len", 24)),
           "--prefill-segment",
           str(getattr(args, "prefill_segment", 0)),
           "--tick-delay-ms",
           str(getattr(args, "tick_delay_ms", 0))]
    if args.pool_tokens:
        cmd += ["--pool-tokens", str(args.pool_tokens)]
    if dump_dir:
        cmd += ["--dump-dir", dump_dir]
    return cmd


def _spawn_replicas(n, args, dump_dir=None):
    """Start n replica subprocesses via the shared READY handshake
    (chaos_common.spawn_ready — select-bounded, startup-flake
    retried); returns [(proc, port, url)]."""
    cmds, envs = [], []
    for i in range(n):
        cmds.append(replica_cmd(args, i, dump_dir=dump_dir))
        env = dict(os.environ)
        env["VELES_TPU_PROCESS_ID"] = str(i + 1)   # distinct blackbox ids
        envs.append(env)
    return cc.spawn_ready(cmds, timeout=300.0, envs=envs,
                          log_dir=dump_dir)


def _fleet_client(router, prompt, max_new, expected, session, tally,
                  lock, errors=None):
    """One fleet storm client (shared verification core:
    chaos_common.fleet_stream_client)."""
    cc.fleet_stream_client(router.host, router.port, router.path,
                           prompt, max_new, expected, session, tally,
                           lock, errors=errors)


_http_json = cc.http_json


def _wait_replica_idle(port, timeout=120.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        try:
            _, h = _http_json("127.0.0.1", port, "/service/health")
            if h.get("queued", 0) == 0 and h.get("in_flight", 0) == 0:
                return True
        except OSError:
            return False
        time.sleep(0.05)
    return False


def run_fleet(replicas=3, clients=150, max_new=8, prompt_len=5,
              slots=4, paged_block=0, pool_tokens=None, slo_ms=250,
              kill_frac=0.15, drain_frac=0.35, seed=7,
              health_interval_ms=100, sessions=16, tick_delay_ms=20,
              flight_dump=None, args=None):
    """The fleet chaos scenario (module docstring).  The SIGKILL fires
    once ``kill_frac`` of the clients completed and the SIGTERM drain
    at ``drain_frac`` — completion-triggered, not timed, so the chaos
    provably lands MID-storm on any box speed.  Returns the report
    dict for :func:`fleet_gates`."""
    from veles_tpu.services.router import FleetRouter
    from veles_tpu.telemetry import flight

    if args is None:
        args = argparse.Namespace(
            slots=slots, paged_block=paged_block,
            pool_tokens=pool_tokens, slo_ms=slo_ms, seed=seed,
            tick_delay_ms=tick_delay_ms)
    report = {"replicas": replicas, "clients": clients, "tally": {},
              "phases": {}}
    t0 = time.monotonic()
    fleet = _spawn_replicas(replicas, args, dump_dir=flight_dump)
    report["phases"]["spawn_s"] = round(time.monotonic() - t0, 2)
    router = FleetRouter(port=0,
                         health_interval_ms=health_interval_ms)
    router.start()
    for _, _, url in fleet:
        router.register(url)
    prompt = [int(1 + i % 7) for i in range(prompt_len)]
    try:
        # ---- warmup every replica directly (compiles happen OUTSIDE
        # the storm) and capture the expected uninterrupted result —
        # all replicas are built from the same seed'd tiny model, so
        # greedy decode is identical everywhere
        t0 = time.monotonic()
        expected = None
        for _, port, _ in fleet:
            status, out = _http_json(
                "127.0.0.1", port, "/service", method="POST",
                body=json.dumps({"input": prompt,
                                 "generate": {"max_new": max_new}}),
                timeout=300)
            assert status == 200, (status, out)
            if expected is None:
                expected = out["result"][0]
            elif list(expected) != list(out["result"][0]):
                report["replica_divergence"] = True
        report["phases"]["warmup_s"] = round(time.monotonic() - t0, 2)
        report["expected_len"] = len(expected)

        # ---- storm through the router; mid-storm: SIGKILL one
        # replica, SIGTERM-drain another
        tally, lock = {}, threading.Lock()
        stream_errors = []
        threads = [threading.Thread(
            target=_fleet_client,
            args=(router, prompt, max_new, expected,
                  "sess-%d" % (i % sessions), tally, lock,
                  stream_errors),
            daemon=True) for i in range(clients)]
        t0 = time.monotonic()
        for th in threads:
            th.start()

        def completed():
            with lock:
                return sum(tally.values())

        # completion-triggered chaos: SIGKILL once kill_frac of the
        # clients finished (streams are provably still in flight),
        # SIGTERM-drain another replica at drain_frac
        kill_proc, kill_port, _ = fleet[0]
        drain_proc, drain_port, _ = fleet[1]
        deadline = time.monotonic() + 300
        cc.wait_fraction(completed, kill_frac, clients, deadline)
        kill_ts = time.monotonic()
        kill_proc.kill()                          # SIGKILL: no goodbye
        report["sigkill_replica_port"] = kill_port
        report["sigkill_at_completed"] = completed()
        cc.wait_fraction(completed, drain_frac, clients, deadline)
        drain_proc.send_signal(signal.SIGTERM)    # graceful drain
        report["sigterm_replica_port"] = drain_port
        report["sigterm_at_completed"] = completed()
        for th in threads:
            th.join(timeout=300)
        report["stuck_client_threads"] = sum(
            1 for th in threads if th.is_alive())
        report["phases"]["storm_s"] = round(time.monotonic() - t0, 2)
        report["tally"] = tally
        report["stream_errors"] = stream_errors[:20]

        # ---- failover detection latency: the first replica_down
        # flight event after the SIGKILL (request-path detection
        # usually beats the health probe; one probe interval is the
        # ceiling the acceptance criterion names)
        down_ts = None
        for ev in flight.recorder.snapshot():
            if ev["kind"] == "serve.replica_down" \
                    and ev["ts"] >= kill_ts + _MONO_TO_WALL:
                down_ts = ev["ts"]
                break
        report["failover_detect_s"] = (
            round(down_ts - (kill_ts + _MONO_TO_WALL), 3)
            if down_ts is not None else None)

        # ---- drained replica must exit 0 (stop admission → finish
        # in-flight → exit 0), SIGKILLed one must be gone
        try:
            report["sigterm_exit"] = drain_proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            report["sigterm_exit"] = None
        report["sigkill_exit"] = kill_proc.wait(timeout=30)

        # ---- survivors: idle, then leak-audited
        survivors = fleet[2:]
        leaks = {}
        for _, port, _ in survivors:
            if not _wait_replica_idle(port):
                leaks[port] = {"error": "never idled"}
                continue
            _, leaks[port] = _http_json("127.0.0.1", port,
                                        "/service/leaks")
        report["survivor_leaks"] = leaks
        report["router_metrics"] = router.metrics()
        kinds = [e["kind"] for e in flight.recorder.snapshot()]
        report["flight_kinds"] = {
            k: kinds.count(k)
            for k in ("serve.replica_up", "serve.replica_down",
                      "serve.failover", "serve.drain")}
        if flight_dump:
            report["flight_dump"] = flight.dump(flight_dump,
                                                reason="fleet-loadtest")
    finally:
        router.stop()
        for proc, _, _ in fleet:
            if proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    proc.kill()
    return report


#: shared wall/monotonic offset (chaos_common)
_MONO_TO_WALL = cc.MONO_TO_WALL


def fleet_gates(report, health_interval_ms=100):
    """Pass/fail verdicts for the fleet chaos run (CI `serve-fleet`).
    Returns failure strings (empty = pass)."""
    fails = []
    tally = report.get("tally", {})
    # exhaustive accounting (chaos_common.tally_gate): EVERY client
    # must end ok or shed — anything else is a lost/corrupt request,
    # and a missing outcome is a client that never reported
    cc.tally_gate(tally, report.get("clients", sum(tally.values())),
                  fails)
    if not tally.get("ok"):
        fails.append("no request completed (tally=%r)" % (tally,))
    if report.get("stuck_client_threads"):
        fails.append("stuck client threads: %d"
                     % report["stuck_client_threads"])
    if report.get("replica_divergence"):
        fails.append("replicas disagreed on the warmup output")
    det = report.get("failover_detect_s")
    # ceiling: one health-check interval (+1 s slack for the flight
    # ring scan and scheduler noise); request-path detection usually
    # lands far earlier
    if det is None:
        fails.append("SIGKILL never produced a serve.replica_down")
    elif det > health_interval_ms / 1e3 + 1.0:
        fails.append("failover took %.3f s (> one %.0f ms health "
                     "interval + slack)" % (det, health_interval_ms))
    if report.get("sigterm_exit") != 0:
        fails.append("SIGTERM replica exit %r != 0 (graceful drain "
                     "failed)" % (report.get("sigterm_exit"),))
    for port, leaks in report.get("survivor_leaks", {}).items():
        if leaks.get("error"):
            fails.append("survivor %s: %s" % (port, leaks["error"]))
            continue
        cc.leak_gate(leaks, fails, label="survivor %s" % port)
    counters = report.get("router_metrics", {}).get("counters", {})
    if not counters.get("failovers"):
        fails.append("router recorded no failover")
    kinds = report.get("flight_kinds", {})
    for kind in ("serve.replica_up", "serve.replica_down",
                 "serve.failover", "serve.drain"):
        if not kinds.get(kind):
            fails.append("missing flight event: %s" % kind)
    return fails


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="chaos load test for the serving survival layer")
    ap.add_argument("--clients", type=int, default=200)
    ap.add_argument("--disconnect", type=float, default=0.25,
                    help="fraction of clients that RST mid-stream")
    ap.add_argument("--slowloris", type=float, default=0.10)
    ap.add_argument("--buffered", type=float, default=0.15)
    ap.add_argument("--fault-rate", type=float, default=0.02,
                    help="probability an engine tick raises")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--paged-block", type=int, default=0)
    ap.add_argument("--pool-tokens", type=int, default=None)
    ap.add_argument("--weights", default=None,
                    choices=["f32", "bf16", "int8", "w4a8"],
                    help="serving weight scheme for the endpoint "
                         "(default f32 = as-trained); the report's "
                         "storm_ms_per_tok compares schemes")
    ap.add_argument("--cache-dtype", default=None,
                    choices=["bfloat16", "int8"],
                    help="KV-cache dtype (int8 + --paged-block runs "
                         "the fused quantized-pool decode kernel)")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=5)
    ap.add_argument("--max-len", type=int, default=24,
                    help="model max_len for the endpoint this "
                         "harness builds (raise it for long-prompt "
                         "legs)")
    ap.add_argument("--prefill-segment", type=int, default=0,
                    help="segmented prefill admission: bound each "
                         "admission prefill pass to this many tokens "
                         "(0 = whole-prompt; docs/services.md "
                         "'Disaggregated prefill')")
    ap.add_argument("--mixed", action="store_true",
                    help="mixed long/short-prompt stall gate: run the "
                         "same storm segmented (--prefill-segment) "
                         "and unsegmented, gate the p99 decode gap "
                         "against the budget bound AND the "
                         "unsegmented baseline")
    ap.add_argument("--long-prompt-len", type=int, default=512,
                    help="(--mixed) long-prompt length; long enough "
                         "that a whole-prompt prefill outlasts the "
                         "tick in flight it is enqueued behind (the "
                         "tick is dispatched one ahead, and the CPU "
                         "backend runs the two side by side)")
    ap.add_argument("--long-clients", type=int, default=6,
                    help="(--mixed) long-prompt admissions during "
                         "the storm")
    ap.add_argument("--streamers", type=int, default=6,
                    help="(--mixed) short streaming clients whose "
                         "inter-chunk gaps are measured")
    ap.add_argument("--slo-ms", type=float, default=250.0)
    ap.add_argument("--deadline-ms", type=float, default=0.0)
    ap.add_argument("--slow-delay", type=float, default=0.4)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--no-expect-shed", action="store_true",
                    help="don't gate on a shed+recover cycle")
    ap.add_argument("--require-slo", action="store_true",
                    help="gate on completed p99 queue wait <= --slo-ms "
                         "(pair with --deadline-ms ~= --slo-ms)")
    ap.add_argument("--json", metavar="FILE",
                    help="write the full report as JSON")
    ap.add_argument("--flight-dump", metavar="DIR",
                    help="leave a flight-recorder dump (CI artifact)")
    ap.add_argument("--trace-sample", metavar="FILE",
                    help="write one reconstructed request timeline "
                         "(CI artifact; see veles-tpu-trace)")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="fleet chaos mode: N replica subprocesses "
                         "behind a FleetRouter; SIGKILL one and "
                         "SIGTERM-drain another mid-storm")
    ap.add_argument("--health-interval-ms", type=float, default=100.0,
                    help="fleet router health-probe period")
    ap.add_argument("--sessions", type=int, default=16,
                    help="distinct affinity session keys in the "
                         "fleet storm")
    ap.add_argument("--kill-frac", type=float, default=0.15,
                    help="completed-client fraction at which replica "
                         "0 is SIGKILLed")
    ap.add_argument("--drain-frac", type=float, default=0.35,
                    help="completed-client fraction at which replica "
                         "1 gets SIGTERM (graceful drain)")
    ap.add_argument("--tick-delay-ms", type=float, default=20.0,
                    help="per-tick decode delay on fleet replicas "
                         "(stretches streams so the chaos lands "
                         "mid-flight)")
    ap.add_argument("--replica", action="store_true",
                    help=argparse.SUPPRESS)   # internal subprocess entry
    ap.add_argument("--dump-dir", default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.replica:
        return replica_main(args)

    if args.mixed:
        report = run_mixed(
            prefill_segment=args.prefill_segment or 16,
            streamers=args.streamers,
            long_clients=args.long_clients,
            long_len=args.long_prompt_len,
            short_len=args.prompt_len, slots=args.slots,
            seed=args.seed)
        fails = mixed_gates(report)
        report["failures"] = fails
        # bank the gate numbers: the sentinel bands them run-over-run
        seg_p99 = (report["segmented"] or {}).get(
            "p99_decode_stall_ms")
        unseg_p99 = (report["unsegmented"] or {}).get(
            "p99_decode_stall_ms")
        cc.bank_gates(
            "serve_loadtest.mixed",
            {"serve_p99_stall_seg_ms": (seg_p99, "ms", "lower"),
             "serve_p99_stall_unseg_ms": (unseg_p99, "ms", "lower"),
             "serve_stall_seg_vs_unseg_x": (
                 round(seg_p99 / unseg_p99, 3)
                 if seg_p99 and unseg_p99 else None, "x", "lower")},
            workload="mixed-storm", gate_failures=len(fails))
        out = json.dumps(report, indent=2, default=str)
        if args.json:
            with open(args.json, "w") as f:
                f.write(out + "\n")
        print(out)
        if fails:
            print("FAIL: " + "; ".join(fails), file=sys.stderr)
            return 1
        print("PASS: segmented p99 decode stall %.3f ms vs "
              "unsegmented %.3f ms (budget %d tok)"
              % (report["segmented"]["p99_decode_stall_ms"],
                 report["unsegmented"]["p99_decode_stall_ms"],
                 args.prefill_segment or 16), file=sys.stderr)
        return 0

    if args.fleet:
        report = run_fleet(
            replicas=args.fleet, clients=args.clients,
            max_new=args.max_new, prompt_len=args.prompt_len,
            slots=args.slots, paged_block=args.paged_block,
            pool_tokens=args.pool_tokens, slo_ms=args.slo_ms,
            kill_frac=args.kill_frac,
            drain_frac=args.drain_frac, seed=args.seed,
            health_interval_ms=args.health_interval_ms,
            sessions=args.sessions,
            tick_delay_ms=args.tick_delay_ms,
            flight_dump=args.flight_dump)
        fails = fleet_gates(report,
                            health_interval_ms=args.health_interval_ms)
        report["failures"] = fails
        cc.bank_gates(
            "serve_loadtest.fleet",
            {"fleet_failover_detect_s": (
                report.get("failover_detect_s"), "s", "lower"),
             "storm_ms_per_tok": (report.get("storm_ms_per_tok"),
                                  "ms", "lower")},
            workload="fleet-%d" % args.fleet,
            gate_failures=len(fails))
        out = json.dumps(report, indent=2, default=str)
        if args.json:
            with open(args.json, "w") as f:
                f.write(out + "\n")
        print(out)
        if fails:
            print("FAIL: " + "; ".join(fails), file=sys.stderr)
            return 1
        print("PASS: fleet survived SIGKILL + SIGTERM drain — "
              "%d ok, %d shed, %d failovers, detect %.3fs"
              % (report["tally"].get("ok", 0),
                 report["tally"].get("shed", 0),
                 report["router_metrics"]["counters"]["failovers"],
                 report["failover_detect_s"]), file=sys.stderr)
        return 0

    report = run(clients=args.clients, disconnect=args.disconnect,
                 slowloris=args.slowloris, buffered=args.buffered,
                 fault_rate=args.fault_rate, slots=args.slots,
                 paged_block=args.paged_block,
                 pool_tokens=args.pool_tokens, max_new=args.max_new,
                 prompt_len=args.prompt_len, slo_ms=args.slo_ms,
                 deadline_ms=args.deadline_ms,
                 slow_delay=args.slow_delay, seed=args.seed,
                 flight_dump=args.flight_dump, weights=args.weights,
                 cache_dtype=args.cache_dtype)
    fails = gates(report, expect_shed=not args.no_expect_shed,
                  require_slo=args.require_slo)
    report["failures"] = fails
    cc.bank_gates(
        "serve_loadtest.storm",
        {"storm_ms_per_tok": (report.get("storm_ms_per_tok"), "ms",
                              "lower"),
         "p99_decode_stall_ms": (
             report.get("metrics", {}).get("p99_decode_stall_ms"),
             "ms", "lower")},
        workload=args.weights or "f32", gate_failures=len(fails))
    if args.trace_sample and report.get("trace_sample_timeline"):
        with open(args.trace_sample, "w") as f:
            f.write(report["trace_sample_timeline"] + "\n")
    out = json.dumps(report, indent=2, default=str)
    if args.json:
        with open(args.json, "w") as f:
            f.write(out + "\n")
    print(out)
    if fails:
        print("FAIL: " + "; ".join(fails), file=sys.stderr)
        return 1
    print("PASS: zero leaks, %d sheds, %d faults survived"
          % (report["metrics"]["shed_total"],
             report.get("injected_faults", 0)), file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
