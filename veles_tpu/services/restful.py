"""RESTful serving (ref: veles/restful_api.py:54-217 + loader/restful.py).

``RESTfulAPI`` wraps a trained workflow's jitted forward function behind an
HTTP endpoint: POST JSON ``{"input": [...]}`` (nested lists or base64 —
the reference's two codecs, restful_api.py:112-217) returns
``{"result": [...]}``.  stdlib http.server in a daemon thread replaces the
reference's Twisted resource — no reactor to manage."""

import base64
import collections
import json
import math
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from veles_tpu.logger import Logger
from veles_tpu.services.lifecycle import (BoundedStream, DeadlineExceeded,
                                          DrainState, EngineUnavailable,
                                          RequestCancelled, ShedError,
                                          SloShedder)
from veles_tpu.telemetry import flight, tracing
from veles_tpu.telemetry.spans import span


def send_json(handler, code, payload, headers=()):
    """Shared JSON-response helper for the stdlib serving handlers
    (this endpoint's and the fleet router's) — ONE place for the
    Content-Type / Content-Length / extra-headers dance so the two
    surfaces cannot drift."""
    msg = json.dumps(payload, default=str).encode()
    handler.send_response(code)
    handler.send_header("Content-Type", "application/json")
    handler.send_header("Content-Length", str(len(msg)))
    for k, v in headers:
        handler.send_header(k, v)
    handler.end_headers()
    handler.wfile.write(msg)


class GenerateBatcher(Logger):
    """Serving coalescer: concurrent generate requests arriving within
    ``window`` seconds merge into ONE device call through
    ``LMGenerator.generate_batch`` (per-row sampling params keep every
    request's random draws independent of which batch it lands in — see
    generate_batch's determinism note).  Batches pad
    up to power-of-two row counts (clamped to ``max_batch``) so the
    generator compiles O(log max_batch) executables instead of one per
    observed size.
    Modern continuous-batching-lite — the reference served strictly one
    request per forward (restful_api.py:112-217)."""

    def __init__(self, generator, window=0.01, max_batch=8):
        super(GenerateBatcher, self).__init__()
        self.generator = generator
        self.window = float(window)
        self.max_batch = int(max_batch)
        self._lock = threading.Condition()
        self._pending = []                # (prompt, opts, slot)
        self._closed = False
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def submit_async(self, prompt_row, opts):
        """Enqueue one row; returns a slot for ``wait``."""
        slot = {"event": threading.Event()}
        with self._lock:
            if self._closed:
                raise EngineUnavailable("batcher is stopped")
            self._pending.append((list(prompt_row), dict(opts), slot))
            self._lock.notify()
        return slot

    @staticmethod
    def wait(slot):
        slot["event"].wait()
        if "error" in slot:
            raise slot["error"]
        return slot["out"]

    def submit(self, prompt_row, opts):
        """Blocks until the coalesced batch ran; returns the 1-D
        output."""
        return self.wait(self.submit_async(prompt_row, opts))

    def pending(self):
        """Requests waiting for a coalesced batch (the drain watcher's
        in-flight signal for this path)."""
        with self._lock:
            return len(self._pending)

    def stop(self):
        with self._lock:
            self._closed = True
            self._lock.notify()
        self._thread.join(timeout=5)

    def _loop(self):
        while True:
            with self._lock:
                while not self._pending and not self._closed:
                    self._lock.wait()
                if self._closed and not self._pending:
                    return
            time.sleep(self.window)       # collect the burst
            with self._lock:
                group = self._pending[:self.max_batch]
                del self._pending[:len(group)]
            if not group:
                continue
            prompts = [g[0] for g in group]
            opts = [g[1] for g in group]
            # pad to the next power of two with throwaway copies of row
            # 0 so compile count stays O(log max_batch); never past the
            # operator's max_batch cap (it may bound KV-cache memory)
            bucket = 1
            while bucket < len(group):
                bucket *= 2
            n_pad = min(bucket, self.max_batch) - len(group)
            # max_new=0: a pad row must never push a full-length prompt
            # past max_len and fail the group
            prompts += [prompts[0]] * n_pad
            opts += [{"max_new": 0}] * n_pad
            try:
                outs = self.generator.generate_batch(prompts, opts)
            except Exception as e:  # noqa: BLE001 — deliver per request
                for _, _, slot in group:
                    slot["error"] = e
                    slot["event"].set()
                continue
            for (_, _, slot), out in zip(group, outs):
                slot["out"] = out
                slot["event"].set()


#: ticks the engine's per-tick ring holds (``ContinuousEngine.
#: tick_records``): every tick of a process at today's 156 ms a tick on
#: the 1.3B cell (PERF.md), 27 s of ticks at 13 ms — so a median over
#: it is of the last half minute or less, whatever the tick costs
TICK_RING = 2048


class ContinuousEngine(Logger):
    """Background driver putting ``models.generate.ContinuousBatcher``
    behind the REST endpoint: one engine thread ticks the slot pool
    whenever work exists; each HTTP worker blocks on its request's
    event and wakes the moment its row leaves the pool.  Unlike the
    window coalescer, a request joins the CURRENT in-flight decode at
    the next tick — no batch boundary, no window wait.

    The engine thread is the ONLY caller of the (thread-unsafe)
    batcher: HTTP workers hand requests over through an ingress deque
    and read results back from their request record, so the device
    dispatch in ``tick()`` runs with NO lock held — admission latency
    stays flat no matter how long a fused dispatch takes (ADVICE r4:
    the previous design blocked every submit for a whole
    ticks_per_dispatch dispatch).

    Every request records queue-wait (submit→admitted to a slot,
    tick granularity) and decode time (admitted→finished), feeding
    ``metrics()`` — per-stream tokens/s with p50/p99, the serving
    plane's SLO surface (ref capability: per-slave stats in the web
    status table, ref web_status.py:113-200, applied to serving)."""

    def __init__(self, generator, slots=8, history=512, paged_block=0,
                 pool_tokens=None, prefix_cache=False, speculative_k=0,
                 ticks_per_dispatch=1, prefill_segment=None,
                 prefill_tick_budget=None):
        super(ContinuousEngine, self).__init__()
        from veles_tpu.models.generate import (ContinuousBatcher,
                                               PagedContinuousBatcher,
                                               parse_paged_block)
        from veles_tpu.config import root as _root
        serve_cfg = _root.common.serve
        #: segmented prefill admission (docs/services.md
        #: "Disaggregated prefill"): root.common.serve.prefill_segment
        #: > 0 bounds how many prompt tokens one admission may prefill
        #: per device pass — long prompts stage and interleave with
        #: decode ticks, so in-flight streams keep their cadence.
        #: None = the config knob; explicit 0 turns it off.
        if prefill_segment is None:
            prefill_segment = int(serve_cfg.get("prefill_segment", 0)
                                  or 0)
        if prefill_tick_budget is None:
            prefill_tick_budget = int(
                serve_cfg.get("prefill_tick_budget", 0) or 0)
        #: paged_block > 0: block-table KV pool — slot memory scales
        #: with the pool_tokens budget, and admission backpressures on
        #: pool exhaustion as well as slot exhaustion; "auto"/-1 keeps
        #: paged KV but lets the pool block resolve through config >
        #: the kernel autotuner > default (docs/perf.md "Autotuning").
        #: prefix_cache: concurrent requests sharing a prompt prefix
        #: share its KV blocks (copy-on-write — the system-prompt case)
        #: ticks_per_dispatch: fuse K engine ticks into one device
        #: dispatch — where the per-dispatch cost dominates per-token
        #: cost, K ~ 8-32 multiplies serving throughput (admission +
        #: streaming then happen at K-token boundaries; token streams
        #: are unchanged)
        paged, block = parse_paged_block(paged_block)
        self.cb = (PagedContinuousBatcher(
                       generator, slots=slots, block=block,
                       pool_tokens=pool_tokens,
                       prefix_cache=prefix_cache,
                       speculative_k=speculative_k,
                       ticks_per_dispatch=ticks_per_dispatch,
                       prefill_segment=prefill_segment,
                       prefill_tick_budget=prefill_tick_budget)
                   if paged else
                   ContinuousBatcher(
                       generator, slots=slots,
                       speculative_k=speculative_k,
                       ticks_per_dispatch=ticks_per_dispatch,
                       prefill_segment=prefill_segment,
                       prefill_tick_budget=prefill_tick_budget))
        #: the batcher reports every staged prefill pass here (engine
        #: thread — the sole tick caller): serve.prefill flight events,
        #: the measured prefill rate the predictive deadline check
        #: uses, and the prefill gauges all feed off it
        self.cb.prefill_observer = self._note_prefill
        #: guards _ingress / _records / _history / counters — NEVER
        #: held across a device dispatch
        self._lock = threading.Lock()
        self._ingress = collections.deque()
        self._records = {}                 # rid -> record (cb-submitted)
        self._history = collections.deque(maxlen=int(history))
        self._served = 0
        #: free-KV-block gauge, snapshotted by the ENGINE thread after
        #: each tick (metrics() must not touch the thread-unsafe
        #: batcher); None on the dense batcher
        self._kv_gauge = (self.cb.free_blocks()
                          if hasattr(self.cb, "free_blocks") else None)
        #: the paged pool's blocks in use, (whole-context group, window
        #: rings), snapshotted with it
        self._blocks_gauge = (self.cb.blocks_in_use()
                              if hasattr(self.cb, "blocks_in_use")
                              else None)
        #: fixed-size state in use, ``(slots, bytes)``: a state layer's
        #: gauge where the blocks above are a paged layer's (both read
        #: for a model that has both kinds; (0, 0) without state layers)
        self._state_gauge = self.cb.state_in_use()
        #: prefix-cache gauge: (registered shared blocks, total owner
        #: refs) — hit rate is visible as refs > blocks
        self._prefix_gauge = ((0, 0) if getattr(self.cb, "prefix_cache",
                                                False) else None)
        self._start_ts = time.monotonic()
        #: queue-wait SLO (root.common.serve.slo_queue_wait_ms): a
        #: completed request that waited longer records a flight-recorder
        #: breach event, so serving SLO violations land in the same
        #: post-mortem timeline as training stalls — AND the same
        #: threshold drives the closed-loop admission shedder
        #: (services.lifecycle.SloShedder): past it, new work is
        #: rejected with ShedError (503 + Retry-After) instead of
        #: queued into a breach.  0 = no SLO, no shedding.
        self._slo_queue_wait_ms = float(
            serve_cfg.get("slo_queue_wait_ms", 0) or 0)
        self._shed = SloShedder(
            self._slo_queue_wait_ms,
            close_fraction=float(
                serve_cfg.get("shed_close_fraction", 0.5)))
        #: request lifecycle (services.lifecycle): every request gets
        #: an id, an optional deadline, and a cancel path
        self._default_deadline_ms = float(
            serve_cfg.get("default_deadline_ms", 0) or 0)
        self._stream_capacity = int(
            serve_cfg.get("stream_queue_chunks", 64))
        self._stream_overflow = str(
            serve_cfg.get("stream_overflow", "drop_oldest"))
        self._stream_stall_s = float(
            serve_cfg.get("stream_stall_timeout_ms", 10000)) / 1e3
        self._next_req_id = 0
        self._by_id = {}                   # req id -> rec (any state)
        self._cancels = collections.deque()  # req ids to cancel
        self._cancelled = 0
        self._deadline_expired = 0
        self._engine_faults = 0
        self._stream_dropped = 0
        self._spec_mixed = False
        #: segmented-prefill surface: total prefill tokens/segments
        #: the engine has advanced, the measured prefill rate (EWMA
        #: over staged chunk passes — feeds the predictive deadline
        #: check), and the prefill backlog gauge (snapshotted by the
        #: engine thread after each tick, like _kv_gauge)
        self._prefill_tokens = 0
        self._prefill_segments = 0
        self._prefill_ms_per_tok = 0.0
        self._prefill_backlog = 0
        #: decode-tick stall: wall gap between one ``cb.tick()``
        #: returning and the next while rows were decoding — two reports
        #: landing, so the cadence a stream sees, and the time
        #: admissions/prefill stole from in-flight streams.
        #: THE number segmented prefill exists to bound.
        self._stall_hist = collections.deque(maxlen=int(history))
        #: one record a tick (the idiom of _stall_hist: appended by
        #: the engine thread, no I/O): the batcher's ``last_tick`` —
        #: seconds of each ``batcher.*`` span and the tick's counts —
        #: plus this loop's own ``engine.ingress`` / ``engine.deliver``
        #: seconds and counts.  metrics() reads its medians.
        self._tick_ring = collections.deque(maxlen=TICK_RING)
        self._ticks_total = 0
        self._last_tick_end = None
        self._had_active = False
        self._gauges = None
        #: request tracing (telemetry.tracing, docs/services.md
        #: "Request tracing"): apply the trace knobs to the process
        #: span store here — the engine starts after CLI config files
        #: ran, the store's module singleton may not have
        trace_cfg = _root.common.trace
        tracing.store.enabled = bool(trace_cfg.get("enabled", True))
        tracing.store.set_capacity(
            trace_cfg.get("capacity", tracing.DEFAULT_CAPACITY),
            trace_cfg.get("max_spans", tracing.DEFAULT_MAX_SPANS))
        #: per-phase completed-request histogram (lazy, fail-soft)
        self._phase_hist = None
        self._closed = False
        self._wake = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def submit_async(self, prompt_row, max_new, temperature=0.0,
                     seed=0, adapter=0, stream=False, deadline_ms=None,
                     shed_exempt=False, trace=None, parent_span=None):
        """Enqueue one row; returns a handle for ``wait`` (submit every
        row of a request BEFORE waiting so they share the pool).
        Validates here so a bad request raises in the CALLER (one 400),
        never on the engine thread.  The length checks delegate to the
        generator's canonical validate_request; only the engine-specific
        constraints (non-empty prompt, at least one new token — a slot
        must decode something to ever free itself) live here.

        ``deadline_ms``: wall budget from NOW for the whole request
        (None/0 falls back to root.common.serve.default_deadline_ms;
        0 there too = no deadline).  An expired request is cancelled —
        before admission if possible, mid-decode otherwise — and its
        waiter raises DeadlineExceeded.  Raises ShedError (the REST
        layer's 503 + Retry-After) while the SLO shedder is open —
        unless ``shed_exempt``: a fleet router's failover resume is
        already-admitted work being RELOCATED off a dead replica, and
        shedding it would turn one replica's death into lost requests
        (plus waste every token the fleet already decoded for them).

        ``trace``/``parent_span``: the request's trace context
        (telemetry.tracing) — every flight event and span this request
        produces keys on it, so one cross-process timeline
        reconstructs end to end."""
        if not shed_exempt and self._shed.should_shed():
            ra = self._shed.shed()
            flight.record("serve.shed", prompt_len=len(prompt_row),
                          max_new=int(max_new),
                          retry_after_s=ra, trace=trace)
            raise ShedError(
                "admission shedding: measured queue wait exceeds the "
                "%.0f ms SLO (root.common.serve.slo_queue_wait_ms) — "
                "retry after %.0f s" % (self._slo_queue_wait_ms, ra),
                retry_after_s=ra)
        prompt = [int(t) for t in prompt_row]
        if not prompt:
            raise ValueError("empty prompt")
        if int(max_new) < 1:
            raise ValueError("max_new must be >= 1, got %d"
                             % int(max_new))
        self.cb.gen.validate_request(
            len(prompt), {"max_new": int(max_new),
                          "temperature": float(temperature)})
        spec_k = getattr(self.cb, "speculative_k", 0)
        if spec_k and len(prompt) + int(max_new) + spec_k \
                > self.cb.gen.max_len:
            raise ValueError(
                "speculative ticks draft %d positions past the "
                "cursor: prompt+max_new+k %d exceeds max_len %d"
                % (spec_k, len(prompt) + int(max_new) + spec_k,
                   self.cb.gen.max_len))
        n_bank = getattr(self.cb.gen, "_n_adapters", 0)
        if not 0 <= int(adapter) <= n_bank:
            raise ValueError("adapter %d outside the loaded bank "
                             "(0..%d)" % (int(adapter), n_bank))
        if getattr(self.cb, "speculative_k", 0) \
                and float(temperature) != 0.0:
            # speculation routes PER ROW (_make_core_spec): a sampled
            # request advances one token per tick itself, but the
            # greedy rows around it keep their full speculation —
            # byte-identical to an all-greedy pool (test-pinned).  The
            # old pool-wide `serve.spec_degraded` cliff event is
            # retired; this informational one-shot only notes that the
            # pool is mixed (the sampled ROW pays the K-wide verify
            # for single-token progress).  Check-and-set under the
            # lock so concurrent HTTP workers cannot double-emit it.
            with self._lock:
                first = not self._spec_mixed
                self._spec_mixed = True
            if first:
                flight.record("serve.spec_mixed",
                              speculative_k=int(self.cb.speculative_k))
        now = time.monotonic()
        eff_deadline_ms = (float(deadline_ms) if deadline_ms
                           else self._default_deadline_ms)
        if eff_deadline_ms < 0:
            raise ValueError("deadline_ms must be >= 0, got %r"
                             % (deadline_ms,))
        rec = {"prompt": prompt, "max_new": int(max_new),
               "temperature": float(temperature), "seed": int(seed),
               "adapter": int(adapter),
               "event": threading.Event(), "submit_ts": now,
               "admit_ts": None, "out": None, "error": None,
               #: absolute monotonic deadline (None = unbounded)
               "deadline": (now + eff_deadline_ms / 1e3
                            if eff_deadline_ms else None),
               #: batcher request id once cb-submitted (cancel needs it)
               "_rid": None,
               "_cancel_reason": None,
               # streaming: the engine thread pushes ("tokens", [...])
               # chunks of NEW tokens per dispatch, then ("done", out)
               # / ("error", e); the HTTP worker drains until a
               # terminal item.  _sent tracks the high-water mark.
               # BOUNDED (lifecycle.BoundedStream): a consumer that
               # stops reading can no longer grow the queue without
               # limit — chunks drop-oldest, or ('block') the engine
               # holds this request's chunks back until the consumer
               # drains, per root.common.serve.stream_overflow.
               "stream_q": (BoundedStream(
                   self._stream_capacity, self._stream_overflow)
                   if stream else None),
               #: first monotonic ts a 'block' push found the channel
               #: full with no progress since (None = not stalled)
               "_stall_since": None,
               "_sent": 0,
               #: trace context: every flight event / span this
               #: request produces keys on "trace"; "_span" is the
               #: replica-side span children parent onto; "_phases"
               #: is the completed queue/prefill/decode decomposition
               "trace": trace, "_span": None, "_phases": None}
        with self._lock:
            if self._closed:
                raise EngineUnavailable("engine is stopped")
            rec["id"] = self._next_req_id
            self._next_req_id += 1
            self._by_id[rec["id"]] = rec
            self._ingress.append(rec)
        self._wake.set()
        if trace:
            rec["_span"] = tracing.span_add(
                trace, "replica.recv", parent=parent_span,
                req=rec["id"], prompt_len=len(prompt))
        flight.record("serve.submit", req=rec["id"],
                      prompt_len=len(prompt),
                      max_new=int(max_new), stream=bool(stream),
                      trace=trace)
        return rec

    @staticmethod
    def wait(handle):
        handle["event"].wait()
        if handle["error"] is not None:
            raise handle["error"]
        return np.asarray(handle["out"], np.int32)

    def submit(self, prompt_row, max_new, temperature=0.0, seed=0,
               adapter=0):
        """Block until this request's row finishes; returns the 1-D
        prompt+continuation array."""
        return self.wait(self.submit_async(prompt_row, max_new,
                                           temperature=temperature,
                                           seed=seed, adapter=adapter))

    def cancel(self, req_id, reason="cancelled by client"):
        """Request cancellation of an in-flight request by id (the
        ``"id"`` field of a ``submit_async`` handle).  Safe from any
        thread: the actual teardown — freeing the slot and, on paged
        pools, its KV blocks, mid-decode if needed — happens on the
        engine thread at the next loop iteration (the sole batcher
        caller).  The waiter raises RequestCancelled; a streaming
        consumer receives a terminal error chunk.  Returns True if the
        request was still live, False if unknown/already finished."""
        with self._lock:
            rec = self._by_id.get(req_id)
            if rec is None:
                return False
            if rec["_cancel_reason"] is None:
                rec["_cancel_reason"] = str(reason)
        self._cancels.append(req_id)
        self._wake.set()
        return True

    def stream_open(self, prompt_row, max_new, temperature=0.0,
                    seed=0, adapter=0, deadline_ms=None,
                    shed_exempt=False, trace=None, parent_span=None):
        """Streaming submit: returns ``(handle, iterator)`` where the
        iterator yields lists of NEW tokens per engine dispatch.  The
        submit (and thus shed/validation errors) happens EAGERLY in
        this call — the REST layer must learn about a 503/400 before
        it commits response headers; ``handle["id"]`` is the cancel
        token for a mid-stream disconnect, and ``handle["out"]`` holds
        the full result after the final chunk (authoritative even if
        drop-oldest overflow dropped mid-stream chunks)."""
        rec = self.submit_async(prompt_row, max_new,
                                temperature=temperature, seed=seed,
                                adapter=adapter, stream=True,
                                deadline_ms=deadline_ms,
                                shed_exempt=shed_exempt,
                                trace=trace, parent_span=parent_span)

        def drain():
            # chunks carry their start offset, and only CONTIGUOUS
            # progress is yielded: drop_oldest removes chunks from the
            # MIDDLE of the sequence, so anything after the first gap
            # is held back and delivered by the terminal
            # reconstruction below — concatenating the yielded chunks
            # ALWAYS equals the complete continuation exactly;
            # overflow costs incremental granularity, never tokens
            expect = 0                # next new-token index to yield
            while True:
                kind, payload = rec["stream_q"].get()
                if kind == "tokens":
                    start, toks = payload
                    if start <= expect < start + len(toks):
                        fresh = toks[expect - start:]
                        expect += len(fresh)
                        yield fresh
                elif kind == "done":
                    tail = list(payload)[len(rec["prompt"]) + expect:]
                    if tail:
                        yield tail
                    return
                else:
                    raise payload

        return rec, drain()

    def stream(self, prompt_row, max_new, temperature=0.0, seed=0,
               adapter=0, deadline_ms=None):
        """Iterator over lists of NEW tokens as they decode (one chunk
        per engine dispatch — ``ticks_per_dispatch`` tokens at a
        time), ending after the final chunk.  Raises the engine's
        error if the request fails."""
        return self.stream_open(prompt_row, max_new,
                                temperature=temperature, seed=seed,
                                adapter=adapter,
                                deadline_ms=deadline_ms)[1]

    def _finish_error(self, rec, err, kind=None, **fields):
        """Terminal error delivery: waiter raises, streaming consumer
        gets its terminal chunk, the lifecycle index forgets the id."""
        rec["error"] = err
        with self._lock:
            self._by_id.pop(rec.get("id"), None)
        if rec["stream_q"] is not None:
            self._stream_dropped += rec["stream_q"].dropped
            rec["stream_q"].put_terminal(("error", err))
        rec["event"].set()
        if kind is not None:
            flight.record(kind, req=rec.get("id"),
                          prompt_len=len(rec["prompt"]),
                          trace=rec.get("trace"), **fields)
        if rec.get("trace"):
            tracing.span_add(rec["trace"], "replica.error",
                             parent=rec.get("_span"),
                             req=rec.get("id"),
                             error=type(err).__name__)

    def _drain_cancels(self):
        """Engine thread: act on queued ``cancel()`` requests — remove
        the record wherever it currently lives (ingress, batcher
        queue, or a live slot) and free its resources."""
        while self._cancels:
            req_id = self._cancels.popleft()
            with self._lock:
                rec = self._by_id.get(req_id)
                if rec is None:
                    continue
                try:
                    self._ingress.remove(rec)
                except ValueError:
                    pass
                if rec["_rid"] is not None:
                    self._records.pop(rec["_rid"], None)
            if rec["_rid"] is not None:
                # sole-caller contract: only this thread touches the
                # batcher — frees the slot and (paged) its KV blocks
                # mid-decode
                self.cb.cancel(rec["_rid"])
            self._cancelled += 1
            admitted = rec["admit_ts"] is not None
            self._finish_error(
                rec, RequestCancelled(rec["_cancel_reason"]
                                      or "cancelled"),
                kind="serve.cancel", admitted=admitted,
                reason=rec["_cancel_reason"] or "cancelled")

    def _p50_ms_per_tok(self):
        """Measured p50 decode rate over the history window (0.0 with
        no history — never blocks admission before the first
        completions).  One O(n log n) pass; callers processing a batch
        compute it ONCE per drain, not per record."""
        with self._lock:
            vals = sorted(h["ms_per_tok"] for h in self._history)
        if not vals:
            return 0.0
        return vals[len(vals) // 2]

    def _expired(self, rec, now, p50_ms_per_tok):
        """Deadline verdict for a not-yet-admitted request: already
        past, or provably unable to finish in the remaining budget —
        predicted as the prompt's PREFILL time (measured per-token
        prefill rate x prompt length; a long prompt with a tight
        deadline 504s at submit instead of after burning its whole
        prefill) plus the decode residency (measured p50 decode rate
        x max_new).  Either estimate is 0.0 before its first
        measurement — the check never blocks a cold engine."""
        if rec["deadline"] is None:
            return False
        est_s = (p50_ms_per_tok * rec["max_new"]
                 + self._prefill_ms_per_tok
                 * len(rec["prompt"])) / 1e3
        return now >= rec["deadline"] or now + est_s > rec["deadline"]

    def _sweep_deadlines(self, now):
        """Cancel every tracked request whose deadline has passed:
        queued ones before they waste a slot, admitted ones
        mid-decode (the slot and its KV blocks free immediately)."""
        doomed = []
        with self._lock:
            for rid, rec in self._records.items():
                if rec["deadline"] is not None \
                        and now >= rec["deadline"]:
                    doomed.append((rid, rec))
            for rid, _ in doomed:
                self._records.pop(rid, None)
        for rid, rec in doomed:
            self.cb.cancel(rid)
            self._deadline_expired += 1
            admitted = rec["admit_ts"] is not None
            self._finish_error(
                rec, DeadlineExceeded(
                    "deadline expired %s (deadline_ms budget spent "
                    "%.0f ms after submit)"
                    % ("mid-decode" if admitted
                       else "before admission",
                       (now - rec["submit_ts"]) * 1e3)),
                kind="serve.deadline", admitted=admitted)

    def _update_shedder(self, now):
        """One control-loop step for the SLO shedder: the head-of-line
        wait (oldest still-unadmitted request) complements the
        per-admit measurements — it keeps the valve responsive when
        the pool is so far behind nothing is admitted at all."""
        if not self._shed.enabled:
            return
        with self._lock:
            oldest = min(
                (rec["submit_ts"] for rec in list(self._ingress)
                 + list(self._records.values())
                 if rec["admit_ts"] is None), default=None)
        head_wait_ms = (now - oldest) * 1e3 if oldest is not None \
            else 0.0
        trans = self._shed.update(head_wait_ms)
        if trans is not None:
            flight.record("serve.shed_%s" % trans,
                          head_wait_ms=round(head_wait_ms, 3),
                          slo_ms=self._slo_queue_wait_ms,
                          shed_total=self._shed.shed_total)

    def _fault_recover(self, err):
        """An engine tick raised: fail every in-flight request, hard-
        reset the batcher pool (a failed DONATED dispatch may have
        invalidated the state buffers), and keep serving — queued
        ingress requests survive and admit into the fresh pool.  The
        alternative (let the engine thread die) wedges every current
        and future waiter forever."""
        self._engine_faults += 1
        with self._lock:
            victims = list(self._records.values())
            self._records.clear()
        self.cb.reset_pool()
        for rec in victims:
            self._finish_error(
                rec, RuntimeError("engine fault failed this request: "
                                  "%r" % (err,)),
                kind="serve.fault_evict")

    def _note_prefill(self, ev):
        """Batcher prefill-observer hook (runs on the engine thread —
        the sole tick caller): one ``serve.prefill`` flight event per
        bounded chunk pass makes the admission stall visible segment
        by segment, and the measured per-token prefill rate (EWMA)
        feeds the predictive deadline check and the router's cost
        calibration surface."""
        kind = ev.get("kind")
        with self._lock:
            rec = self._records.get(ev.get("rid"))
        req = rec.get("id") if rec is not None else None
        trace = rec.get("trace") if rec is not None else None
        if kind == "segment":
            toks = int(ev.get("tokens") or 0)
            # None where the pass's start or end was not seen: the rate
            # keeps its last value rather than take an upper bound
            dt = ev.get("seconds")
            self._prefill_tokens += toks
            self._prefill_segments += 1
            if toks and dt:
                ms_tok = dt * 1e3 / toks
                self._prefill_ms_per_tok = (
                    ms_tok if not self._prefill_ms_per_tok
                    else 0.8 * self._prefill_ms_per_tok
                    + 0.2 * ms_tok)
            flight.record("serve.prefill", req=req, phase="segment",
                          start=ev.get("start"), tokens=toks,
                          cursor=ev.get("cursor"),
                          plen=ev.get("plen"),
                          ms=None if dt is None else round(dt * 1e3, 3),
                          trace=trace)
        elif kind in ("begin", "admit"):
            flight.record("serve.prefill", req=req, phase=kind,
                          plen=ev.get("plen"), trace=trace)

    def _note_done(self, rec, now):
        """Completion telemetry for one request (engine thread,
        outside the lock): the ``serve.done`` flight event, the
        replica-side phase spans (queue/prefill/decode partition the
        submit→finish wall span exactly — the timeline a trace
        aggregator renders), and the per-phase latency histogram."""
        phases = rec.get("_phases") or {}
        trace = rec.get("trace")
        flight.record("serve.done", req=rec.get("id"), trace=trace,
                      new_tokens=len(rec["out"]) - len(rec["prompt"]),
                      **{"%s_ms" % k: v for k, v in phases.items()})
        if trace:
            parent = rec.get("_span")
            t = tracing.mono_to_wall(rec["submit_ts"])
            for phase in ("queue", "prefill", "decode"):
                ms = phases.get(phase)
                if ms is None:
                    continue
                tracing.span_add(trace, "phase." + phase, ts=t,
                                 dur_ms=ms, parent=parent,
                                 req=rec.get("id"))
                t += ms / 1e3
            tracing.span_add(trace, "replica.done", parent=parent,
                             ts=tracing.mono_to_wall(now),
                             req=rec.get("id"))
        try:
            from veles_tpu import telemetry
            if self._phase_hist is None:
                self._phase_hist = telemetry.registry.histogram(
                    "veles_request_phase_ms",
                    "completed-request latency decomposition "
                    "(queue/prefill/decode) in milliseconds",
                    labelnames=("phase",),
                    buckets=tracing.PHASE_BUCKETS_MS)
            for phase, ms in phases.items():
                self._phase_hist.observe(ms, phase=phase)
        except Exception:   # noqa: BLE001 — fail-soft telemetry
            pass

    def _export_serve_gauges(self, stall_ms=None):
        """Segmented-prefill registry surface (PR 3 MetricsRegistry;
        fail-soft — telemetry must never take the engine down):
        ``veles_serve_prefill_tokens_total`` /
        ``veles_serve_prefill_segments_total`` counters, the prefill
        backlog gauge, and ``veles_serve_decode_stall_ms`` — the last
        measured inter-decode-dispatch gap with rows in flight."""
        try:
            from veles_tpu import telemetry
            if self._gauges is None:
                self._gauges = {
                    "tokens": telemetry.registry.counter(
                        "veles_serve_prefill_tokens_total",
                        "prompt tokens prefilled by segmented "
                        "admission chunk passes"),
                    "segments": telemetry.registry.counter(
                        "veles_serve_prefill_segments_total",
                        "bounded admission prefill chunk passes"),
                    "backlog": telemetry.registry.gauge(
                        "veles_serve_prefill_backlog_tokens",
                        "queued-but-unprefilled prompt tokens"),
                    "stall": telemetry.registry.gauge(
                        "veles_serve_decode_stall_ms",
                        "inter-decode-dispatch gap with streams in "
                        "flight (the admission stall)"),
                    "state_slots": telemetry.registry.gauge(
                        "veles_serve_state_slots_in_use",
                        "slots whose fixed-size per-slot state (a "
                        "retention layer's S and z, a delta rule's S "
                        "and conv tail) a request holds"),
                    "pool_blocks": telemetry.registry.gauge(
                        "veles_serve_pool_blocks_in_use",
                        "paged pool blocks the requests in the slots "
                        "hold, every group's"),
                    "state_bytes": telemetry.registry.gauge(
                        "veles_serve_state_bytes_in_use",
                        "bytes of fixed-size per-slot state held"),
                    "_tokens_seen": 0, "_segments_seen": 0,
                }
            d_tok = self._prefill_tokens - self._gauges["_tokens_seen"]
            if d_tok > 0:
                self._gauges["tokens"].inc(d_tok)
                self._gauges["_tokens_seen"] = self._prefill_tokens
            d_seg = (self._prefill_segments
                     - self._gauges["_segments_seen"])
            if d_seg > 0:
                self._gauges["segments"].inc(d_seg)
                self._gauges["_segments_seen"] = self._prefill_segments
            self._gauges["backlog"].set(self._prefill_backlog)
            if self.cb._state_row_bytes:
                self._gauges["state_slots"].set(self._state_gauge[0])
                self._gauges["state_bytes"].set(self._state_gauge[1])
            if self._blocks_gauge is not None:
                self._gauges["pool_blocks"].set(sum(self._blocks_gauge))
            if stall_ms is not None:
                self._gauges["stall"].set(round(stall_ms, 3))
        except Exception:   # noqa: BLE001 — fail-soft
            pass

    def _loop(self):
        while True:
            # engine.ingress / engine.deliver bracket this loop's own
            # work round cb.tick() (docs/services.md "Request tracing").
            # cb.tick() returns with its own dispatch still on the
            # device, so deliver, the next ingress and the next call's
            # admission and dispatch run BESIDE the device; cb.idle()
            # stays false until a last call has read that dispatch's
            # report, so the loop drains it before it sleeps
            submitted = 0
            with span("engine.ingress") as ingress:
                with self._lock:
                    if self._closed:
                        return
                    new = list(self._ingress)
                    self._ingress.clear()
                now = time.monotonic()
                p50_ms = self._p50_ms_per_tok() if new else 0.0
                for rec in new:           # engine thread: sole cb caller
                    if rec["_cancel_reason"] is not None:
                        continue          # cancel arrived pre-submit —
                                          # _drain_cancels below delivers
                    if self._expired(rec, now, p50_ms):
                        with self._lock:
                            self._by_id.pop(rec.get("id"), None)
                        self._deadline_expired += 1
                        self._finish_error(
                            rec, DeadlineExceeded(
                                "deadline expired before admission"),
                            kind="serve.deadline", admitted=False)
                        continue
                    try:
                        rid = self.cb.submit(rec["prompt"], rec["max_new"],
                                             adapter=rec.get("adapter", 0),
                                             temperature=rec["temperature"],
                                             seed=rec["seed"])
                    except Exception as e:  # noqa: BLE001 — to the waiter
                        self._finish_error(rec, e)
                        continue
                    stopped = False
                    with self._lock:
                        if self._closed:   # stop() raced the hand-off
                            stopped = True
                        else:
                            rec["_rid"] = rid
                            self._records[rid] = rec
                            submitted += 1
                    if stopped:           # release the waiter
                        self._finish_error(rec, RuntimeError(
                            "engine stopped before request completed"))
                self._drain_cancels()
                now = time.monotonic()
                self._sweep_deadlines(now)
                self._update_shedder(now)
            if self.cb.idle():
                self._wake.wait(timeout=0.05)
                self._wake.clear()
                continue
            tick_start = time.monotonic()
            try:
                n_active = self.cb.tick()   # dispatch + read — NO lock
            except Exception as e:    # noqa: BLE001 — survive the tick
                flight.record("serve.engine_fault", error=repr(e))
                self._fault_recover(e)
                self._had_active = False
                continue
            pushed = 0
            with span("engine.deliver") as deliver:
                now = time.monotonic()
                # decode-tick cadence: the gap between consecutive
                # dispatch completions while rows were decoding across the
                # boundary — the inter-chunk gap a streaming client sees.
                # Whole-prompt admissions inflate its p99; the segmented
                # prefill budget bounds it (metrics p50/p99_decode_stall).
                stall_ms = None
                if self._had_active and self._last_tick_end is not None:
                    stall_ms = (now - self._last_tick_end) * 1e3
                    self._stall_hist.append(stall_ms)
                self._last_tick_end = now
                self._had_active = bool(n_active)
                active = self.cb.active_requests()
                done = []
                pushes = []
                with self._lock:
                    for rid, rec in self._records.items():
                        admitted = rid in active or \
                            self.cb.result(rid) is not None
                        if rec["admit_ts"] is None and admitted:
                            # admission happened in THIS tick's admit phase
                            # — stamp its start, so a request that also
                            # finishes within the tick (short max_new,
                            # fused dispatch) records the tick's real
                            # duration as decode time, not a 1e-9 floor
                            rec["admit_ts"] = tick_start
                            qw_ms = (tick_start - rec["submit_ts"]) * 1e3
                            # the MEASURED queue wait: the flight event is
                            # the post-mortem record, the shedder feed is
                            # the closed loop acting on the same number
                            self._shed.note_admit(qw_ms)
                            # flight gets the REAL admission (serve.submit
                            # marked the enqueue): the gap between the two
                            # is the queue wait a post-mortem measures
                            flight.record(
                                "serve.admit", req=rec.get("id"),
                                prompt_len=len(rec["prompt"]),
                                queue_wait_ms=qw_ms,
                                trace=rec.get("trace"))
                    for rid, rec in self._records.items():
                        if rec["stream_q"] is None:
                            continue
                        part = self.cb.partial(rid)
                        if part is None:
                            continue
                        # _sent advances only on DELIVERY below: a chunk a
                        # full 'block' channel refuses is re-derived from
                        # the next dispatch's partial instead of lost
                        fresh = part[len(rec["prompt"]) + rec["_sent"]:]
                        if fresh:
                            pushes.append((rec, fresh))
                    for rid in list(self._records):
                        out = self.cb.pop_result(rid)
                        if out is None:
                            continue
                        rec = self._records.pop(rid)
                        self._by_id.pop(rec.get("id"), None)
                        rec["out"] = out
                        done.append(rec)
                        admit = rec["admit_ts"] or now
                        dec = max(1e-9, now - admit)
                        n_new = len(out) - len(rec["prompt"])
                        qw_ms = (admit - rec["submit_ts"]) * 1e3
                        rec["_queue_wait_ms"] = qw_ms
                        # phase decomposition: the batcher stamped when
                        # this row's FIRST decode dispatch went out, so
                        # the admitted→finished residency splits into the
                        # prefill share (admission chunk passes) and the
                        # pure decode share — non-overlapping by
                        # construction (they partition [admit, now])
                        ds = self.cb.pop_decode_start(rid)
                        if ds is None or not admit <= ds <= now:
                            ds = admit
                        prefill_ms = (ds - admit) * 1e3
                        pure_ms = max(0.0, (now - ds) * 1e3)
                        rec["_phases"] = {
                            "queue": round(qw_ms, 3),
                            "prefill": round(prefill_ms, 3),
                            "decode": round(pure_ms, 3)}
                        self._history.append({
                            "queue_wait_ms": qw_ms,
                            "decode_ms": dec * 1e3,
                            "prefill_ms": prefill_ms,
                            "pure_decode_ms": pure_ms,
                            "new_tokens": n_new,
                            "tokens_per_sec": n_new / dec,
                            "ms_per_tok": dec * 1e3 / max(1, n_new),
                            "finish_ts": now})
                        self._served += 1
                # stream delivery: push is NON-blocking (one slow consumer
                # must never freeze the engine loop every other request's
                # decode shares).  A full 'block' channel keeps this
                # request's chunks back for the next dispatch; once it has
                # made no progress for stream_stall_timeout_ms the
                # consumer is dead or a slowloris — cancel the request
                # instead of letting it pin its slot.
                for rec, fresh in pushes:
                    if rec["stream_q"].push(
                            ("tokens", (rec["_sent"], fresh))):
                        rec["_sent"] += len(fresh)
                        rec["_stall_since"] = None
                        pushed += 1
                    elif rec["_stall_since"] is None:
                        rec["_stall_since"] = now
                    elif now - rec["_stall_since"] > self._stream_stall_s:
                        flight.record("serve.stream_stall",
                                      req=rec.get("id"),
                                      sent=rec["_sent"])
                        self.cancel(rec["id"],
                                    reason="stream consumer stalled past "
                                           "stream_stall_timeout_ms")
                if self.cb._state_row_bytes:
                    with self._lock:
                        self._state_gauge = self.cb.state_in_use()
                if self._kv_gauge is not None:
                    with self._lock:
                        self._kv_gauge = self.cb.free_blocks()
                        self._blocks_gauge = self.cb.blocks_in_use()
                        if self._prefix_gauge is not None:
                            self._prefix_gauge = self.cb.prefix_stats()
                # prefill-backlog snapshot (engine thread — the batcher's
                # queue/staging are tick-caller state) + registry gauges
                self._prefill_backlog = self.cb.prefill_backlog_tokens()
                self._export_serve_gauges(stall_ms)
                for rec in done:          # wake waiters outside the lock
                    self._note_done(rec, now)
                    if self._slo_queue_wait_ms and \
                            rec.get("_queue_wait_ms", 0.0) \
                            > self._slo_queue_wait_ms:
                        flight.record(
                            "serve.slo_breach", req=rec.get("id"),
                            queue_wait_ms=rec["_queue_wait_ms"],
                            slo_ms=self._slo_queue_wait_ms,
                            prompt_len=len(rec["prompt"]),
                            trace=rec.get("trace"))
                    if rec["stream_q"] is not None:
                        # no tail flush here: the terminal's payload IS the
                        # full result, and the consumer-side drain yields
                        # whatever the last dispatch decoded (or overflow
                        # swallowed) as one final reconstructed chunk —
                        # a full 'block' channel at completion can refuse
                        # nothing it would lose
                        self._stream_dropped += rec["stream_q"].dropped
                        rec["stream_q"].put_terminal(("done", rec["out"]))
                    rec["event"].set()
            tick = self.cb.last_tick
            if tick is not None:      # None: a tick() stubbed by a test
                with self._lock:
                    self._ticks_total += 1
                    self._tick_ring.append(dict(
                        tick, ingress_s=ingress.seconds,
                        deliver_s=deliver.seconds, submitted=submitted,
                        pushed=pushed, refused=len(pushes) - pushed))

    def metrics(self):
        """Serving-plane SLO snapshot: queue depth, in-flight rows,
        served count, p50/p99 queue-wait and per-stream decode rate
        over the last ``history`` completed requests."""
        with self._lock:
            hist = list(self._history)
            queued = len(self._ingress) + sum(
                1 for r in self._records.values()
                if r["admit_ts"] is None)
            in_flight = sum(1 for r in self._records.values()
                            if r["admit_ts"] is not None)
            served = self._served
            # prompts still in the HTTP ingress have not reached the
            # batcher's queue — they are prefill backlog too
            ingress_toks = sum(len(r["prompt"]) for r in self._ingress)
            stalls = list(self._stall_hist)
            ticks = list(self._tick_ring)
            ticks_total = self._ticks_total
        out = {"served": served, "queued": queued,
               "in_flight": in_flight, "slots": self.cb.slots,
               "uptime_s": round(time.monotonic() - self._start_ts, 1),
               "agg_tokens_per_sec": 0.0,
               # lifecycle counters (docs/services.md "Serving
               # robustness"): shed valve state + how many requests
               # each enforcement path has taken out
               "shed_state": self._shed.status()["state"],
               "shed_total": self._shed.shed_total,
               "cancelled_total": self._cancelled,
               "deadline_expired_total": self._deadline_expired,
               "engine_faults": self._engine_faults,
               "stream_dropped_chunks": self._stream_dropped,
               # segmented-prefill surface (docs/services.md
               # "Disaggregated prefill"): backlog in TOKENS (the
               # autoscaler's early signal), work done, measured rate
               "queued_prefill_tokens": (ingress_toks
                                         + self._prefill_backlog),
               "prefill_tokens_total": self._prefill_tokens,
               "prefill_segments_total": self._prefill_segments,
               "prefill_ms_per_tok": round(self._prefill_ms_per_tok,
                                           4)}
        if self._kv_gauge is not None:
            out["free_kv_blocks"] = self._kv_gauge
            out["pool_blocks_full_in_use"], \
                out["pool_blocks_window_in_use"] = self._blocks_gauge
        if self.cb._state_row_bytes:
            out["state_slots_in_use"], out["state_bytes_in_use"] = \
                self._state_gauge
        if self._prefix_gauge is not None:
            out["prefix_shared_blocks"] = self._prefix_gauge[0]
            out["prefix_block_refs"] = self._prefix_gauge[1]

        def pct(vals, q):
            if not vals:
                return 0.0
            vals = sorted(vals)
            return round(vals[min(len(vals) - 1,
                                  int(q / 100.0 * len(vals)))], 3)

        def pct_or_none(vals):
            return pct(vals, 50) if vals else None

        # per-phase decomposition percentiles (docs/services.md
        # "Request tracing"): queue_wait/prefill/pure_decode partition
        # each request's submit→finish span, so the phase a latency
        # miss lives in reads straight off the metrics
        for key in ("queue_wait_ms", "ms_per_tok", "tokens_per_sec",
                    "prefill_ms", "pure_decode_ms"):
            vals = [h[key] for h in hist if key in h]
            out["p50_" + key] = pct(vals, 50)
            out["p99_" + key] = pct(vals, 99)
        # span-store eviction count (the bounded trace ring's
        # "counted gauge"; also veles_trace_dropped_total)
        out["trace_dropped_total"] = tracing.store.dropped
        # the decode-tick cadence: inter-dispatch gap with streams in
        # flight — whole-prompt admissions inflate its p99, segmented
        # prefill bounds it (the stall-free serving gate's number)
        out["p50_decode_stall_ms"] = pct(stalls, 50)
        out["p99_decode_stall_ms"] = pct(stalls, 99)
        # where a tick's time goes, from the per-tick ring (the last
        # TICK_RING ticks): the batcher's spans, this loop's own, and
        # what the ticks carried (docs/services.md "Request tracing").
        # Host = the tick less the time blocked on the device.
        out["ticks_total"] = ticks_total
        for key, ms in (
                ("p50_tick_ms", lambda t: t["tick_s"]),
                ("p50_tick_wait_ms", lambda t: t["wait_s"]),
                ("p50_tick_host_ms", lambda t: t["tick_s"] - t["wait_s"]),
                ("p50_tick_fetch_ms", lambda t: t["fetch_s"]),
                ("p50_tick_admit_ms", lambda t: t["admit_s"]),
                ("p50_engine_host_ms",
                 lambda t: t["ingress_s"] + t["deliver_s"])):
            out[key] = pct([ms(t) * 1e3 for t in ticks], 50)
        # the bytes a tick read from the device: its report (a few
        # hundred, whatever max_len is)
        out["p50_tick_fetch_bytes"] = pct(
            [t["fetch_bytes"] for t in ticks], 50)
        out["tick_rows_mean"] = round(
            sum(t["rows"] for t in ticks) / len(ticks), 3) if ticks \
            else 0.0
        # the device's side, from the landing stamps: the median device
        # time of a plain tick and of a full staged pass, where both
        # ends were seen (None where none was); the share of the calls
        # — a cold one is none — whose first work went onto an empty
        # queue, by the phase that first saw it empty; and the share of
        # the dispatches landed whose device time was not seen
        out["p50_tick_device_ms"] = pct_or_none(
            [t["device_s"] * 1e3 for t in ticks
             if t["plain"] and t["device_s"] is not None])
        out["p50_pass_device_ms"] = pct_or_none(
            [s * 1e3 for t in ticks for n, s in t["passes"]
             if n == self.cb.prefill_segment and s is not None]) \
            if self.cb.prefill_segment else None
        warm = [t for t in ticks if not t["cold"]]
        out["drained_share"] = round(
            sum(t["drained"] for t in warm) / len(warm), 4) if warm \
            else 0.0
        out["drained_by"] = dict(collections.Counter(
            t["drained_in"] for t in warm if t["drained"]))
        landed = sum(t["landed"] for t in ticks)
        out["unseen_share"] = round(
            sum(t["unseen"] for t in ticks) / landed, 4) if landed \
            else 0.0
        out["p50_tick_kv_tokens"] = pct([t["kv_tokens"] for t in ticks],
                                        50)
        out["p50_tick_kv_pages"] = pct([t["kv_pages"] for t in ticks], 50)
        # what the attention's softmax ran over (= kv_tokens without a
        # sparse-attention indexer) and the experts a tick's rows
        # touched (0 without dropless expert layers)
        out["p50_tick_sel_keys"] = pct([t["sel_keys"] for t in ticks], 50)
        out["p50_tick_experts_touched"] = pct(
            [t["experts_touched"] for t in ticks], 50)
        # a window group's own count of keys (0 without window layers)
        # and the pairs that landed on the experts held here (0 where
        # a layer holds all of its experts)
        out["p50_tick_win_keys"] = pct([t["win_keys"] for t in ticks], 50)
        out["p50_tick_expert_pairs"] = pct(
            [t["expert_pairs"] for t in ticks], 50)
        # the state a tick's decode steps moved (0 without state layers):
        # rows, and the bytes they read + wrote
        out["p50_tick_state_rows"] = pct(
            [t["state_rows"] for t in ticks], 50)
        out["p50_tick_state_bytes"] = pct(
            [t["state_bytes"] for t in ticks], 50)
        # the state layers' decode-kernel launches a tick, and what the
        # ring's admissions cost apart: state bytes written into slots,
        # pool blocks claimed
        out["p50_tick_state_launches"] = pct(
            [t["state_launches"] for t in ticks], 50)
        out["admit_state_bytes"] = sum(t["admit_state_bytes"]
                                       for t in ticks)
        out["admit_blocks"] = sum(t["admit_blocks"] for t in ticks)
        staged = sum(t["staged_tokens"] for t in ticks)
        out["staged_expert_pairs_per_token"] = round(
            sum(t["staged_expert_pairs"] for t in ticks) / staged, 4) \
            if staged else 0.0
        if len(hist) >= 2:
            # pool-level throughput: all new tokens in the history
            # window over the window's wall span (concurrent streams
            # overlap — summing per-stream decode times would undercount)
            span = hist[-1]["finish_ts"] - hist[0]["finish_ts"]
            if span > 1e-9:
                out["agg_tokens_per_sec"] = round(
                    sum(h["new_tokens"] for h in hist[1:]) / span, 1)
        return out

    def tick_records(self):
        """The per-tick ring, oldest first: one dict a call of
        ``cb.tick()`` with the seconds of each ``batcher.*`` /
        ``engine.*`` span (``tick_s``, ``admit_s``, ``dispatch_s``,
        ``wait_s``, ``fetch_s``, ``emit_s``, ``ingress_s``,
        ``deliver_s``) and its counts (docs/services.md "Request
        tracing") — which ticks were slow, and what they carried.  The
        tick is dispatched one ahead: ``admitted``, ``prompt_tokens``
        and the ``staged_*`` counts are of what the call ENQUEUED,
        ``rows``, the key and expert counts and ``finished`` of the
        report it READ (the dispatch before its own); its landing stamps
        (``generate.TICK_STAMPS``) say what the device spent on what it
        landed and whether its first enqueue found the queue empty."""
        with self._lock:
            return list(self._tick_ring)

    def reset_metrics(self):
        """Clear the latency history and served counter (e.g. after a
        warmup request whose first-dispatch compile time would pollute
        the percentiles)."""
        with self._lock:
            self._history.clear()
            self._stall_hist.clear()
            self._tick_ring.clear()
            self._ticks_total = 0
            self._served = 0
            self._start_ts = time.monotonic()

    def lifecycle_status(self):
        """The ``/api/health`` serving block: shed valve state plus
        the lifecycle counters — cheap and lock-light, safe for a
        liveness probe."""
        out = dict(self._shed.status())
        with self._lock:
            out.update({
                "open_requests": len(self._by_id),
                "cancelled_total": self._cancelled,
                "deadline_expired_total": self._deadline_expired,
                "engine_faults": self._engine_faults,
                "stream_dropped_chunks": self._stream_dropped,
            })
        return out

    def leak_check(self):
        """Post-drain resource audit for the chaos harness and the
        lifecycle tests: call AFTER the engine went idle (metrics()
        queued == in_flight == 0) — it reads batcher state that only
        the engine thread may touch while work is in flight.  Every
        value should be 0 / True on a healthy drained engine."""
        with self._lock:
            out = {"ingress": len(self._ingress),
                   "records": len(self._records),
                   "open_requests": len(self._by_id),
                   "pending_cancels": len(self._cancels)}
        out["slots_busy"] = sum(
            1 for r in self.cb._slot_req if r is not None)
        if hasattr(self.cb, "free_blocks"):
            # a model with no per-token layer has no blocks to leak
            # (pool_blocks 0); its slots' state is counted beside them
            out["kv_blocks_leaked"] = (self.cb.pool_blocks
                                       - self.cb.free_blocks())
        out["state_slots_held"] = self.cb.state_in_use()[0]
        out["engine_thread_alive"] = self._thread.is_alive()
        return out

    def stop(self):
        with self._lock:
            self._closed = True
            # release every waiter: queued records error out, in-flight
            # ones too (wait() raises instead of hanging forever)
            pending = list(self._ingress) + list(self._records.values())
            self._ingress.clear()
            self._records.clear()
            self._by_id.clear()
        self._cancels.clear()
        for rec in pending:
            if rec["out"] is None and rec["error"] is None:
                rec["error"] = RuntimeError(
                    "engine stopped before request completed")
            if rec.get("stream_q") is not None and rec["out"] is None:
                # a streaming consumer blocks in stream_q.get(), not on
                # the event — it needs its own terminal or it hangs
                rec["stream_q"].put_terminal(("error", rec["error"]))
            rec["event"].set()
        self._wake.set()
        self._thread.join(timeout=5)


class RESTfulAPI(Logger):
    def __init__(self, forward, input_shape, host="127.0.0.1", port=8180,
                 path="/service", generator=None, batch_window=0.0,
                 max_batch=8, continuous_slots=0, paged_block=0,
                 pool_tokens=None, prefix_cache=False,
                 speculative_k=0, ticks_per_dispatch=1,
                 prefill_segment=None):
        super(RESTfulAPI, self).__init__()
        self.forward = forward            # callable(np.ndarray) -> ndarray
        self.input_shape = tuple(input_shape)
        self.host, self.port, self.path = host, port, path
        #: models.generate.LMGenerator — enables the ``"generate"``
        #: request form for causal-LM workflows
        self.generator = generator
        #: batch_window > 0: coalesce concurrent generate requests into
        #: one device call (GenerateBatcher)
        self.batcher = (GenerateBatcher(generator, batch_window,
                                        max_batch)
                        if generator is not None and batch_window > 0
                        else None)
        #: continuous_slots > 0: in-flight batching — requests join the
        #: live decode at the next tick (ContinuousEngine; greedy and
        #: plain-temperature requests only, top_k/top_p/beam/speculative
        #: fall through to the other paths)
        self.engine = (ContinuousEngine(generator, continuous_slots,
                                        paged_block=paged_block,
                                        pool_tokens=pool_tokens,
                                        prefix_cache=prefix_cache,
                                        speculative_k=speculative_k,
                                        ticks_per_dispatch=
                                        ticks_per_dispatch,
                                        prefill_segment=prefill_segment)
                       if generator is not None and continuous_slots > 0
                       else None)
        self._server = None
        self._thread = None
        #: graceful-shutdown state machine (services.lifecycle): while
        #: not "serving", the work endpoint rejects new requests with
        #: 503 + Retry-After, in-flight ones finish, and {path}/health
        #: reports the drain state so a fleet router stops routing here
        self.drain_state = DrainState()
        self._drain_thread = None
        #: in-flight work-endpoint POSTs (admission through response
        #: written) — the drain watcher's "finished in-flight" signal;
        #: engine/batcher queue depths alone miss the tail between a
        #: request leaving the pool and its response hitting the socket
        self._http_inflight = 0
        self._http_lock = threading.Lock()

    # ------------------------------------------------------------- server
    def start(self):
        api = self

        class Handler(BaseHTTPRequestHandler):
            def _send_json(self, code, payload, headers=()):
                send_json(self, code, payload, headers)

            def do_GET(self):
                if self.path == api.path + "/metrics":
                    self._send_json(200, api.serving_metrics())
                elif self.path == api.path + "/health":
                    # the fleet router's probe surface: drain state +
                    # the PR 6 lifecycle block.  503 while not serving
                    # so dumb LBs also stop sending traffic here.
                    state = api.health_status()
                    self._send_json(
                        200 if state["state"] == "serving" else 503,
                        state)
                elif self.path == api.path + "/leaks":
                    # post-drain resource audit (chaos harness; call
                    # once idle — see ContinuousEngine.leak_check)
                    self._send_json(200, api.engine.leak_check()
                                    if api.engine is not None else {})
                elif self.path.startswith(api.path + "/trace/"):
                    # this replica's spans for one trace id (the
                    # router's /trace/<id> aggregation fans out here;
                    # 404 = unknown or already ring-evicted)
                    tid = self.path[len(api.path + "/trace/"):]
                    spans = tracing.store.spans(tid)
                    self._send_json(
                        200 if spans else 404,
                        {"trace": tid, "spans": spans,
                         "phases": tracing.phases_of(spans)})
                else:
                    self.send_error(404)

            def do_POST(self):
                if self.path == api.path + "/drain":
                    # admin drain: stop admission, finish in-flight,
                    # report "drained" on /health.  202: the drain is
                    # accepted and proceeds in the background.
                    api.drain(reason="admin /drain")
                    self._send_json(202, api.drain_state.status())
                    return
                if self.path != api.path:
                    self.send_error(404)
                    return
                # count FIRST, then check the drain gate: the drain
                # watcher polls the counter, so a request that passed
                # the gate is always visible to it (no slip-through
                # between check and increment)
                with api._http_lock:
                    api._http_inflight += 1
                try:
                    if not api.drain_state.is_serving():
                        ra = api.drain_retry_after_s()
                        self._send_json(
                            503,
                            {"error": "draining: this endpoint is "
                                      "not admitting new work",
                             "draining": True, "retry_after_s": ra},
                            headers=[("Retry-After",
                                      str(max(1, int(math.ceil(ra)))))])
                        return
                    self._do_work_post()
                finally:
                    with api._http_lock:
                        api._http_inflight -= 1

            def _do_work_post(self):
                # trace context (telemetry.tracing): a fleet router
                # upstream supplies it on the X-Veles-Trace header;
                # serving bare, THIS replica is the edge and mints —
                # an absent/forged header value mints fresh too, so a
                # client can never pick its own id here either
                ctx = tracing.parse_header(
                    self.headers.get(tracing.TRACE_HEADER))
                minted = ctx is None
                if minted:
                    trace = tracing.new_trace_id()
                    parent = tracing.span_add(trace, "request",
                                              edge="replica")
                else:
                    trace, parent = ctx
                t_edge = time.monotonic()
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    req = json.loads(self.rfile.read(length))
                    if isinstance(req.get("generate"), dict) and \
                            req["generate"].get("stream"):
                        # NDJSON streaming: one {"tokens": [...]} line
                        # per engine dispatch, then {"done", "result"}.
                        # HTTP/1.0 semantics — body is EOF-delimited,
                        # so no Content-Length / chunking needed.
                        # run_generate_stream submits EAGERLY, so
                        # shed (503) / validation (400) surface before
                        # the 200 header commits.
                        prompt, chunks, handle = \
                            api.run_generate_stream(
                                req, trace=trace, parent_span=parent)
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         "application/x-ndjson")
                        self.end_headers()
                        got = list(prompt)
                        # headers are out: a mid-stream ENGINE failure
                        # surfaces as a structured NDJSON error line;
                        # a failed WRITE means the client is gone —
                        # cancel engine-side so the request frees its
                        # slot (and KV blocks) instead of decoding to
                        # completion for nobody.
                        try:
                            for fresh in chunks:
                                got.extend(fresh)
                                self.wfile.write(
                                    (json.dumps({"tokens": fresh})
                                     + "\n").encode())
                                self.wfile.flush()
                            t_done = time.monotonic()
                            # the handle's final result is authoritative
                            # even if drop-oldest overflow dropped
                            # mid-stream chunks on a slow reader
                            result = (list(handle["out"])
                                      if handle["out"] is not None
                                      else got)
                            # the terminal line carries the trace id
                            # (the client's reconstruction key) and
                            # the engine's phase decomposition (the
                            # router's fleet rollup reads it here)
                            tail = {"done": True, "result": result,
                                    "trace": trace}
                            if handle.get("_phases"):
                                tail["phases"] = handle["_phases"]
                            dropped = (handle["stream_q"].dropped
                                       if handle["stream_q"] is not None
                                       else 0)
                            if dropped:
                                tail["dropped_chunks"] = dropped
                            self.wfile.write(
                                (json.dumps(tail) + "\n").encode())
                            # stream phase: engine completion → final
                            # line written (the delivery tail the
                            # engine phases cannot see).  Only when
                            # THIS replica is the edge — behind a
                            # router the stream remainder is the
                            # router's to attribute, and recording it
                            # twice would double-count the phase
                            if minted:
                                tracing.span_add(
                                    trace, "phase.stream",
                                    parent=(handle.get("_span")
                                            or parent),
                                    dur_ms=round((time.monotonic()
                                                  - t_done) * 1e3, 3),
                                    req=handle.get("id"))
                        except Exception as e:  # noqa: BLE001
                            api.engine.cancel(
                                handle["id"],
                                reason="stream write failed: %r" % e)
                            try:
                                # "kind" lets a fleet router tell a
                                # REQUEST-scoped terminal (deadline,
                                # cancel — relay to the client, the
                                # replica is healthy) from an ENGINE-
                                # scoped one (fail over)
                                self.wfile.write(
                                    (json.dumps(
                                        {"error": str(e),
                                         "kind": type(e).__name__})
                                     + "\n").encode())
                            except Exception:  # noqa: BLE001 — dead pipe
                                pass
                        return
                    meta = {}
                    if "generate" in req:
                        out = api.run_generate(req, trace=trace,
                                               parent_span=parent,
                                               meta=meta)
                    else:
                        out = np.asarray(api.forward(api.decode_input(req)))
                    payload = {"result": out.tolist(), "trace": trace}
                    if meta.get("phases"):
                        payload["phases"] = meta["phases"]
                    self._send_json(200, payload)
                except ShedError as e:
                    # SLO admission shedding: tell the client to back
                    # off instead of queuing into a breach
                    self._send_json(
                        503, {"error": str(e),
                              "retry_after_s": e.retry_after_s},
                        headers=[("Retry-After", str(max(
                            1, int(math.ceil(e.retry_after_s)))))])
                except EngineUnavailable as e:
                    # a stopped engine is service unavailability, not
                    # a bad request: 503 so a fleet router routes
                    # around this replica instead of failing the
                    # client with a "deterministic" 400
                    self._send_json(
                        503, {"error": str(e), "retry_after_s": 1.0},
                        headers=[("Retry-After", "1")])
                except DeadlineExceeded as e:
                    self._send_json(504, {"error": str(e)})
                except Exception as e:  # noqa: BLE001 — report to client
                    self._send_json(400, {"error": str(e)})
                finally:
                    if minted:
                        # terminal-span rule: the edge that MINTED the
                        # id terminates the trace — exactly once, on
                        # every exit path (success, error, dead pipe)
                        tracing.span_add(
                            trace, "request.done", parent=parent,
                            terminal=True,
                            dur_ms=round((time.monotonic()
                                          - t_edge) * 1e3, 3))

            def log_message(self, fmt, *args):
                api.debug("http: " + fmt, *args)

        class Server(ThreadingHTTPServer):
            # socketserver's default listen backlog of 5 resets
            # connections under a concurrent client burst
            request_queue_size = 128

        self._server = Server((self.host, self.port), Handler)
        self.port = self._server.server_address[1]   # resolve port 0
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        daemon=True)
        self._thread.start()
        self.info("serving on http://%s:%d%s", self.host, self.port,
                  self.path)

    def stop(self):
        if self._server is not None:
            self._server.shutdown()
            self._server = None
        if self.batcher is not None:
            self.batcher.stop()
        if self.engine is not None:
            self.engine.stop()

    # -------------------------------------------------------------- drain
    def drain(self, reason="drain"):
        """Graceful shutdown, phase 1: stop admitting (the work
        endpoint 503s with Retry-After), let every in-flight request
        finish, then flip ``drain_state`` to ``drained`` (watched by
        :meth:`wait_drained`, ``{path}/health``, and the fleet
        router).  Idempotent; returns True on the serving→draining
        transition.  The endpoint itself stays up — a drained replica
        still answers health probes until its owner calls
        :meth:`stop` / exits."""
        if not self.drain_state.begin(reason):
            return False
        flight.record("serve.drain", pid=os.getpid(),
                      reason=str(reason))
        self._drain_thread = threading.Thread(
            target=self._drain_watch, name="VelesDrain", daemon=True)
        self._drain_thread.start()
        return True

    def wait_drained(self, timeout=None):
        """Block until every in-flight request finished (True) or
        ``timeout`` passed (False)."""
        return self.drain_state.wait("drained", timeout=timeout)

    def drain_retry_after_s(self):
        """Retry-After hint for requests refused while draining: one
        shedder window when an SLO is configured (same backoff the
        overload path hands out), else one second."""
        if self.engine is not None:
            return self.engine._shed.retry_after_s()
        return 1.0

    def _idle(self):
        """True iff no request is anywhere in the serving pipeline:
        engine queue/pool empty, coalescer empty, and every work POST
        has written its response."""
        with self._http_lock:
            if self._http_inflight:
                return False
        if self.engine is not None:
            m = self.engine.metrics()
            if m["queued"] or m["in_flight"]:
                return False
        if self.batcher is not None and self.batcher.pending():
            return False
        return True

    def _drain_watch(self):
        from veles_tpu.config import root
        timeout_s = float(root.common.serve.get(
            "drain_timeout_ms", 30000)) / 1e3
        deadline = time.monotonic() + timeout_s
        forced = False
        while not self._idle():
            if time.monotonic() >= deadline:
                forced = True
                break
            time.sleep(0.02)
        self.drain_state.finish()
        flight.record("serve.drained", pid=os.getpid(),
                      forced=forced)
        if forced:
            self.warning("drain forced through after %.1f s with "
                         "requests still in flight "
                         "(root.common.serve.drain_timeout_ms)",
                         timeout_s)

    def health_status(self):
        """``{path}/health`` payload: drain state + the PR 6 lifecycle
        block + queue-depth vitals — everything the fleet router's
        probe needs in one cheap GET.  A dead ENGINE thread (stopped,
        or killed by something the fault-recovery path could not
        survive) reports ``"failed"`` even though HTTP still answers —
        a router must not route work into a serving shell whose pool
        no longer ticks."""
        state = self.drain_state.state
        if self.engine is not None and state == "serving" \
                and not self.engine._thread.is_alive():
            state = "failed"
        out = {"state": state, "pid": os.getpid(),
               "port": self.port}
        if self.drain_state.since is not None:
            out["drain"] = self.drain_state.status()
        if self.engine is not None:
            try:
                out["serving"] = self.engine.lifecycle_status()
                m = self.engine.metrics()
                # queued_prefill_tokens: the fleet autoscaler's early
                # scale-up signal (prefill backlog predicts the queue-
                # wait breach); the measured rates feed the router's
                # cost-weighted placement calibration
                for key in ("queued", "in_flight", "served", "slots",
                            "queued_prefill_tokens", "p50_ms_per_tok",
                            "prefill_ms_per_tok"):
                    out[key] = m[key]
            except Exception as e:  # noqa: BLE001 — probe never 500s
                out["serving"] = {"error": str(e)}
        return out

    def serving_metrics(self):
        """GET ``{path}/metrics``: the serving plane's SLO surface —
        ContinuousEngine latency percentiles when the slot pool is on,
        plus which serving paths are active."""
        out = {"paths": {
            "continuous": self.engine is not None,
            "coalescing": self.batcher is not None,
            "generate": self.generator is not None}}
        if self.engine is not None:
            out["continuous"] = self.engine.metrics()
        return out

    # ---------------------------------------------------------- generation
    @staticmethod
    def _engine_opts_subset(opts):
        """The sampling-options subset EVERY slot-pool path requires:
        no top-k/top-p truncation (the pool decodes greedy/plain-
        temperature only) and at least one new token (a slot must
        decode something to ever free itself).  The engine dispatch
        branch checks exactly this — beam/speculative requests were
        dispatched before it runs — while the adapter and streaming
        gates layer the beam/speculative exclusions on top via
        ``_plain_engine_request``."""
        return (int(opts.get("top_k", 0)) == 0
                and float(opts.get("top_p", 1.0)) >= 1.0
                and int(opts.get("max_new", 16)) >= 1)

    @staticmethod
    def _plain_engine_request(opts):
        """True iff this generate request can ride the slot pool from
        a cold start: plain greedy/temperature, no beam, no
        speculative — the predicate the adapter gate and the streaming
        gate share (the engine dispatch branch needs only
        ``_engine_opts_subset``; see there)."""
        return (int(opts.get("beam", 0)) <= 1
                and not int(opts.get("speculative", 0))
                and RESTfulAPI._engine_opts_subset(opts))

    def run_generate_stream(self, req, trace=None, parent_span=None):
        """NDJSON token streaming: validates a single-row greedy /
        plain-temperature engine request and returns (prompt, iterator
        over new-token chunks, engine handle).  The submit happens
        EAGERLY — shed/validation errors raise here, before the
        HTTP layer commits response headers — and the handle carries
        the cancel token (``handle["id"]``) for a mid-stream
        disconnect plus the authoritative final result
        (``handle["out"]``).  Everything else must use the buffered
        endpoint — streaming has no batch to coalesce and no beam
        state to surface incrementally."""
        if self.generator is None:
            raise ValueError("this endpoint serves a non-LM workflow: "
                             "no generator is attached")
        opts = req.get("generate")
        if not isinstance(opts, dict):
            raise ValueError("'generate' must be an options object")
        if self.engine is None:
            raise ValueError("\"stream\" requires the continuous "
                             "engine (continuous_slots>0)")
        prompt = np.asarray(req["input"], np.int32)
        if prompt.ndim == 1:
            prompt = prompt[None]
        if prompt.shape[0] != 1:
            raise ValueError("\"stream\" serves ONE row per request")
        if not self._plain_engine_request(opts):
            raise ValueError("\"stream\" supports plain greedy/"
                             "temperature requests only")
        self.generator.validate_request(len(prompt[0]), opts)
        handle, it = self.engine.stream_open(
            prompt[0], int(opts.get("max_new", 16)),
            temperature=float(opts.get("temperature", 0.0)),
            seed=int(opts.get("seed", 0)),
            adapter=int(opts.get("adapter", 0)),
            deadline_ms=opts.get("deadline_ms"),
            # {"resume": true}: a fleet router relocating an already-
            # admitted stream off a dead replica — exempt from the
            # shed valve (see submit_async), never from validation
            shed_exempt=bool(req.get("resume")),
            trace=trace, parent_span=parent_span)
        return prompt[0].tolist(), it, handle

    def run_generate(self, req, trace=None, parent_span=None,
                     meta=None):
        """``{"input": [[tok, ...]], "generate": {"max_new": N,
        "temperature": T, "seed": S}}`` → generated token matrix (causal
        LM serving; needs ``generator=``).  ``trace``/``parent_span``
        thread the request's trace context into the slot pool;
        ``meta`` (a dict, mutated) receives the engine's phase
        decomposition for the HTTP layer to embed in the response."""
        if self.generator is None:
            raise ValueError("this endpoint serves a non-LM workflow: "
                             "no generator is attached")
        opts = req.get("generate")
        if not isinstance(opts, dict):
            # null/false/0/[] must not silently mean "generate with
            # defaults" — only an options object selects this endpoint
            raise ValueError(
                "'generate' must be an options object like "
                "{\"max_new\": 16}, got %r" % (opts,))
        prompt = np.asarray(req["input"], np.int32)
        if prompt.ndim == 1:
            prompt = prompt[None]
        if int(opts.get("adapter", 0)) and (
                self.engine is None
                or not self._plain_engine_request(opts)):
            # adapter routing lives in the slot pool's tick; every
            # other path runs un-adapted params and would silently
            # serve the base model
            raise ValueError("\"adapter\" routing requires the "
                             "continuous engine (continuous_slots>0) "
                             "and a plain greedy/temperature request")
        beam = int(opts.get("beam", 0))
        if beam > 1:
            out, _ = self.generator.beam_search(
                prompt, int(opts.get("max_new", 16)), beam=beam)
            return out
        spec = int(opts.get("speculative", 0))
        if (spec and prompt.shape[0] == 1 and self.batcher is None
                and float(opts.get("temperature", 0.0)) == 0.0):
            # greedy single-row requests can opt into in-jit n-gram
            # speculation (exact greedy semantics; generate_speculative
            # falls back itself when speculation can't apply)
            return self.generator.generate_speculative(
                prompt, int(opts.get("max_new", 16)), draft_k=spec)
        if self.engine is not None and self._engine_opts_subset(opts):
            # (beam/speculative were dispatched above; a speculative
            # request that fell through — batcher attached, sampled,
            # or multi-row — rides the pool as plain decode, as
            # before.  max_new=0 echo/score requests fall through —
            # the solo and coalescing paths serve them; the slot pool
            # can't)
            for row in prompt:
                self.generator.validate_request(len(row), opts)
            handles = []
            try:
                for row in prompt:
                    handles.append(self.engine.submit_async(
                        row, int(opts.get("max_new", 16)),
                        temperature=float(opts.get("temperature", 0.0)),
                        seed=int(opts.get("seed", 0)),
                        adapter=int(opts.get("adapter", 0)),
                        deadline_ms=opts.get("deadline_ms"),
                        # a resume relocation keeps its exemption on
                        # the buffered path too (prefill handoffs ride
                        # it); the trace context rides along so the
                        # relocated leg joins the original timeline
                        shed_exempt=bool(req.get("resume")),
                        trace=trace, parent_span=parent_span))
            except ShedError:
                # the shedder opened mid-request: the rows already in
                # must not decode for a client that gets a 503
                for h in handles:
                    self.engine.cancel(h["id"],
                                       reason="sibling row shed")
                raise
            out = np.stack([self.engine.wait(h) for h in handles])
            if meta is not None and handles[0].get("_phases"):
                meta["phases"] = handles[0]["_phases"]
            return out
        if self.batcher is not None:
            # validate THIS request up front — a bad one must 400 alone,
            # never poison the batch it would have coalesced into
            for row in prompt:
                self.generator.validate_request(len(row), opts)
            # coalesce with whatever else is in flight; a request's
            # rows share its opts, outputs re-stack to the input shape
            # (enqueue every row BEFORE waiting so one request's rows
            # ride a single batch)
            slots = [self.batcher.submit_async(row, opts)
                     for row in prompt]
            return np.stack([self.batcher.wait(s) for s in slots])
        return self.generator.generate(
            prompt, int(opts.get("max_new", 16)),
            temperature=float(opts.get("temperature", 0.0)),
            seed=int(opts.get("seed", 0)),
            top_k=int(opts.get("top_k", 0)),
            top_p=float(opts.get("top_p", 1.0)))

    # ------------------------------------------------------------ decoding
    def decode_input(self, req):
        """codec 'list' (default): nested lists; codec 'base64': raw
        float32 little-endian bytes with explicit shape (ref restful
        input contract)."""
        codec = req.get("codec", "list")
        if codec == "base64":
            raw = base64.b64decode(req["input"])
            shape = tuple(req.get("shape") or (-1,) + self.input_shape)
            x = np.frombuffer(raw, dtype=np.float32).reshape(shape)
        elif codec == "list":
            x = np.asarray(req["input"], np.float32)
        else:
            raise ValueError("unknown codec %r" % codec)
        if x.ndim == len(self.input_shape):   # single sample
            x = x[None]
        expect = x.shape[1:]
        if tuple(expect) != self.input_shape and \
                int(np.prod(expect)) != int(np.prod(self.input_shape)):
            raise ValueError("input shape %s incompatible with %s"
                             % (expect, self.input_shape))
        return x.reshape((len(x),) + self.input_shape)


#: the fleet READY handshake: a replica process announces its bound
#: port on stdout with this prefix, and whoever spawned it (a pod
#: agent, tools/chaos_common.spawn_ready) reads the line to learn
#: where to register it.  One spelling everywhere — the agent, the
#: chaos harnesses and `--serve` must not drift.
READY_LINE = "REPLICA_READY"


def announce_ready(api, force=False, stream=None):
    """Print the fleet READY handshake line for a started
    :class:`RESTfulAPI` (``REPLICA_READY port=<p> pid=<pid>``).  By
    default it only fires when ``VELES_TPU_REPLICA_ANNOUNCE`` is set
    in the environment — the pod agent sets it on every replica it
    spawns, so any serving command (``python -m veles_tpu ...
    --serve 0``) becomes a fleet replica without a dedicated entry
    point; pass ``force=True`` for dedicated replica entries.
    Returns True iff the line was printed."""
    if not force and not os.environ.get("VELES_TPU_REPLICA_ANNOUNCE"):
        return False
    print("%s port=%d pid=%d" % (READY_LINE, api.port, os.getpid()),
          file=stream if stream is not None else sys.stdout,
          flush=True)
    return True


def parse_ready_line(line):
    """``{"port": int, "pid": int|None}`` for a READY handshake line,
    or None when the line is not one (startup chatter is expected —
    callers scan until the first match)."""
    if not line or not line.lstrip().startswith(READY_LINE):
        return None
    out = {"port": None, "pid": None}
    for tok in line.split():
        for key in ("port", "pid"):
            if tok.startswith(key + "="):
                try:
                    out[key] = int(tok.split("=", 1)[1])
                except ValueError:
                    pass
    return out if out["port"] is not None else None


def install_sigterm_drain(api, exit_code=0, grace_s=None,
                          on_drained=None):
    """SIGTERM → graceful drain for a standalone serve process: stop
    admission, finish in-flight, exit ``exit_code`` — the same
    lifecycle a fleet replica walks, instead of the bare PR 5
    crashdump-and-die.  ``grace_s`` caps the wait (default: the
    ``drain_timeout_ms`` knob plus slack).  Must run on the main
    thread (signal API); returns the previous handler.

    The handler itself only *starts* the drain (signal context must
    stay tiny); a waiter thread watches for drained, runs
    ``on_drained`` (e.g. a flight dump — atexit hooks do NOT survive
    the ``os._exit``), stops the endpoint, and ``os._exit``\\ s so the
    exit status is 0 no matter what non-daemon machinery the
    embedding process runs."""
    import signal

    from veles_tpu.config import root
    if grace_s is None:
        grace_s = float(root.common.serve.get(
            "drain_timeout_ms", 30000)) / 1e3 + 5.0

    def _waiter():
        api.wait_drained(timeout=grace_s)
        if on_drained is not None:
            try:
                on_drained()
            except Exception:   # noqa: BLE001 — exiting anyway
                pass
        try:
            api.stop()
        except Exception:   # noqa: BLE001 — exiting anyway
            pass
        os._exit(exit_code)

    def on_sigterm(signum, frame):
        flight.record("serve.sigterm_drain", pid=os.getpid())
        api.drain(reason="SIGTERM")
        threading.Thread(target=_waiter, name="VelesSigtermDrain",
                         daemon=True).start()

    return signal.signal(signal.SIGTERM, on_sigterm)
