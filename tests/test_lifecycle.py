"""Serving survival layer (services.lifecycle + ContinuousEngine):
engine-side cancellation frees slots AND paged-KV blocks mid-decode,
deadlines are enforced (never admitted / cancelled mid-decode),
streaming queues are bounded, the SLO shedder opens and closes around
the threshold, disconnects leak nothing, and an engine tick fault is
survived.  One tiny untrained transformer is shared module-wide — the
suite tests lifecycle plumbing, not the model."""

import json
import socket
import struct
import threading
import time

import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.config import root
from veles_tpu.services.lifecycle import (BoundedStream, DeadlineExceeded,
                                          RequestCancelled, ShedError,
                                          SloShedder)

T, VOCAB = 16, 11
PROMPT = [1, 2, 3, 4, 5]


@pytest.fixture(scope="module")
def gen():
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.models import zoo
    from veles_tpu.models.generate import LMGenerator
    from veles_tpu.models.standard_workflow import StandardWorkflow

    prng.seed_all(31)
    toks = np.random.RandomState(5).randint(
        0, VOCAB, (8, T)).astype(np.int32)
    wf = StandardWorkflow(
        layers=zoo.transformer_lm(vocab_size=VOCAB, d_model=16,
                                  n_heads=2, n_layers=1, dropout=0.0),
        loader=FullBatchLoader(None, data=toks, labels=toks,
                               minibatch_size=4,
                               class_lengths=[0, 4, 4]),
        loss="lm", decision_config={"max_epochs": 1},
        name="lifecycle-serve")
    wf.initialize()
    return LMGenerator(wf.trainer, max_len=T)


@pytest.fixture
def serve_cfg():
    """Snapshot/restore the process-global serve config so per-test
    knob changes never leak into other tests."""
    keys = ("slo_queue_wait_ms", "default_deadline_ms",
            "stream_queue_chunks", "stream_overflow",
            "stream_stall_timeout_ms", "shed_close_fraction")
    prev = {k: root.common.serve.get(k) for k in keys}
    try:
        yield root.common.serve
    finally:
        for k, v in prev.items():
            setattr(root.common.serve, k, v)


def _engine(gen, **kw):
    from veles_tpu.services.restful import ContinuousEngine
    return ContinuousEngine(gen, **kw)


def _wait_idle(eng, timeout=60.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        m = eng.metrics()
        if m["queued"] == 0 and m["in_flight"] == 0:
            return True
        time.sleep(0.02)
    return False


def _assert_no_leaks(eng):
    leaks = eng.leak_check()
    for key in ("ingress", "records", "open_requests",
                "pending_cancels", "slots_busy"):
        assert leaks[key] == 0, leaks
    assert leaks.get("kv_blocks_leaked", 0) == 0, leaks
    assert leaks["engine_thread_alive"]


class TestBoundedStream:
    def test_drop_oldest_bounds_and_counts(self):
        bs = BoundedStream(capacity=3, overflow="drop_oldest")
        for i in range(7):
            assert bs.push(("tokens", [i]))
        assert bs.qsize() == 3
        assert bs.dropped == 4
        # survivors are the NEWEST chunks
        assert [bs.get()[1] for _ in range(3)] == [[4], [5], [6]]

    def test_block_mode_refuses_without_sleeping(self):
        bs = BoundedStream(capacity=2, overflow="block")
        assert bs.push(("tokens", [0]))
        assert bs.push(("tokens", [1]))
        t0 = time.monotonic()
        assert not bs.push(("tokens", [2]))      # full: refused, and
        assert time.monotonic() - t0 < 0.5      # NEVER sleeps (the
        # producer is the engine thread every request's decode shares)
        assert bs.dropped == 0                   # nothing discarded
        bs.get()
        assert bs.push(("tokens", [2]))          # space freed

    def test_terminal_never_dropped_never_blocked(self):
        bs = BoundedStream(capacity=1, overflow="block")
        bs.push(("tokens", [0]))
        t0 = time.monotonic()
        bs.put_terminal(("done", [0, 1]))        # instant despite full
        assert time.monotonic() - t0 < 1.0
        assert bs.get()[1] == [0]
        assert bs.get()[0] == "done"
        # closed: producers no-op instead of growing the queue
        assert bs.push(("tokens", [9]))
        assert bs.qsize() == 0

    def test_invalid_overflow_rejected(self):
        with pytest.raises(ValueError):
            BoundedStream(overflow="explode")


class TestSloShedder:
    def test_opens_and_closes_with_hysteresis(self):
        sh = SloShedder(100.0, close_fraction=0.5)
        assert sh.enabled and not sh.should_shed()
        assert sh.update(head_wait_ms=50.0) is None
        assert sh.update(head_wait_ms=150.0) == "open"
        assert sh.should_shed()
        # between close and open thresholds: stays open (hysteresis)
        assert sh.update(head_wait_ms=80.0) is None
        assert sh.should_shed()
        assert sh.update(head_wait_ms=10.0) == "close"
        assert not sh.should_shed()
        assert sh.open_total == 1

    def test_admitted_wait_also_opens(self):
        sh = SloShedder(100.0)
        sh.note_admit(250.0)
        assert sh.update(head_wait_ms=0.0) == "open"

    def test_disabled_never_sheds(self):
        sh = SloShedder(0)
        assert not sh.enabled
        sh.note_admit(1e9)
        assert sh.update(head_wait_ms=1e9) is None
        assert not sh.should_shed()

    def test_shed_counts_and_retry_after(self):
        sh = SloShedder(2000.0)
        ra = sh.shed()
        assert ra == pytest.approx(2.0)
        assert sh.shed_total == 1
        assert sh.status()["state"] == "closed"

    def test_retry_after_scales_with_overshoot(self):
        """A deeply overloaded replica pushes clients (and the fleet
        router) away for longer: the Retry-After hint scales with the
        measured queue-wait overshoot, capped."""
        sh = SloShedder(1000.0, overshoot_cap=8.0)
        assert sh.retry_after_s() == pytest.approx(1.0)  # no measure yet
        sh.update(head_wait_ms=500.0)          # under the SLO: floor
        assert sh.retry_after_s() == pytest.approx(1.0)
        sh.update(head_wait_ms=3500.0)         # 3.5x the SLO
        assert sh.retry_after_s() == pytest.approx(3.5)
        sh.update(head_wait_ms=100000.0)       # pathological: capped
        assert sh.retry_after_s() == pytest.approx(8.0)
        sh.update(head_wait_ms=200.0)          # drained: back to floor
        assert sh.retry_after_s() == pytest.approx(1.0)


class TestCancel:
    def test_cancel_mid_decode_frees_slot_and_kv_blocks(self, gen,
                                                        serve_cfg):
        eng = _engine(gen, slots=2, paged_block=4, pool_tokens=64)
        try:
            pool_blocks = eng.cb.pool_blocks
            eng.wait(eng.submit_async(PROMPT, 4))       # warmup/compile
            handle, it = eng.stream_open(PROMPT, 10)
            first = next(it)                            # admitted + decoding
            assert first
            assert eng.cancel(handle["id"], reason="test cancel")
            with pytest.raises(RequestCancelled):
                for _ in it:
                    pass
            assert _wait_idle(eng)
            assert eng.cb.free_blocks() == pool_blocks  # blocks freed
            _assert_no_leaks(eng)
            m = eng.metrics()
            assert m["cancelled_total"] == 1
            # the pool still serves fresh work after the cancel
            out = eng.wait(eng.submit_async(PROMPT, 3))
            assert len(out) == len(PROMPT) + 3
        finally:
            eng.stop()

    def test_cancel_queued_request_before_admission(self, gen,
                                                    serve_cfg):
        eng = _engine(gen, slots=1)
        try:
            eng.wait(eng.submit_async(PROMPT, 2))       # warmup
            blocker = eng.submit_async(PROMPT, 10)      # owns the slot
            queued = eng.submit_async(PROMPT, 10)       # waits behind it
            assert eng.cancel(queued["id"])
            with pytest.raises(RequestCancelled):
                eng.wait(queued)
            assert queued["admit_ts"] is None           # never admitted
            assert len(eng.wait(blocker)) == len(PROMPT) + 10
            assert _wait_idle(eng)
            _assert_no_leaks(eng)
        finally:
            eng.stop()

    def test_cancel_unknown_id_is_false(self, gen, serve_cfg):
        eng = _engine(gen, slots=1)
        try:
            assert eng.cancel(12345) is False
        finally:
            eng.stop()


class TestDeadline:
    def test_expired_request_never_admitted(self, gen, serve_cfg):
        eng = _engine(gen, slots=1)
        try:
            eng.wait(eng.submit_async(PROMPT, 2))       # warmup
            blocker = eng.submit_async(PROMPT, 10)
            doomed = eng.submit_async(PROMPT, 10, deadline_ms=1)
            with pytest.raises(DeadlineExceeded):
                eng.wait(doomed)
            assert doomed["admit_ts"] is None
            assert len(eng.wait(blocker)) == len(PROMPT) + 10
            assert _wait_idle(eng)
            _assert_no_leaks(eng)
            assert eng.metrics()["deadline_expired_total"] == 1
        finally:
            eng.stop()

    def test_deadline_event_in_flight_ring(self, gen, serve_cfg):
        from veles_tpu.telemetry import flight
        eng = _engine(gen, slots=1)
        try:
            eng.wait(eng.submit_async(PROMPT, 2))
            blocker = eng.submit_async(PROMPT, 10)
            doomed = eng.submit_async(PROMPT, 4, deadline_ms=1)
            with pytest.raises(DeadlineExceeded):
                eng.wait(doomed)
            eng.wait(blocker)
            kinds = [e["kind"] for e in flight.recorder.snapshot()]
            assert "serve.deadline" in kinds
        finally:
            eng.stop()


class TestBoundedStreamOnEngine:
    def test_slow_consumer_bounded_and_result_authoritative(
            self, gen, serve_cfg):
        serve_cfg.stream_queue_chunks = 2
        serve_cfg.stream_overflow = "drop_oldest"
        eng = _engine(gen, slots=1)
        try:
            eng.wait(eng.submit_async(PROMPT, 2))       # warmup
            want = eng.wait(eng.submit_async(PROMPT, 10)).tolist()
            handle, it = eng.stream_open(PROMPT, 10)
            chunks = [next(it)]                         # start, then stall
            assert _wait_idle(eng)                      # decode finished
            assert handle["stream_q"].qsize() <= 3      # bounded (+done)
            assert handle["stream_q"].dropped > 0
            for c in it:                                # drain remainder
                chunks.append(c)
            # drops cost incremental granularity, NEVER tokens: the
            # drain yields only contiguous progress and reconstructs
            # everything after the first gap from the terminal payload
            assert PROMPT + [t for c in chunks for t in c] == want
            assert list(handle["out"]) == want
            _assert_no_leaks(eng)
            assert eng.metrics()["stream_dropped_chunks"] > 0
        finally:
            eng.stop()

    def test_block_mode_stall_cancels_slowloris(self, gen, serve_cfg):
        serve_cfg.stream_queue_chunks = 2
        serve_cfg.stream_overflow = "block"
        serve_cfg.stream_stall_timeout_ms = 100
        eng = _engine(gen, slots=1)
        try:
            eng.wait(eng.submit_async(PROMPT, 2))       # warmup
            # throttle decode below the stall budget: push never
            # blocks, so an unthrottled 10-token decode would finish
            # before the 100 ms no-progress window can expire
            orig = eng.cb.tick

            def slow_tick():
                time.sleep(0.03)
                return orig()

            eng.cb.tick = slow_tick
            handle, it = eng.stream_open(PROMPT, 10)
            next(it)                 # read ONE chunk, then stop reading
            deadline = time.monotonic() + 30
            while handle["error"] is None \
                    and time.monotonic() < deadline:
                time.sleep(0.05)
            assert isinstance(handle["error"], RequestCancelled)
            assert _wait_idle(eng)
            _assert_no_leaks(eng)
        finally:
            eng.stop()


class TestShedderOnEngine:
    def test_sheds_under_overload_and_recovers(self, gen, serve_cfg):
        serve_cfg.slo_queue_wait_ms = 20
        eng = _engine(gen, slots=1)
        try:
            eng.wait(eng.submit_async(PROMPT, 2))       # warmup
            # a burst of instant submissions all precedes the breach —
            # the valve reacts to the MEASURED wait, so overload the
            # pool, wait for the head-of-line wait to cross the SLO,
            # and only then probe admission
            handles = [eng.submit_async(PROMPT, 11) for _ in range(25)]
            deadline = time.monotonic() + 30
            while not eng._shed.should_shed() \
                    and time.monotonic() < deadline:
                time.sleep(0.002)
            assert eng._shed.should_shed(), \
                "overload never opened the shedder"
            assert eng.metrics()["shed_state"] == "open"
            shed = 0
            for _ in range(3):
                try:
                    handles.append(eng.submit_async(PROMPT, 5))
                except ShedError as e:
                    shed += 1
                    assert e.retry_after_s >= 1.0
            assert shed > 0, "open valve admitted every probe"
            for h in handles:                           # admitted work OK
                assert len(eng.wait(h)) > len(PROMPT)
            deadline = time.monotonic() + 30
            while eng.metrics()["shed_state"] != "closed" \
                    and time.monotonic() < deadline:
                time.sleep(0.05)
            assert eng.metrics()["shed_state"] == "closed"
            # valve closed: fresh work admits again
            assert len(eng.wait(eng.submit_async(PROMPT, 2))) == \
                len(PROMPT) + 2
            assert eng.metrics()["shed_total"] == shed
            assert _wait_idle(eng)
            _assert_no_leaks(eng)
        finally:
            eng.stop()


class TestEngineFaultRecovery:
    def test_tick_fault_evicts_resets_and_keeps_serving(self, gen,
                                                        serve_cfg):
        eng = _engine(gen, slots=2, paged_block=4, pool_tokens=64)
        try:
            pool_blocks = eng.cb.pool_blocks
            eng.wait(eng.submit_async(PROMPT, 2))       # warmup
            orig = eng.cb.tick
            state = {"armed": True}

            def chaos_tick():
                if state["armed"]:
                    state["armed"] = False
                    raise RuntimeError("injected tick fault")
                return orig()

            eng.cb.tick = chaos_tick
            victim = eng.submit_async(PROMPT, 6)
            with pytest.raises(RuntimeError, match="engine fault"):
                eng.wait(victim)
            # the pool reset freed everything and fresh work succeeds
            out = eng.wait(eng.submit_async(PROMPT, 3))
            assert len(out) == len(PROMPT) + 3
            assert eng.cb.free_blocks() == pool_blocks
            assert _wait_idle(eng)
            _assert_no_leaks(eng)
            assert eng.metrics()["engine_faults"] == 1
        finally:
            eng.stop()


class TestDisconnectOverRest:
    def test_mid_stream_rst_frees_slot_blocks_and_serves_on(
            self, gen, serve_cfg):
        from veles_tpu.services.restful import RESTfulAPI
        api = RESTfulAPI(lambda xx: xx, (T,), port=0, generator=gen,
                         continuous_slots=2, paged_block=4,
                         pool_tokens=64)
        api.start()
        try:
            eng = api.engine
            pool_blocks = eng.cb.pool_blocks
            eng.wait(eng.submit_async(PROMPT, 2))       # warmup
            # throttle decode so the RST lands MID-decode: on an
            # unthrottled CPU the whole 10-token stream fits in the
            # loopback buffer before the client even reads chunk 1,
            # and the server would never see the broken pipe
            orig = eng.cb.tick

            def slow_tick():
                time.sleep(0.03)
                return orig()

            eng.cb.tick = slow_tick
            body = json.dumps({"input": PROMPT,
                               "generate": {"max_new": 10,
                                            "stream": True}}).encode()
            sock = socket.create_connection(
                ("127.0.0.1", api.port), timeout=30)
            sock.sendall(
                b"POST /service HTTP/1.1\r\nHost: x\r\n"
                b"Content-Type: application/json\r\n"
                b"Content-Length: %d\r\n\r\n%s" % (len(body), body))
            buf = b""
            while b"\r\n\r\n" not in buf or b"tokens" not in buf:
                chunk = sock.recv(256)
                assert chunk, "connection closed before first tokens"
                buf += chunk
            # vanish rudely: RST so the server's next write fails
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                            struct.pack("ii", 1, 0))
            sock.close()
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if eng.metrics()["cancelled_total"] >= 1 \
                        and _wait_idle(eng, timeout=1):
                    break
                time.sleep(0.05)
            assert eng.metrics()["cancelled_total"] >= 1, \
                "disconnect never cancelled the request"
            assert eng.cb.free_blocks() == pool_blocks
            _assert_no_leaks(eng)
            # and the endpoint still serves
            import urllib.request
            req = urllib.request.Request(
                "http://127.0.0.1:%d/service" % api.port,
                data=json.dumps({"input": PROMPT,
                                 "generate": {"max_new": 2}}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                assert json.loads(resp.read())["result"]
        finally:
            api.stop()


class TestShedOverRest:
    def test_503_with_retry_after(self, gen, serve_cfg):
        import urllib.error
        import urllib.request

        from veles_tpu.services.restful import RESTfulAPI
        serve_cfg.slo_queue_wait_ms = 10
        api = RESTfulAPI(lambda xx: xx, (T,), port=0, generator=gen,
                         continuous_slots=1)
        api.start()
        try:
            eng = api.engine
            eng.wait(eng.submit_async(PROMPT, 2))       # warmup
            # widen the overload window past the HTTP round-trip: an
            # unthrottled warm pool can drain a small backlog (and
            # close the valve) before the probe request even connects
            orig = eng.cb.tick

            def slow_tick():
                time.sleep(0.005)
                return orig()

            eng.cb.tick = slow_tick
            handles = [eng.submit_async(PROMPT, 11) for _ in range(16)]
            deadline = time.monotonic() + 30
            while not eng._shed.should_shed() \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            assert eng._shed.should_shed(), "overload never shed"
            req = urllib.request.Request(
                "http://127.0.0.1:%d/service" % api.port,
                data=json.dumps({"input": PROMPT,
                                 "generate": {"max_new": 2}}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=60)
            assert ei.value.code == 503
            assert int(ei.value.headers["Retry-After"]) >= 1
            for h in handles:
                eng.wait(h)
            assert _wait_idle(eng)
            _assert_no_leaks(eng)
        finally:
            api.stop()


class TestSpecMixedEvent:
    def test_one_shot_informational_event_on_sampled_request(
            self, gen, serve_cfg):
        """The pool-wide `serve.spec_degraded` cliff event is RETIRED
        (speculation routes per row now); a sampled request entering
        a speculative pool emits the downgraded one-shot
        `serve.spec_mixed` informational event instead — and never
        the old degraded one."""
        from veles_tpu.telemetry import flight
        eng = _engine(gen, slots=2, speculative_k=2)
        try:
            eng.cb.tick = lambda: 0        # no decode needed: the
            # event fires at submit, and compiling the spec tick here
            # would buy the test nothing
            def count(kind):
                return sum(1 for e in flight.recorder.snapshot()
                           if e["kind"] == kind)
            before = count("serve.spec_mixed")
            # lint-ok: VC954 — retired event; this gate asserts it
            # never comes back, so nothing is supposed to emit it
            degraded = count("serve.spec_degraded")
            eng.submit_async(PROMPT, 2, temperature=0.7)
            eng.submit_async(PROMPT, 2, temperature=0.9)
            assert count("serve.spec_mixed") - before == 1  # one-shot
            assert count("serve.spec_degraded") == degraded  # retired
        finally:
            eng.stop()


class TestFusedSublaneFallback:
    def test_small_blocks_are_an_error_when_mosaic_compiles(
            self, gen, monkeypatch):
        """Construction-time guard: on a REAL TPU backend (interpret
        off) a paged_block below Mosaic's sublane minimum for the pool
        dtype cannot compile — the decode kernel's K/V tile is one
        block — and construction raises naming the reason."""
        import veles_tpu.ops.pallas as ops_pallas
        from veles_tpu.models.generate import PagedContinuousBatcher
        from veles_tpu.ops.pallas import mosaic_sublane_min
        assert mosaic_sublane_min(np.float32) == 8
        assert mosaic_sublane_min("bfloat16") == 16
        assert mosaic_sublane_min(np.int8) == 32
        monkeypatch.setattr(ops_pallas, "autodetect_interpret",
                            lambda i: False)   # pretend: real TPU
        dtype_min = mosaic_sublane_min(gen._model_dtype())
        below = max(1, dtype_min // 2)
        with pytest.raises(ValueError, match="sublane minimum"):
            PagedContinuousBatcher(gen, slots=2, block=below,
                                   pool_tokens=T * 2)
        cb2 = PagedContinuousBatcher(gen, slots=2, block=dtype_min,
                                     pool_tokens=T * 2)
        assert cb2.fused                       # at the minimum: fine

    def test_interpret_mode_keeps_fused(self, gen):
        from veles_tpu.models.generate import PagedContinuousBatcher
        cb = PagedContinuousBatcher(gen, slots=2, block=4,
                                    pool_tokens=T * 2)
        assert cb.fused                        # CPU suite: interpret


class TestChaosScaledDown:
    def test_storm_sheds_recovers_and_leaks_nothing(self, gen,
                                                    serve_cfg):
        """The tools/serve_loadtest.py harness at tier-1 scale:
        concurrent streaming clients with mid-stream RSTs, slowloris
        readers, and injected engine faults — afterwards zero leaked
        slots / KV blocks / threads, a shed+recover cycle, and the
        engine serving fresh requests."""
        import tools.serve_loadtest as lt
        serve_cfg.slo_queue_wait_ms = 20
        api = lt.build_api(slots=2, paged_block=4, pool_tokens=96,
                           slo_ms=20, generator=gen)
        # throttle decode so 24 clients over 2 slots provably exceed
        # the 20 ms queue-wait SLO, and ramp the arrivals: on a fast
        # box an unthrottled burst both drains before the valve can
        # open AND submits every client before the first breach is
        # measured, leaving nobody to shed (FaultInjector wraps tick
        # at storm start, so the throttle composes)
        orig = api.engine.cb.tick

        def slow_tick():
            time.sleep(0.02)
            return orig()

        api.engine.cb.tick = slow_tick
        try:
            report = lt.run(clients=24, disconnect=0.3, slowloris=0.1,
                            buffered=0.2, fault_rate=0.03, max_new=10,
                            prompt_len=len(PROMPT), slo_ms=20,
                            slow_delay=0.1, seed=11, api=api,
                            ramp_s=1.0)
        finally:
            api.stop()
        fails = lt.gates(report, expect_shed=True)
        assert not fails, (fails, report)
        assert report["metrics"]["shed_total"] > 0
