"""The device's side of the serving loop, measured inside the program
(docs/services.md "Request tracing", PERF.md section 3): each dispatch's
landing is stamped EXACT where the host was blocked when the device
finished it and late otherwise; its device time is kept only where both
ends were seen; and a call whose first work went onto an empty device
queue is counted ``drained``, charged to the host phase that first saw
the queue empty.  Three per-layer readers carry the numbers to the
benchmark."""

import importlib.util
import json
import os
import time

import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.models import generate, zoo
from veles_tpu.models.generate import (ContinuousBatcher, LMGenerator,
                                       PagedContinuousBatcher)
from veles_tpu.models.standard_workflow import StandardWorkflow
from veles_tpu.services.restful import ContinuousEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: how long a tick "runs" on the slow device below: a report is ready to
#: ``generate._ready`` only this long after its dispatch
DEVICE_S = 0.2


@pytest.fixture(scope="module")
def lm():
    prng.seed_all(31)
    vocab, t = 13, 48
    r = np.random.RandomState(5)
    toks = ((np.arange(t)[None, :] * 2 + r.randint(0, 4, 96)[:, None])
            % vocab).astype(np.int32)
    loader = FullBatchLoader(None, data=toks, labels=toks,
                             minibatch_size=48, class_lengths=[0, 48, 48])
    wf = StandardWorkflow(
        layers=zoo.transformer_lm(vocab_size=vocab, d_model=32, n_heads=4,
                                  n_layers=2, lr=5e-3, dropout=0.0),
        loader=loader, loss="lm", decision_config={"max_epochs": 1},
        name="tick-landings-lm")
    wf.initialize()
    return LMGenerator(wf.trainer, max_len=t), toks


@pytest.fixture
def slow_device(monkeypatch):
    """``slow_device(cb)``: ``cb``'s tick reports are ready to
    ``generate._ready`` only ``DEVICE_S`` after their dispatch (the
    host's own waits return at once on the CPU), so a phase of the host
    drains the queue only where it is given that long."""
    born, real = {}, generate._ready

    def ready(array):
        t = born.get(id(array))
        return real(array) if t is None \
            else time.perf_counter() >= t[0] + DEVICE_S

    monkeypatch.setattr(generate, "_ready", ready)

    def attach(cb):
        tick = cb._tick

        def timed(st):
            st = tick(st)
            born[id(cb._report)] = (time.perf_counter(), cb._report)
            return st

        cb._tick = timed
        return cb

    return attach


class _SlowList(list):
    """A request's token list whose appends take longer than a tick."""

    def extend(self, more):
        time.sleep(1.5 * DEVICE_S)
        super().extend(more)


class _SlowLists(dict):
    def __setitem__(self, rid, tokens):
        super().__setitem__(rid, _SlowList(tokens))


def _drive(cb, requests):
    rids = [cb.submit(p, n) for p, n in requests]
    ticks = []
    while not cb.idle():
        cb.tick()
        ticks.append(cb.last_tick)
        assert len(ticks) < 500
    return [cb.pop_result(r) for r in rids], ticks


def _settle(eng, handles):
    for h in handles:
        ContinuousEngine.wait(h)
    deadline = time.monotonic() + 60
    while not eng.cb.idle() and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.1)          # the last iteration's record is appended


def _requests(toks, n=2, plen=8, max_new=6):
    return [(toks[i, :plen].tolist(), max_new) for i in range(n)]


# ------------------------------------------- (a) the phase that drained it
def test_a_delay_in_the_engines_delivery_drains_there(lm, slow_device):
    """``engine.deliver`` holds the host past the tick in flight: every
    call that follows with work finds the queue empty, first seen at its
    own entry."""
    gen, toks = lm
    eng = ContinuousEngine(gen, slots=2)
    try:
        slow_device(eng.cb)
        active = eng.cb.active_requests

        def slow():             # the deliver phase asks it once a tick
            time.sleep(1.5 * DEVICE_S)
            return active()

        eng.cb.active_requests = slow
        _settle(eng, [eng.submit_async(p, n) for p, n in _requests(toks)])
        ring = eng.tick_records()
        drained = [t for t in ring if t["drained"]]
        assert len(drained) >= 3
        assert {t["drained_in"] for t in drained} == {"engine"}
        # first sight at the call's entry, the enqueue a little later
        assert all(0 <= t["drained_s"] < DEVICE_S for t in drained)
        m = eng.metrics()
        assert m["drained_by"] == {"engine": len(drained)}
        assert m["drained_share"] > 0.5
    finally:
        eng.stop()


@pytest.mark.parametrize("make", [
    lambda gen: ContinuousBatcher(gen, slots=2),
    lambda gen: PagedContinuousBatcher(gen, slots=2, block=4,
                                       pool_tokens=96),
], ids=["dense", "paged"])
def test_a_delay_in_the_emission_drains_there(lm, slow_device, make):
    gen, toks = lm
    cb = slow_device(make(gen))
    cb._partials = _SlowLists()
    got, ticks = _drive(cb, _requests(toks, max_new=4))
    assert got == [gen.generate(np.asarray([p], np.int32), n)[0].tolist()
                   for p, n in _requests(toks, max_new=4)]
    drained = [t for t in ticks if t["drained"]]
    # every call after the first read emits, and all but the tail's
    # successor enqueue behind it
    assert len(drained) >= 2
    assert {t["drained_in"] for t in drained} == {"emit"}


@pytest.mark.parametrize("pool", [{}, {"paged_block": 4,
                                       "pool_tokens": 96}],
                         ids=["dense", "paged"])
def test_no_delay_no_drain(lm, slow_device, pool):
    """The host's phases, each far shorter than the tick, never let the
    queue run dry: ``drained_share`` 0 over a steady run."""
    gen, toks = lm
    eng = ContinuousEngine(gen, slots=2, **pool)
    try:
        slow_device(eng.cb)
        _settle(eng, [eng.submit_async(p, n)
                      for p, n in _requests(toks, max_new=10)])
        ring = eng.tick_records()
        assert len(ring) >= 10 and not any(t["drained"] for t in ring)
        m = eng.metrics()
        assert m["drained_share"] == 0.0 and m["drained_by"] == {}
    finally:
        eng.stop()


# --------------------------------------- (b) a late landing stores nothing
def test_a_late_landing_stores_no_device_time(lm, monkeypatch):
    """The device always done before the host waits: no landing is
    exact, so no dispatch has a device time, every pass's ``seconds`` is
    None, and the engine's prefill rate is never touched."""
    monkeypatch.setattr(generate, "_ready", lambda array: True)
    gen, toks = lm
    eng = ContinuousEngine(gen, slots=2, prefill_segment=8)
    try:
        events = []
        note = eng.cb.prefill_observer
        eng.cb.prefill_observer = lambda ev: (events.append(ev), note(ev))
        _settle(eng, [eng.submit_async(toks[i, :plen].tolist(), 4)
                      for i, plen in enumerate((30, 20))])
        segments = [e for e in events if e["kind"] == "segment"]
        assert segments and all(e["seconds"] is None for e in segments)
        ring = eng.tick_records()
        assert all(t["device_s"] is None for t in ring)
        assert all(s is None for t in ring for _, s in t["passes"])
        assert sum(t["unseen"] for t in ring) == \
            sum(t["landed"] for t in ring) > 0
        m = eng.metrics()
        assert m["prefill_segments_total"] == len(segments)
        assert m["prefill_ms_per_tok"] == 0.0
        assert m["unseen_share"] == 1.0
        assert m["p50_tick_device_ms"] is None
        assert m["p50_pass_device_ms"] is None
    finally:
        eng.stop()


def test_a_pass_without_seconds_leaves_the_prefill_rate(lm):
    gen, _ = lm
    eng = ContinuousEngine(gen, slots=2, prefill_segment=8)
    try:
        eng._note_prefill({"kind": "segment", "rid": -1, "tokens": 8,
                           "seconds": 0.004})
        assert eng.metrics()["prefill_ms_per_tok"] == 0.5
        eng._note_prefill({"kind": "segment", "rid": -1, "tokens": 8,
                           "seconds": None})
        m = eng.metrics()
        assert m["prefill_ms_per_tok"] == 0.5
        assert m["prefill_segments_total"] == 2
        assert m["prefill_tokens_total"] == 16
    finally:
        eng.stop()


def test_an_exact_pass_feeds_the_prefill_rate(lm, monkeypatch):
    """The contrast to the late case: a device never done before the
    wait gives every pass its device time, and the median of the passes
    of ``prefill_segment`` tokens reads."""
    monkeypatch.setattr(generate, "_ready", lambda array: False)
    gen, toks = lm
    eng = ContinuousEngine(gen, slots=2, prefill_segment=8)
    try:
        _settle(eng, [eng.submit_async(toks[i, :plen].tolist(), 4)
                      for i, plen in enumerate((30, 20))])
        ring = eng.tick_records()
        passes = [p for t in ring for p in t["passes"]]
        assert {n for n, _ in passes} >= {8}
        assert all(s > 0 for _, s in passes)
        m = eng.metrics()
        assert m["prefill_ms_per_tok"] > 0
        assert m["p50_pass_device_ms"] > 0
        assert m["p50_tick_device_ms"] > 0 and m["unseen_share"] == 0.0
    finally:
        eng.stop()


# -------------------------------------- (c) exact device times add up
@pytest.mark.parametrize("make", [
    lambda gen: ContinuousBatcher(gen, slots=2),
    lambda gen: PagedContinuousBatcher(gen, slots=2, block=4,
                                       pool_tokens=96, prefill_segment=8),
    lambda gen: ContinuousBatcher(gen, slots=2, prefill_segment=4,
                                  prefill_tick_budget=16),
], ids=["dense", "paged_segmented", "dense_budget_16"])
def test_exact_device_times_are_positive_and_fit_in_the_run(lm, make):
    """On the CPU's own device: each exact ``device_s`` is positive, the
    intervals never overlap, so their sum is at most the run's wall
    time; every landing is either seen or counted unseen."""
    gen, toks = lm
    cb = make(gen)
    requests = [(toks[i, :plen].tolist(), 6)
                for i, plen in enumerate((30, 5, 21, 12))]
    t0 = time.perf_counter()
    _, ticks = _drive(cb, requests)
    wall = time.perf_counter() - t0
    seen = [t["device_s"] for t in ticks if t["device_s"] is not None] \
        + [s for t in ticks for _, s in t["passes"] if s is not None]
    assert seen and all(s > 0 for s in seen)
    assert sum(seen) <= wall
    assert len(seen) + sum(t["unseen"] for t in ticks) == \
        sum(t["landed"] for t in ticks)
    assert sum(t["cold"] for t in ticks) == 1


# ------------------------------------------------------- (d) the readers
READERS = {"tick_device_ms_p50": ("p50_tick_device_ms", 11.5, 11.5),
           "pass_device_ms_p50": ("p50_pass_device_ms", 88.25, 88.25),
           "drained_pct": ("drained_share", 0.0375, 3.75)}

#: an ``engine.metrics()`` of PR 38's program: no landing stamps
PR38_ENGINE = {"served": 976, "ticks_total": 2048, "slots": 32,
               "p50_tick_ms": 10.77, "p50_tick_wait_ms": 5.9,
               "p50_tick_host_ms": 4.97, "tick_rows_mean": 31.7,
               "prefill_ms_per_tok": 0.0}


def _reader(name):
    path = os.path.join(ROOT, "benchmarks", "readers", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_finds_nothing_on_the_parent(name):
    read = _reader(name)
    assert read({"engine": dict(PR38_ENGINE)}) is None
    assert read({}) is None and read({"engine": {}}) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_reads_its_key(name):
    key, value, expected = READERS[name]
    read = _reader(name)
    engine = dict(PR38_ENGINE, **{key: value})
    assert read({"engine": engine}) == pytest.approx(expected)
    # no tick yet, or nothing of that kind seen: nothing to read
    assert read({"engine": dict(engine, ticks_total=0)}) is None
    assert read({"engine": dict(engine, **{key: None})}) is None


def test_the_benchmark_lists_the_three_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    serve = ["cgpt13.serve_closed", "keye30.serve_long",
             "cmdaplus.serve_mixed", "brumby14.serve_decode",
             "qwen3next.serve_long"]
    got = {m["name"]: m for m in bench["per_layer"] if m["name"] in READERS}
    assert got == {
        "tick_device_ms_p50": {
            "name": "tick_device_ms_p50", "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "batcher_tick",
            "moves": "out_tokens_per_s", "workloads": serve},
        "pass_device_ms_p50": {
            "name": "pass_device_ms_p50", "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "batcher_tick",
            "moves": "out_tokens_per_s", "workloads": serve[1:]},
        "drained_pct": {
            "name": "drained_pct", "unit": "%", "better": "lower",
            "source": "program_counter", "layer": "device",
            "moves": "out_tokens_per_s", "workloads": serve}}
