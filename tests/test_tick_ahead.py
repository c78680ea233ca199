"""The tick is dispatched ONE AHEAD (docs/services.md "Request tracing"):
a call of ``ContinuousBatcher.tick`` enqueues its own dispatch and THEN
reads the report of the one the previous call enqueued.  Pinned here:
no token differs from the solo continuation in any kind of batcher; a
report is read against the occupancy of its own dispatch; whatever a
frozen row still writes lands in blocks it owns; nothing is lost at the
ends (``idle``, ``run_all``, ``cancel``, ``reset_pool``, a fault); the
``ahead`` count; and a staged pass's ``seconds`` is its own time on the
device, not the tick's it was enqueued behind."""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import build_brumby, build_cmda  # noqa: E402
from veles_tpu import prng  # noqa: E402
from veles_tpu.loader.fullbatch import FullBatchLoader  # noqa: E402
from veles_tpu.models import generate, zoo  # noqa: E402
from veles_tpu.models.generate import (  # noqa: E402
    ContinuousBatcher, LMGenerator, PagedContinuousBatcher)
from veles_tpu.models.standard_workflow import StandardWorkflow  # noqa: E402
from veles_tpu.services.restful import ContinuousEngine  # noqa: E402

MAX_LEN = 48


def _workflow(t=MAX_LEN, epochs=6):
    prng.seed_all(31)
    vocab, n = 13, 96
    r = np.random.RandomState(5)
    toks = ((np.arange(t)[None, :] * 2 + r.randint(0, 4, n)[:, None])
            % vocab).astype(np.int32)
    loader = FullBatchLoader(None, data=toks, labels=toks,
                             minibatch_size=48, class_lengths=[0, 48, 48])
    wf = StandardWorkflow(
        layers=zoo.transformer_lm(vocab_size=vocab, d_model=32, n_heads=4,
                                  n_layers=2, lr=5e-3, dropout=0.0),
        loader=loader, loss="lm", decision_config={"max_epochs": epochs},
        name="tick-ahead-lm")
    wf.initialize()
    wf.run()
    return wf, toks


@pytest.fixture(scope="module")
def lms():
    """One trained tiny model behind three cache dtypes."""
    wf, toks = _workflow()
    return {"f32": LMGenerator(wf.trainer, max_len=MAX_LEN),
            "bf16": LMGenerator(wf.trainer, max_len=MAX_LEN,
                                cache_dtype=jnp.bfloat16),
            "int8": LMGenerator(wf.trainer, max_len=MAX_LEN,
                                cache_dtype="int8"),
            "toks": toks}


def _tiny(name):
    with open(os.path.join(
            ROOT, "benchmarks", "tests", "data", "tiny_" + name,
            "benchmarks", "configs", "tiny-%s.json" % name)) as f:
        return json.load(f)


_BUILT = {}


@pytest.fixture
def built(f32_precision):
    """``built(name)``: the benchmark's tiny window-ring model (which
    holds a SHARE of its experts, so a staged pass hands out counts) or
    its tiny state-layer one, float32 compute, built once."""
    def build(name):
        if name not in _BUILT:
            cfg = _tiny(name)
            if name == "cmda":
                cfg = dict(cfg, num_hidden_layers=4, experts_held=[0, 2],
                           num_experts=2)
            module = {"cmda": build_cmda, "brumby": build_brumby}[name]
            wf = module.build_workflow(cfg, 96, param="float32")
            module.install_weights(wf.trainer, cfg, 2 ** 31 + 7)
            gen = LMGenerator(wf.trainer, max_len=96)
            gen.prefill_min = 4
            _BUILT[name] = (cfg, gen)
        return _BUILT[name]
    return build


def _solo(gen, prompt, max_new, temperature=0.0, seed=0):
    return gen.generate(np.asarray([prompt], np.int32), max_new,
                        temperature=temperature, seed=seed)[0].tolist()


def _drive(cb, requests):
    """Submit ``(prompt, max_new, temperature, seed)`` rows, tick until
    idle; the results and every call's ``last_tick``."""
    rids = [cb.submit(p, n, temperature=t, seed=s)
            for p, n, t, s in requests]
    ticks = []
    while not cb.idle():
        cb.tick()
        ticks.append(cb.last_tick)
        assert len(ticks) < 2000
    return [cb.pop_result(r) for r in rids], ticks


def _mixed(toks):
    """Five requests for two slots: every slot is re-admitted while its
    previous occupant's last report is in flight; different lengths, so
    a stale cursor would cut a stranger short; one sampled row."""
    return [(toks[0, :20].tolist(), 9, 0.0, 0),
            (toks[1, :5].tolist(), 3, 0.0, 0),
            (toks[2, :11].tolist(), 12, 0.8, 7),
            (toks[3, :2].tolist(), 5, 0.0, 0),
            (toks[4, :26].tolist(), 4, 0.0, 0)]


def _paged(**kw):
    return lambda gen: PagedContinuousBatcher(
        gen, slots=2, block=4, pool_tokens=128, **kw)


def _dense(**kw):
    return lambda gen: ContinuousBatcher(gen, slots=2, **kw)


#: name -> (which generator, the batcher)
KINDS = {
    "dense": ("f32", _dense()),
    "dense_ticks_per_dispatch_4": ("f32", _dense(ticks_per_dispatch=4)),
    "dense_speculative": ("f32", _dense(speculative_k=4)),
    "dense_segmented": ("f32", _dense(prefill_segment=4)),
    "dense_prompt_forced": ("f32", _dense(chunked_prefill=False)),
    "paged": ("f32", _paged()),
    "paged_bf16_pool": ("bf16", _paged()),
    "paged_int8_pool": ("int8", _paged()),
    "paged_prefix_cache": ("f32", _paged(prefix_cache=True)),
    "paged_ticks_per_dispatch_4": ("f32", _paged(ticks_per_dispatch=4)),
    "paged_segmented": ("f32", _paged(prefill_segment=4)),
    "paged_segmented_budget_16": ("f32", _paged(prefill_segment=4,
                                                 prefill_tick_budget=16)),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_every_request_is_its_solo_continuation(lms, f32_precision, kind):
    """Same programs, same tokens: greedy and seeded sampling alike."""
    which, make = KINDS[kind]
    gen, cb = lms[which], make(lms[which])
    requests = _mixed(lms["toks"])
    got, ticks = _drive(cb, requests)
    assert got == [_solo(gen, *req) for req in requests]
    assert sum(t["finished"] for t in ticks) == len(requests)
    assert sum(t["admitted"] for t in ticks) == len(requests)
    # drained: nothing in flight, every block back
    assert cb._flying is None and not cb._passes and not cb._partials
    if hasattr(cb, "free_blocks"):
        assert cb.free_blocks() == cb.pool_blocks
        assert cb.prefix_stats() == (0, 0)


@pytest.mark.parametrize("name,segment", [("cmda", 0), ("cmda", 8),
                                          ("brumby", 0), ("brumby", 8)])
def test_ring_and_state_groups_are_the_solo_continuation(built, name,
                                                         segment):
    """A window-ring model (staged passes that count their expert
    pairs: ``_pass_counts``) and a state-layer model, whole prefills and
    passes of 8: five requests through two slots."""
    cfg, gen = built(name)
    rng = np.random.default_rng(11)
    requests = [(rng.integers(0, cfg["vocab_size"], n).tolist(), m, 0.0, 0)
                for n, m in ((30, 9), (5, 14), (21, 4), (2, 6), (40, 11))]
    kw = {"pool_tokens": 256} if name == "cmda" else {}
    cb = PagedContinuousBatcher(gen, slots=2, block=4,
                                prefill_segment=segment, **kw)
    got, ticks = _drive(cb, requests)
    assert got == [_solo(gen, *req) for req in requests]
    # a ring admits every prompt longer than itself in passes
    staged = sum(t["staged_tokens"] for t in ticks)
    assert (staged > 0) == bool(segment or name == "cmda")
    if name == "cmda":
        assert cb._pass_counts and cb.ring_blocks
        # every pass's count was read, a call after its dispatch
        assert sum(t["staged_expert_pairs"] for t in ticks) > 0
        assert cb.blocks_in_use() == (0, 0)
    else:
        assert cb._state_row_bytes and cb.state_in_use() == (0, 0)


# ------------------------------------ a report and its own occupancy
@pytest.mark.parametrize("make", [
    lambda gen: ContinuousBatcher(gen, slots=1),
    lambda gen: PagedContinuousBatcher(gen, slots=1, block=4,
                                       pool_tokens=64),
], ids=["dense", "paged"])
def test_a_stale_report_is_not_read_against_the_slots_new_owner(
        lms, f32_precision, make):
    """One slot.  ``a`` (26 positions) finishes in the report read by
    call N; the dispatch behind it still carries ``a``, frozen at cursor
    25.  Call N + 1 admits ``b`` (8 positions in all) into the slot and
    reads THAT report: judged by the slot's occupancy now, ``b`` would
    look done at a cursor past its total and take a stranger's row."""
    gen, toks = lms["f32"], lms["toks"]
    cb = make(gen)
    a, b = toks[0, :20].tolist(), toks[1, :5].tolist()
    ra, rb = cb.submit(a, 6), cb.submit(b, 3)
    while cb.result(ra) is None:
        cb.tick()
    # a's last report has been read; the dispatch enqueued before that
    # read is in flight, with a in it
    assert cb._flying is not None and cb._flying.req == [ra]
    assert cb.active_requests() == set() and not cb.idle()
    cb.tick()                   # admits b, dispatches, reads the stale one
    assert cb.active_requests() == {rb}
    assert cb.result(rb) is None and cb.partial(rb) == b
    assert cb.last_tick["rows"] == 0 and cb.last_tick["finished"] == 0
    assert cb.last_tick["admitted"] == 1 and cb.last_tick["ahead"] == 1
    cb.run_all()
    assert cb.result(ra) == _solo(gen, a, 6)
    assert cb.result(rb) == _solo(gen, b, 3)


@pytest.mark.parametrize("pool", ["f32", "int8"])
def test_a_frozen_rows_tick_writes_only_blocks_it_owns(lms, f32_precision,
                                                       pool):
    """``a`` reaches its budget in dispatch 3; dispatch 4 is enqueued
    before report 3 is read and still walks ``a`` through its table.
    Every block ``a`` does not own is poisoned before dispatch 4 and is
    the poison, bit for bit, after it; so are the blocks of ``a`` that
    are registered for prefix sharing; the one position written is
    ``a``'s last, in the last block it owns."""
    gen, toks = lms[pool], lms["toks"]
    cb = PagedContinuousBatcher(gen, slots=2, block=4, pool_tokens=128,
                                prefix_cache=True)
    a = toks[0, :17].tolist()
    ra = cb.submit(a, 3)                      # positions 0..19: 5 blocks
    for _ in range(3):
        cb.tick()
    assert cb.partial(ra) == _solo(gen, a, 3)[:-1]   # report 2 is read
    owned = list(cb._slot_blocks[0])
    shared = [blk for blk in owned if blk in cb._prefix_ref]
    assert len(owned) == 5 and shared == owned[:4]
    strangers = [blk for blk in range(1, 1 + cb.pool_blocks)
                 if blk not in owned]

    def poisoned(leaf):
        fill = jnp.asarray(77, leaf.dtype)
        return leaf.at[np.asarray(strangers)].set(fill)

    pool_leaves, *tables = cb._caches
    cb._caches = (jax.tree_util.tree_map(poisoned, pool_leaves), *tables)
    before = [np.asarray(leaf) for leaf in
              jax.tree_util.tree_leaves(cb._caches[0])]
    cb.tick()                   # dispatch 4 (a frozen), reads report 3
    assert cb.result(ra) == _solo(gen, a, 3) and cb._flying is not None
    assert cb.last_tick["finished"] == 1
    after = [np.asarray(leaf) for leaf in
             jax.tree_util.tree_leaves(cb._caches[0])]
    last = owned[-1]
    for was, now in zip(before, after):
        np.testing.assert_array_equal(now[strangers], was[strangers])
        assert (now[strangers] == 77).all()
        np.testing.assert_array_equal(now[shared], was[shared])
        # position 19 = the last block's last row, and nothing else
        changed = np.nonzero((now != was).reshape(now.shape[0], -1).any(1))
        assert set(changed[0]) <= {0, last}
        np.testing.assert_array_equal(now[last][:, :3], was[last][:, :3])
    cb.tick()                   # drains dispatch 4: nobody's rows
    assert cb.idle() and cb.last_tick["rows"] == 0
    assert cb.free_blocks() == cb.pool_blocks


# ------------------------------------------------ nothing lost at the ends
def test_idle_and_run_all_drain_the_report_in_flight(lms, f32_precision):
    gen, toks = lms["f32"], lms["toks"]
    cb = ContinuousBatcher(gen, slots=2)
    assert cb.idle() and cb.tick() == 0       # an idle call enqueues nothing
    assert cb.last_tick["dispatch_s"] == 0 and cb._flying is None
    prompt = toks[0, :7].tolist()
    rid = cb.submit(prompt, 2)
    assert cb.tick() == 1                     # dispatched, nothing read
    assert cb.partial(rid) == prompt and cb._flying is not None
    assert cb.tick() == 1                     # report 1: one token
    assert len(cb.partial(rid)) == len(prompt) + 1
    assert cb.tick() == 0                     # report 2: done, frozen
    assert cb.result(rid) == _solo(gen, prompt, 2)
    assert cb.active_requests() == set()
    assert not cb.idle()                      # dispatch 3 is in flight
    assert cb.tick() == 0 and cb.idle() and cb._flying is None
    # run_all leaves nothing behind either
    rid = cb.submit(prompt, 4)
    assert cb.run_all()[rid] == _solo(gen, prompt, 4)
    assert cb.idle() and cb._flying is None


@pytest.mark.parametrize("make", [_dense(), _paged(prefix_cache=True)],
                         ids=["dense", "paged"])
def test_cancel_with_a_report_in_flight(lms, f32_precision, make):
    """The cancelled row's tokens in the report still in flight reach
    nobody — not its own list, not the request admitted into its slot
    before that report is read — and its blocks are back at once."""
    gen, toks = lms["f32"], lms["toks"]
    cb = make(gen)
    a, b, c = (toks[i, :n].tolist() for i, n in ((0, 9), (1, 6), (2, 13)))
    ra, rb = cb.submit(a, 20), cb.submit(b, 7)
    for _ in range(3):
        cb.tick()
    flying = cb._flying.report
    assert cb._flying.req == [ra, rb]
    assert cb.cancel(ra)
    assert cb.partial(ra) is None and cb.result(ra) is None
    if hasattr(cb, "free_blocks"):
        assert cb.blocks_in_use()[0] == len(cb._slot_blocks[1])
    rc = cb.submit(c, 5)
    cb.tick()                   # admits c into a's slot, reads ``flying``
    assert cb._flying.report is not flying and cb._flying.req == [rc, rb]
    assert cb.last_tick["rows"] == 1          # b alone was read
    assert cb.partial(rc) == c and cb.partial(ra) is None
    cb.run_all()
    assert cb.result(ra) is None and ra not in cb._partials
    assert cb.result(rb) == _solo(gen, b, 7)
    assert cb.result(rc) == _solo(gen, c, 5)
    if hasattr(cb, "free_blocks"):
        assert cb.free_blocks() == cb.pool_blocks


@pytest.mark.parametrize("make", [_dense(prefill_segment=4),
                                  _paged(prefill_segment=4)],
                         ids=["dense", "paged"])
def test_reset_pool_drops_what_is_in_flight(lms, f32_precision, make):
    gen, toks = lms["f32"], lms["toks"]
    cb = make(gen)
    events = []
    cb.prefill_observer = events.append
    cb.submit(toks[0, :4].tolist(), 9)
    cb.tick()
    cb.submit(toks[1, :30].tolist(), 4)       # stages behind the tick
    cb.tick()
    assert cb._flying is not None and cb._passes and cb._staging
    cb.reset_pool()
    assert cb.idle() and cb._flying is None and not cb._passes
    assert cb.tick() == 0 and cb.last_tick["fetch_bytes"] == 0
    assert [e["kind"] for e in events] == ["begin"]
    prompt = toks[2, :10].tolist()
    rid = cb.submit(prompt, 5)
    assert cb.run_all()[rid] == _solo(gen, prompt, 5)


def test_a_fault_at_the_read_resets_what_is_in_flight(lms, f32_precision):
    """A dispatch that fails on the device shows when its report is
    read, a call later: the engine's recovery drops every request and
    what is in flight, and serves on."""
    gen, toks = lms["f32"], lms["toks"]
    eng = ContinuousEngine(gen, slots=2)
    try:
        warm = eng.submit_async(toks[0, :6].tolist(), 3)
        assert ContinuousEngine.wait(warm).tolist() == \
            _solo(gen, toks[0, :6], 3)
        real, armed = eng.cb._tick, [True]

        class Failing:
            def __array__(self, *a, **k):
                raise RuntimeError("device fault in the dispatch")

        def tick(st):
            st = real(st)
            if armed.pop() if armed else False:
                eng.cb._report = Failing()
            return st

        eng.cb._tick = tick
        doomed = eng.submit_async(toks[1, :8].tolist(), 6)
        with pytest.raises(RuntimeError, match="engine fault"):
            ContinuousEngine.wait(doomed)
        assert eng.metrics()["engine_faults"] == 1
        after = eng.submit_async(toks[2, :9].tolist(), 4)
        assert ContinuousEngine.wait(after).tolist() == \
            _solo(gen, toks[2, :9], 4)
        deadline = time.monotonic() + 30
        while not eng.cb.idle() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.cb._flying is None
        assert all(v in (0, True) for v in eng.leak_check().values())
    finally:
        eng.stop()


# ------------------------------------------------------------- the count
@pytest.mark.parametrize("make", [_dense(), _paged(),
                                  _dense(ticks_per_dispatch=4)],
                         ids=["dense", "paged", "ticks_per_dispatch_4"])
def test_ahead_by_hand(lms, f32_precision, make):
    """0 on the first call (nothing to read), 1 on every read that had
    the call's own dispatch queued behind it, 0 on the drained tail."""
    gen, toks = lms["f32"], lms["toks"]
    cb = make(gen)
    assert "ahead" in generate.TICK_COUNTS
    _, ticks = _drive(cb, [(toks[0, :9].tolist(), 8, 0.0, 0),
                           (toks[1, :4].tolist(), 5, 0.0, 0)])
    # 8 tokens at ``ticks_per_dispatch`` a dispatch, + the first call
    # and the tail's
    assert len(ticks) == -(-8 // cb.ticks_per_dispatch) + 2
    assert [t["ahead"] for t in ticks] == [0] + [1] * (len(ticks) - 2) + [0]
    assert ticks[0]["wait_s"] == 0 and ticks[0]["fetch_bytes"] == 0
    assert all(t["wait_s"] > 0 and t["fetch_bytes"] > 0 for t in ticks[1:])
    assert ticks[-1]["dispatch_s"] == 0


def test_the_engine_reports_the_share_of_reads_that_were_ahead(
        lms, f32_precision):
    gen, toks = lms["f32"], lms["toks"]
    eng = ContinuousEngine(gen, slots=2)
    try:
        assert eng.metrics()["tick_ahead_share"] == 0.0
        hs = [eng.submit_async(toks[i, :8].tolist(), 12) for i in range(2)]
        for h in hs:
            ContinuousEngine.wait(h)
        deadline = time.monotonic() + 30
        while not eng.cb.idle() and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.1)
        ring = eng.tick_records()
        assert all("ahead" in t for t in ring)
        share = eng.metrics()["tick_ahead_share"]
        assert share == pytest.approx(
            sum(t["ahead"] for t in ring) / len(ring), abs=1e-3)
        assert 0.5 < share < 1.0          # all but the two ends
    finally:
        eng.stop()


# ------------------------------------------------------ a staged pass
@pytest.fixture
def clock(monkeypatch):
    """``time`` for the batcher on a clock that moves in the two waits
    alone: a pass "takes" 5 s (``block_until_ready``), a tick 100 s (the
    read of a report)."""
    fake = type("FakeClock", (), {"now": 1000.0,
                                  "monotonic": staticmethod(time.monotonic)})
    fake.perf_counter = staticmethod(lambda: fake.now)
    real_block = jax.block_until_ready

    def block(x):
        fake.now += 5.0
        return real_block(x)

    class SlowReads:
        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, a, *args, **kw):
            if isinstance(a, jax.Array):
                fake.now += 100.0
            return np.asarray(a, *args, **kw)

    monkeypatch.setattr(generate, "time", fake)
    monkeypatch.setattr(generate.jax, "block_until_ready", block)
    monkeypatch.setattr(generate, "np", SlowReads())
    return fake


@pytest.mark.parametrize("make", [_dense(prefill_segment=8),
                                  _paged(prefill_segment=8)],
                         ids=["dense", "paged"])
def test_a_pass_is_timed_on_the_device_without_the_tick_in_flight(
        lms, f32_precision, clock, make):
    """The first pass is enqueued behind a tick in flight: its
    ``seconds`` is 5, from the landing of that tick's report to its own
    row being ready — not 105; nor is any later one's."""
    gen, toks = lms["f32"], lms["toks"]
    cb = make(gen)
    events = []
    cb.prefill_observer = events.append
    short = cb.submit(toks[1, :5].tolist(), 30)
    cb.tick()                                 # a tick in flight
    long = cb.submit(toks[0, :34].tolist(), 3)
    calls = 0
    while cb.result(long) is None:
        cb.tick()
        calls += 1
        if calls == 1:
            # enqueued, not waited for: no event yet
            assert [e["kind"] for e in events] == ["begin"]
            assert cb.last_tick["staged_tokens"] == 8
    segments = [e for e in events if e["kind"] == "segment"]
    assert [(e["start"], e["tokens"]) for e in segments] == [
        (0, 8), (8, 8), (16, 8), (24, 8), (32, 1)]
    assert [e["seconds"] for e in segments] == [5.0] * 5
    assert {"rid", "slot", "start", "tokens", "cursor", "plen",
            "seconds"} <= set(segments[0])
    # the admission's events keep their order: its ``admit`` waits
    # behind its last ``segment``
    assert [e["kind"] for e in events] == \
        ["begin"] + ["segment"] * 5 + ["admit"]
    cb.run_all()
    assert cb.result(long) == _solo(gen, toks[0, :34].tolist(), 3)
    assert cb.result(short) == _solo(gen, toks[1, :5].tolist(), 30)


def test_an_idle_devices_pass_is_timed_from_its_own_enqueue(
        lms, f32_precision, clock):
    """No tick in flight (every slot stages): no tick is enqueued, the
    passes run one ahead of their waits, and each is timed from its own
    enqueue or the pass before it."""
    gen, toks = lms["f32"], lms["toks"]
    cb = ContinuousBatcher(gen, slots=1, prefill_segment=8)
    events = []
    cb.prefill_observer = events.append
    rid = cb.submit(toks[0, :20].tolist(), 2)
    cb.tick()
    assert cb.last_tick["dispatch_s"] == 0 and cb.last_tick["staging"] == 1
    assert cb._flying is None and len(cb._passes) == 1 and not cb.idle()
    clock.now += 2.0            # the host was elsewhere meanwhile
    cb.tick()                   # waits for pass 1 where its row is donated
    assert [e["kind"] for e in events] == ["begin", "segment"]
    assert events[1]["seconds"] == 7.0        # enqueue -> ready
    cb.run_all()
    assert [e["seconds"] for e in events if e["kind"] == "segment"] == [
        7.0, 5.0, 5.0]
    assert cb.result(rid) == _solo(gen, toks[0, :20].tolist(), 2)
