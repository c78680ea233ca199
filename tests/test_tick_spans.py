"""The serving tick's spans and counts, inside the program
(docs/services.md "Request tracing", PERF.md section 3): every phase of
``ContinuousBatcher.tick`` and of ``ContinuousEngine._loop`` runs under a
``telemetry.span`` — a ``TraceAnnotation`` on the profiler's clock — and
the engine keeps one record a tick, from which ``metrics()`` reads where
a tick's time goes and what the ticks carried."""

import glob
import os
import tempfile
import time

import jax
import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.models import generate, zoo
from veles_tpu.models.generate import (ContinuousBatcher, LMGenerator,
                                       PagedContinuousBatcher)
from veles_tpu.models.standard_workflow import StandardWorkflow
from veles_tpu.services import restful
from veles_tpu.services.restful import ContinuousEngine

ENGINE_SPANS = ("engine.ingress", "engine.deliver")
NEW_KEYS = {"ticks_total", "p50_tick_ms", "p50_tick_wait_ms",
            "tick_ahead_share",
            "p50_tick_host_ms", "p50_tick_fetch_ms", "p50_tick_admit_ms",
            "p50_engine_host_ms", "tick_rows_mean", "p50_tick_kv_tokens",
            "p50_tick_kv_pages", "p50_tick_fetch_bytes"}


def _workflow(t=48, epochs=0, **zoo_kwargs):
    prng.seed_all(31)
    vocab, n = 13, 96
    r = np.random.RandomState(5)
    toks = ((np.arange(t)[None, :] * 2 + r.randint(0, 4, n)[:, None])
            % vocab).astype(np.int32)
    loader = FullBatchLoader(None, data=toks, labels=toks,
                             minibatch_size=48, class_lengths=[0, 48, 48])
    wf = StandardWorkflow(
        layers=zoo.transformer_lm(vocab_size=vocab, d_model=32, n_heads=4,
                                  n_layers=2, lr=5e-3, dropout=0.0,
                                  **zoo_kwargs),
        loader=loader, loss="lm",
        decision_config={"max_epochs": max(epochs, 1)},
        name="tick-spans-lm")
    wf.initialize()
    if epochs:
        wf.run()
    return wf, toks


@pytest.fixture(scope="module")
def lm():
    wf, toks = _workflow()
    return LMGenerator(wf.trainer, max_len=48), toks


def drain(eng, handles, timeout=120.0):
    for h in handles:
        ContinuousEngine.wait(h)
    deadline = time.monotonic() + timeout
    while not eng.cb.idle() and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.1)          # the last iteration's record is appended


@pytest.mark.parametrize("batcher", [
    lambda gen: ContinuousBatcher(gen, slots=2),
    lambda gen: PagedContinuousBatcher(gen, slots=2, block=4,
                                       pool_tokens=96),
    lambda gen: ContinuousBatcher(gen, slots=2, prefill_segment=5),
], ids=["dense", "paged", "segmented"])
def test_a_tick_records_its_phases_and_counts(lm, batcher):
    gen, toks = lm
    cb = batcher(gen)
    assert cb.last_tick is None
    plens, max_new = (20, 5), 4
    for i, plen in enumerate(plens):
        cb.submit(toks[i, :plen].tolist(), max_new)
    cb.tick()
    first = cb.last_tick
    assert set(first) == {n.partition(".")[2] + "_s"
                          for n in generate.TICK_SPANS} \
        | set(generate.TICK_COUNTS)
    # a call's own counts are of what it ENQUEUED (admissions, staged
    # passes); rows, keys and finished requests are of the report it
    # READ, which is the dispatch before its own: the first call reads
    # none and never blocks
    assert first["admitted"] == 2
    staged = cb.prefill_segment > 0
    assert first["rows"] == first["kv_tokens"] == first["ahead"] == 0
    assert first["wait_s"] == first["fetch_s"] == first["emit_s"] == 0
    assert first["dispatch_s"] > 0
    if staged:
        # the long prompt stages and prefills in bounded passes, the
        # short one is admitted whole
        assert first["prompt_tokens"] == 5 and first["staged_tokens"] > 0
        assert first["staging"] == 1
    else:
        assert first["prompt_tokens"] == sum(plens)
    cb.tick()
    second = cb.last_tick
    assert second["ahead"] == 1 and second["admitted"] == 0
    if staged:
        assert second["rows"] + second["staging"] == 2
    else:
        assert second["rows"] == 2 and second["staging"] == 0
        # admission leaves a row at plen - 1; the tick writes that
        # position and attends keys 0..plen-1: plen keys a row
        assert second["kv_tokens"] == sum(plens)
    # the parent covers its five children; blocked time is part of it
    parts = sum(second[k] for k in ("admit_s", "dispatch_s", "fetch_s",
                                    "emit_s"))
    assert 0 < second["wait_s"] < second["tick_s"]
    assert parts <= second["tick_s"]
    if not staged:
        assert parts + second["wait_s"] <= second["tick_s"]
    n, finished = 2, first["finished"] + second["finished"]
    while not cb.idle():
        cb.tick()
        n += 1
        assert cb.last_tick["admitted"] == 0 or staged
        finished += cb.last_tick["finished"]
    # the last call drains the dispatch still in flight: the rows it
    # carried had been released by the report before it
    assert cb.last_tick["ahead"] == 0 and cb.last_tick["rows"] == 0
    assert finished == 2


@pytest.mark.parametrize("indexer", [{"heads": 2, "head_dim": 8,
                                      "topk": 4}, None],
                         ids=["indexer", "plain"])
def test_staged_keys_counts_the_live_widths_of_the_passes(
        lm, indexer, monkeypatch):
    """``staged_keys``: the keys a query's selection ranged over, summed
    over a tick's staged prefill passes — ``start + tokens`` rounded up
    to whole key blocks, from the helper that bounds the pass's own
    loops (``attention.dsa_live_blocks``); 0 for a model that selects
    nothing."""
    from veles_tpu.ops import attention
    monkeypatch.setattr(attention, "DSA_KEY_BLOCK", 8)
    gen, toks = lm
    if indexer:
        prng.seed_all(33)
        loader = FullBatchLoader(None, data=toks, labels=toks,
                                 minibatch_size=48,
                                 class_lengths=[0, 48, 48])
        wf = StandardWorkflow(
            layers=zoo.transformer_lm(vocab_size=13, d_model=32,
                                      n_heads=4, n_layers=2, pos="rope",
                                      indexer=indexer),
            loader=loader, loss="lm", decision_config={"max_epochs": 1},
            name="staged-keys-lm")
        wf.initialize()
        gen = LMGenerator(wf.trainer, max_len=48)
    cb = PagedContinuousBatcher(gen, slots=2, block=4, pool_tokens=96,
                                prefill_segment=8)
    passes = []
    cb.prefill_observer = lambda e: passes.append(e) \
        if e["kind"] == "segment" else None
    cb.submit(toks[0, :30].tolist(), 3)
    keys, tokens, per_tick = 0, 0, set()
    while not cb.idle():
        cb.tick()
        keys += cb.last_tick["staged_keys"]
        tokens += cb.last_tick["staged_tokens"]
        per_tick.add(cb.last_tick["staged_keys"])
    # 29 prompt positions in passes of 8, 8, 8 and a tail: live widths
    # 8, 16, 24 and 32 keys of the row's 48
    assert [(e["start"], e["tokens"]) for e in passes] == [
        (0, 8), (8, 8), (16, 8), (24, 8)]
    assert tokens == 32
    if indexer:
        assert keys == 8 + 16 + 24 + 32 and per_tick >= {8, 16, 24, 32}
        # block rounding included: a pass that ends inside a block
        # ranges to the block's end, and never past the row
        assert attention.dsa_live_blocks(21, 48) == (3, 24)
        assert attention.dsa_live_blocks(48, 48) == (6, 48)
    else:
        assert keys == 0


#: (model width and heads, max_len, key block, pass length) -> does the
#: pass's attention run in ``veles_dsa_prefill``
KERNEL_PASSES = {
    "kernel": (dict(d_model=256, n_heads=2, n_kv_heads=1), 256, 128, 32, True),
    "narrow_heads": (dict(d_model=32, n_heads=4), 256, 128, 32, False),
    "ragged_row": (dict(d_model=256, n_heads=2, n_kv_heads=1), 160, 128, 32,
                   False),
    "short_pass": (dict(d_model=256, n_heads=2, n_kv_heads=1), 256, 128, 8,
                   False),
}


@pytest.mark.parametrize("case", sorted(KERNEL_PASSES))
def test_staged_kernel_tokens_counts_the_passes_the_kernel_ran(
        lm, case, monkeypatch):
    """``staged_kernel_tokens``: the staged tokens whose pass's masked
    attention ran in the Pallas kernel — all of ``staged_tokens`` where
    ``attention.dsa_prefill_tiles`` takes the pass's shapes (head dim
    128, a row the key block divides, a pass that fills the mask's
    tile), 0 where it does not; and the count is what the pass's
    program did: the kernel is traced once a layer where it counts, and
    never where it does not."""
    from veles_tpu.ops import attention
    from veles_tpu.ops.pallas import dsa
    model, max_len, kb, segment, kernel = KERNEL_PASSES[case]
    monkeypatch.setattr(attention, "DSA_KEY_BLOCK", kb)
    traced = []
    real = dsa.dsa_prefill_attention
    monkeypatch.setattr(dsa, "dsa_prefill_attention",
                        lambda *a, **kw: traced.append(1) or real(*a, **kw))
    _, toks = lm
    prng.seed_all(35)
    loader = FullBatchLoader(None, data=toks, labels=toks,
                             minibatch_size=48, class_lengths=[0, 48, 48])
    wf = StandardWorkflow(
        layers=zoo.transformer_lm(
            vocab_size=13, n_layers=2, pos="rope",
            indexer={"heads": 2, "head_dim": 8, "topk": 4}, **model),
        loader=loader, loss="lm", decision_config={"max_epochs": 1},
        name="kernel-tokens-lm-" + case)
    wf.initialize()
    gen = LMGenerator(wf.trainer, max_len=max_len)
    cb = PagedContinuousBatcher(gen, slots=1, block=4, pool_tokens=max_len,
                                prefill_segment=segment)
    # 96 positions to prefill: whole passes of 32 (or of 8), no tail
    cb.submit((list(toks[0]) * 3)[:97], 2)
    tokens = kernel_tokens = 0
    while not cb.idle():
        cb.tick()
        tokens += cb.last_tick["staged_tokens"]
        kernel_tokens += cb.last_tick["staged_kernel_tokens"]
    assert tokens == 96
    assert kernel_tokens == (tokens if kernel else 0)
    assert len(traced) == (2 if kernel else 0)


@pytest.mark.parametrize("block", [4, 16])
def test_kv_pages_counts_the_pages_the_kernel_walked(lm, block):
    """``kv_pages``: over the occupied rows, the written position //
    block + 1 — the paged decode kernel's trip count a row — and what
    ``kv_tokens`` rounds up to in whole pages; the dense slots have no
    pages.  Followed by hand through a run whose rows cross page
    boundaries at different ticks."""
    gen, toks = lm
    cb = PagedContinuousBatcher(gen, slots=3, block=block,
                                pool_tokens=144)
    plens, max_new = (15, 16, 3), (9, 5, 7)
    for i, (plen, new) in enumerate(zip(plens, max_new)):
        cb.submit(toks[i, :plen].tolist(), new)
    k, seen = 0, set()
    cb.tick()                   # the first call reads no report
    assert cb.last_tick["rows"] == cb.last_tick["kv_pages"] == 0
    while not cb.idle():
        cb.tick()
        # the k-th REPORT (read one call after its dispatch) wrote
        # position plen - 1 + k of every row still decoding (rows finish
        # after max_new - 1 ticks past the first); the last call drains
        # a dispatch whose rows had all been released
        written = [p - 1 + k for p, new in zip(plens, max_new) if k < new]
        tick = cb.last_tick
        assert tick["rows"] == len(written)
        assert tick["kv_tokens"] == sum(w + 1 for w in written)
        assert tick["kv_pages"] == sum(w // block + 1 for w in written)
        assert tick["kv_pages"] * block >= tick["kv_tokens"]
        seen.add(tick["kv_pages"] * block - tick["kv_tokens"])
        k += 1
    assert k == max(max_new) + 1 and len(seen) > 1
    dense = ContinuousBatcher(gen, slots=2)
    dense.submit(toks[0, :9].tolist(), 2)
    dense.tick()
    dense.tick()
    assert dense.last_tick["kv_tokens"] == 9
    assert dense.last_tick["kv_pages"] == 0


def test_engine_metrics_read_the_tick_ring(lm):
    gen, toks = lm
    eng = ContinuousEngine(gen, slots=2)
    try:
        before = eng.metrics()
        assert NEW_KEYS <= set(before) and before["ticks_total"] == 0
        assert before["tick_rows_mean"] == 0.0
        # two rows kept busy for the same number of ticks: plen 12 and
        # 8, 6 new tokens each
        plens, max_new = (12, 8), 6
        hs = [eng.submit_async(toks[i, :p].tolist(), max_new)
              for i, p in enumerate(plens)]
        drain(eng, hs)
        ring, m = eng.tick_records(), eng.metrics()
        assert m["ticks_total"] == len(ring) >= max_new
        assert sum(t["submitted"] for t in ring) == 2
        assert sum(t["admitted"] for t in ring) == 2
        assert sum(t["finished"] for t in ring) == 2
        assert all(t["ingress_s"] >= 0 and t["deliver_s"] > 0
                   for t in ring)
        assert 0 < m["p50_tick_host_ms"] <= m["p50_tick_ms"]
        assert 0 < m["p50_tick_wait_ms"] < m["p50_tick_ms"]
        assert m["p50_tick_fetch_ms"] > 0 and m["p50_engine_host_ms"] > 0
        # every tick read its report and nothing else: a token, a
        # count, a cursor and a flag a slot, an int32 each
        assert {t["fetch_bytes"] for t in ring} == {0, 2 * 4 * 4}
        assert [t["fetch_bytes"] for t in ring].count(0) == 1
        assert m["p50_tick_fetch_bytes"] == 32
        # both rows decoding in every tick that carried any
        busy = [t for t in ring if t["rows"]]
        assert {t["rows"] for t in busy} == {2}
        assert m["tick_rows_mean"] == pytest.approx(
            2.0 * len(busy) / len(ring), abs=1e-3)
        # by hand: the k-th tick after admission attends plen + k keys
        # in each row
        # in the reports read one call after the admission's own
        both = [t["kv_tokens"] for t in ring if t["rows"] == 2]
        first = next(t for t in ring if t["admitted"] == 2)
        assert first["kv_tokens"] == 0 and first["ahead"] == 0
        assert both[:4] == [sum(plens) + 2 * k for k in (0, 1, 2, 3)]
        # every read but the drained tail's had a dispatch behind it
        reads = [t["ahead"] for t in ring if t["wait_s"] > 0]
        assert reads[-1] == 0 and set(reads[:-1]) == {1}
        assert m["tick_ahead_share"] == pytest.approx(
            sum(t["ahead"] for t in ring) / len(ring), abs=1e-3)
        kv = sorted(t["kv_tokens"] for t in ring)
        assert m["p50_tick_kv_tokens"] == kv[len(kv) // 2]
        pages = sorted(t["kv_pages"] for t in ring)
        assert m["p50_tick_kv_pages"] == pages[len(pages) // 2]
        eng.reset_metrics()
        after = eng.metrics()
        assert eng.tick_records() == [] and after["ticks_total"] == 0
        assert after["p50_tick_ms"] == 0.0
    finally:
        eng.stop()


# ------------------------------------------------ the tick's report
@pytest.fixture(scope="module")
def report_lms():
    """A linear-cache model at two ``max_len`` and a rolling-window one
    (whose admission chunk rounds DOWN, so the tick forces the prompt's
    tail)."""
    wf, toks = _workflow(t=32, epochs=6)
    wfw, _ = _workflow(t=32, epochs=6, window=6)
    return {"linear": LMGenerator(wf.trainer, max_len=32),
            "linear_short": LMGenerator(wf.trainer, max_len=16),
            "rolling": LMGenerator(wfw.trainer, max_len=32),
            "toks": toks}


def _paged(**kw):
    return lambda gen: PagedContinuousBatcher(
        gen, slots=2, block=4, pool_tokens=128, **kw)


def _dense(**kw):
    return lambda gen: ContinuousBatcher(gen, slots=2, **kw)


REPORT_CASES = {
    "dense": _dense(),
    "paged": _paged(),
    "paged_segment_passes": _paged(prefill_segment=4),
    "dense_segment_passes": _dense(prefill_segment=4),
    "dense_ticks_per_dispatch_4": _dense(ticks_per_dispatch=4),
    "paged_ticks_per_dispatch_4": _paged(ticks_per_dispatch=4),
    "speculative_k": _dense(speculative_k=4),
    "speculative_k_ticks_per_dispatch_4": _dense(speculative_k=4,
                                                 ticks_per_dispatch=4),
    "dense_prompt_forced": _dense(chunked_prefill=False),
    "paged_prompt_forced": _paged(chunked_prefill=False),
    "rolling_window_chunk": _dense(),
    "prefix_cache_resume": _paged(prefix_cache=True),
}


@pytest.mark.parametrize("case", sorted(REPORT_CASES))
def test_results_and_every_partial_are_the_solo_continuation(
        report_lms, f32_precision, case):
    """The host's token lists, built from the ticks' reports alone,
    are the solo ``gen.generate`` continuation: every tick's
    ``partial(rid)`` a prefix of it that holds the whole prompt, and the
    result all of it — through a cancel mid-flight, the slot reused
    after it, and a slot reused after a completion."""
    toks = report_lms["toks"]
    gen = report_lms["rolling" if case == "rolling_window_chunk"
                     else "linear"]
    cb = REPORT_CASES[case](gen)
    # a and b fill the slots; c shares a's first two pool blocks and
    # takes b's slot when b is cancelled; d (sampled) takes a slot that
    # finished
    prompts = {"a": toks[0, :12].tolist(), "b": toks[1, :5].tolist(),
               "c": toks[0, :8].tolist() + toks[2, 8:11].tolist(),
               "d": toks[3, :9].tolist()}
    opts = {"a": (8, 0.0, 0), "b": (22, 0.0, 0), "c": (7, 0.0, 0),
            "d": (6, 0.7, 5)}
    want = {k: gen.generate(np.asarray([prompts[k]], np.int32), n,
                            temperature=t, seed=sd)[0].tolist()
            for k, (n, t, sd) in opts.items()}
    rids = {k: cb.submit(prompts[k], n, temperature=t, seed=sd)
            for k, (n, t, sd) in opts.items()}
    grown = dict.fromkeys(rids, 0)
    shared = ticks = 0
    while not cb.idle():
        cb.tick()
        ticks += 1
        if case == "prefix_cache_resume":
            blocks, refs = cb.prefix_stats()
            shared = max(shared, refs - blocks)
        for k, rid in rids.items():
            part = cb.partial(rid)
            if part is None:
                continue
            assert len(part) >= max(grown[k], len(prompts[k])), (k, ticks)
            assert part == want[k][:len(part)], (k, ticks)
            grown[k] = len(part)
        if ticks == 1:
            assert grown["b"] >= len(prompts["b"])
            assert cb.cancel(rids["b"])
            assert cb.partial(rids["b"]) is None
    assert cb.result(rids["b"]) is None and ticks >= 4
    for k in "acd":
        assert cb.partial(rids[k]) is None
        assert cb.pop_result(rids[k]) == want[k], k
        assert grown[k] > len(prompts[k])        # it streamed
    if case == "prefix_cache_resume":
        assert shared >= 2        # c resumed behind a's two blocks
    assert not cb._partials


@pytest.mark.parametrize("make", [_dense(), _paged(),
                                  _dense(ticks_per_dispatch=4)],
                         ids=["dense", "paged", "ticks_per_dispatch_4"])
def test_a_tick_fetches_its_report_whatever_max_len_is(
        report_lms, make, monkeypatch):
    """``fetch_bytes`` — what the host read from the device in a tick —
    is the report's bytes: the same for two batchers that differ in
    ``max_len`` alone, under a ``[slots, max_len]`` token matrix's;
    the report is ONE array a dispatch, and the only device array a
    tick makes a host array of."""
    class NumpySpy:
        read = []

        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, a, *args, **kw):
            if isinstance(a, jax.Array):
                self.read.append(a)
            return np.asarray(a, *args, **kw)

    monkeypatch.setattr(generate, "np", NumpySpy())
    toks = report_lms["toks"]
    fetched = []
    for name in ("linear", "linear_short"):
        cb = make(report_lms[name])
        for i in range(2):
            cb.submit(toks[i, :6 + i].tolist(), 5)
        per_tick, dispatched = [], None
        while not cb.idle():
            cb.tick()
            per_tick.append(cb.last_tick["fetch_bytes"])
            # what a call reads is the report of the dispatch BEFORE its
            # own (none in the first call), and nothing else
            assert [id(a) for a in NumpySpy.read] == \
                [id(dispatched)] * (dispatched is not None)
            del NumpySpy.read[:]
            dispatched = cb._flying and cb._flying.report
            assert cb._report.shape[0] == cb.ticks_per_dispatch
        assert per_tick[0] == 0 and len(set(per_tick[1:])) == 1
        assert per_tick[1] == cb._report.nbytes
        fetched.append(per_tick[1])
    assert 0 < fetched[0] == fetched[1] < cb.slots * 32 * 4


def test_streamed_chunks_are_counted_in_deliver(lm):
    gen, toks = lm
    eng = ContinuousEngine(gen, slots=2)
    try:
        chunks = list(eng.stream(toks[0, :10].tolist(), 5))
        assert chunks
        deadline = time.monotonic() + 30
        while not eng.cb.idle() and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.1)
        ring = eng.tick_records()
        assert sum(t["pushed"] for t in ring) >= 1
        assert sum(t["refused"] for t in ring) == 0
        # a stream costs the tick no read of its own
        # (the first call of a busy spell reads none at all)
        assert {t["fetch_bytes"] for t in ring} == {0, 32}
    finally:
        eng.stop()


def test_the_ring_is_bounded_and_holds_a_window(lm):
    gen, _ = lm
    assert restful.TICK_RING >= 512
    eng = ContinuousEngine(gen, slots=2)
    try:
        assert eng._tick_ring.maxlen == restful.TICK_RING
    finally:
        eng.stop()


def test_spans_reach_the_profiler_and_nest(lm):
    """Under ``jax.profiler`` the eight names are on the host plane, on
    the profiler's clock, nested as the table in docs/services.md says:
    the five phases inside ``batcher.tick``, the engine's two outside
    it, one before and one after."""
    from benchmarks import trace
    gen, toks = lm
    eng = ContinuousEngine(gen, slots=2, paged_block=4, pool_tokens=96)
    d = tempfile.mkdtemp(prefix="tick_spans_")
    try:
        # compile outside the capture
        drain(eng, [eng.submit_async(toks[0, :12].tolist(), 3)])
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=options)
        try:
            drain(eng, [eng.submit_async(toks[i, :12].tolist(), 4)
                        for i in range(2)])
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                      "*.xplane.pb"))[0]
        spans = trace.host_spans(jax.profiler.ProfileData.from_file(path))
    finally:
        eng.stop()
        import shutil
        shutil.rmtree(d, ignore_errors=True)
    by_name = {}
    for start, end, name in spans:
        by_name.setdefault(name, []).append((start, end))
    assert set(generate.TICK_SPANS) | set(ENGINE_SPANS) <= set(by_name)
    ticks = sorted(by_name["batcher.tick"])

    def inside(name):
        return all(any(a <= s and e <= b for a, b in ticks)
                   for s, e in by_name[name])

    for child in generate.TICK_SPANS[1:]:
        assert inside(child), child
    for outer in ENGINE_SPANS:
        assert not any(a < e and s < b for a, b in ticks
                       for s, e in by_name[outer]), outer
    # the innermost span wins where a gap is charged: at the middle of a
    # fetch the reduction names the fetch, not the tick round it (among
    # the program's own spans: on the CPU backend the tick in flight
    # runs its operations on the host plane, beside the fetch)
    own = [sp for sp in spans
           if sp[2] in generate.TICK_SPANS + ENGINE_SPANS]
    s, e = by_name["batcher.fetch"][0]
    assert trace.enclosing_spans(own, [(s + e) / 2]) == ["batcher.fetch"]
    # every tick is followed by a deliver and (but the first) preceded
    # by an ingress, in the engine's own thread
    a, b = ticks[-1]
    assert any(b <= s for s, _ in by_name["engine.deliver"])
    assert any(e <= a for _, e in by_name["engine.ingress"])
    # the jitted serving programs under their stable names
    names = {n for n in by_name if n.startswith("PjitFunction(")}
    assert "PjitFunction(serve_tick)" in names, names
