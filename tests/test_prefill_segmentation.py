"""Segmented prefill admission (docs/services.md "Disaggregated
prefill"): long prompts admit through bounded chunk passes interleaved
with decode ticks.  THE bar: every segmented configuration's token
streams are byte-identical to the unsegmented admission (and to
token-by-token prompt forcing) — the segments reuse the prefix-cache
resume math, so a single drifted position would also break the PR 7
failover splice."""

import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.models import zoo
from veles_tpu.models.generate import (ContinuousBatcher, LMGenerator,
                                       PagedContinuousBatcher)
from veles_tpu.models.standard_workflow import StandardWorkflow


def _lm_workflow(t=48, vocab=13, seed=31, **zoo_kwargs):
    prng.seed_all(seed)
    r = np.random.RandomState(5)
    n = 96
    toks = ((np.arange(t)[None, :] * 2 + r.randint(0, 4, n)[:, None])
            % vocab).astype(np.int32)
    loader = FullBatchLoader(None, data=toks, labels=toks,
                             minibatch_size=48,
                             class_lengths=[0, 48, 48])
    wf = StandardWorkflow(
        layers=zoo.transformer_lm(vocab_size=vocab, d_model=32,
                                  n_heads=4, n_layers=2, lr=5e-3,
                                  dropout=0.0, **zoo_kwargs),
        loader=loader, loss="lm", decision_config={"max_epochs": 1},
        name="seg-lm")
    wf.initialize()
    return wf, toks


@pytest.fixture(scope="module")
def lm():
    wf, toks = _lm_workflow()
    return LMGenerator(wf.trainer, max_len=48), toks


@pytest.fixture(scope="module")
def lm_rolling():
    wf, toks = _lm_workflow(window=8)
    return LMGenerator(wf.trainer, max_len=48), toks


def _pool_results(cb, requests):
    rids = [cb.submit(*req) for req in requests]
    cb.run_all()
    return [cb.pop_result(r) for r in rids]


class TestSegmentedEquivalence:
    """The byte-identity matrix: odd segment sizes vs prompt lengths
    around PREFILL_MIN, rolling-window round-down, prefix-cache shared
    blocks, speculative pools, and paged (bf16 + int8) pools — all
    equal to the unsegmented path AND to token-by-token forcing."""

    @pytest.mark.parametrize("segment", [3, 5, 7])
    @pytest.mark.parametrize("plen", [31, 33])
    def test_dense_odd_segments_vs_prefill_min(self, lm, segment,
                                               plen, f32_precision):
        gen, toks = lm
        reqs = [(toks[i, :plen].tolist(), 6, 0.0, i) for i in range(2)]
        base = _pool_results(ContinuousBatcher(gen, slots=2), reqs)
        seg = _pool_results(
            ContinuousBatcher(gen, slots=2, prefill_segment=segment),
            reqs)
        forced = _pool_results(
            ContinuousBatcher(gen, slots=2, chunked_prefill=False),
            reqs)
        assert seg == base == forced

    def test_sampled_rows_identical(self, lm, f32_precision):
        gen, toks = lm
        reqs = [(toks[0, :30].tolist(), 6, 0.8, 7),
                (toks[1, :33].tolist(), 6, 0.0, 1)]
        base = _pool_results(ContinuousBatcher(gen, slots=2), reqs)
        seg = _pool_results(
            ContinuousBatcher(gen, slots=2, prefill_segment=5), reqs)
        assert seg == base

    def test_rolling_window_rounds_down_unsegmented(self, lm_rolling,
                                                    f32_precision):
        """A rolling-window model must keep the unsegmented round-DOWN
        prefill (a ring slot may never hold a position past its own
        start): _will_segment refuses, outputs stay byte-identical."""
        gen, toks = lm_rolling
        cb = ContinuousBatcher(gen, slots=2, prefill_segment=5)
        assert not cb._will_segment(33)
        reqs = [(toks[i, :33].tolist(), 6, 0.0, i) for i in range(2)]
        base = _pool_results(ContinuousBatcher(gen, slots=2), reqs)
        seg = _pool_results(
            ContinuousBatcher(gen, slots=2, prefill_segment=5), reqs)
        assert seg == base

    def test_speculative_pool_identical(self, lm, f32_precision):
        gen, toks = lm
        reqs = [(toks[i, :30].tolist(), 8, 0.0, i) for i in range(2)]
        base = _pool_results(
            ContinuousBatcher(gen, slots=2, speculative_k=4), reqs)
        seg = _pool_results(
            ContinuousBatcher(gen, slots=2, speculative_k=4,
                              prefill_segment=7), reqs)
        plain = _pool_results(ContinuousBatcher(gen, slots=2), reqs)
        assert seg == base == plain

    @pytest.mark.parametrize("ticks_per_dispatch", [1, 4])
    def test_paged_pool_identical(self, lm, ticks_per_dispatch,
                                  f32_precision):
        gen, toks = lm
        reqs = [(toks[i, :31].tolist(), 6, 0.0, i) for i in range(2)]
        base = _pool_results(
            PagedContinuousBatcher(
                gen, slots=2, block=4, pool_tokens=96,
                ticks_per_dispatch=ticks_per_dispatch), reqs)
        cb = PagedContinuousBatcher(gen, slots=2, block=4,
                                    pool_tokens=96, prefill_segment=5,
                                    ticks_per_dispatch=ticks_per_dispatch)
        seg = _pool_results(cb, reqs)
        assert seg == base
        assert cb.free_blocks() == cb.pool_blocks

    def test_paged_int8_pool_identical(self, f32_precision):
        wf, toks = _lm_workflow(t=32)
        gen = LMGenerator(wf.trainer, max_len=32, cache_dtype="int8")
        reqs = [(toks[i, :22].tolist(), 5, 0.0, i) for i in range(2)]
        base = _pool_results(
            PagedContinuousBatcher(gen, slots=2, block=4,
                                   pool_tokens=64), reqs)
        seg = _pool_results(
            PagedContinuousBatcher(gen, slots=2, block=4,
                                   pool_tokens=64, prefill_segment=6),
            reqs)
        assert seg == base

    def test_prefix_cache_shared_blocks_identical(self, lm,
                                                  f32_precision):
        """Same-prefix requests under segmentation: results equal the
        no-sharing batcher's, the sharing accounting is exact, and
        every block returns to the free list."""
        gen, toks = lm
        prompt = toks[0, :33].tolist()
        reqs = [(prompt, 4, 0.0, 0), (prompt, 4, 0.0, 0)]
        base = _pool_results(
            PagedContinuousBatcher(gen, slots=2, block=4,
                                   pool_tokens=96), reqs)
        cb = PagedContinuousBatcher(gen, slots=2, block=4,
                                    pool_tokens=96, prefix_cache=True,
                                    prefill_segment=6)
        free0 = cb.free_blocks()
        r1 = cb.submit(*reqs[0])
        r2 = cb.submit(*reqs[1])
        cb.run_all()
        assert [cb.pop_result(r1), cb.pop_result(r2)] == base
        assert cb.free_blocks() == free0
        assert not cb._prefix_reg and not cb._prefix_ref

    def test_staged_blocks_not_matchable_until_finish(self, lm,
                                                      f32_precision):
        """Deferred prefix registration: while a staged admission is
        still prefilling, its new blocks hold no K/V — they must not
        appear in the prefix registry (a sharer matching them would
        attend garbage).  They publish at finish."""
        gen, toks = lm
        prompt = toks[0, :33].tolist()
        cb = PagedContinuousBatcher(gen, slots=2, block=4,
                                    pool_tokens=96, prefix_cache=True,
                                    prefill_segment=4,
                                    prefill_tick_budget=4)
        cb.submit(prompt, 4)
        cb.tick()                      # begins staging + 1 segment
        assert cb.staging_slots() == 1
        assert not cb._prefix_reg      # nothing matchable mid-staging
        cb.run_all()
        assert not cb._staging


class TestSegmentedMechanics:
    def test_budget_bounds_tokens_per_tick(self, lm, f32_precision):
        """Each tick advances at most the budget (pow2 bucketing may
        overshoot < 2x) — a 32-token prefill at segment 4 takes
        several ticks, decode ticks interleaved throughout."""
        gen, toks = lm
        events = []
        cb = ContinuousBatcher(gen, slots=2, prefill_segment=4)
        cb.prefill_observer = events.append
        # an in-flight decode stream the admission must not stall
        r_short = cb.submit(toks[1, :4].tolist(), 20)
        cb.tick()
        r_long = cb.submit(toks[0, :33].tolist(), 4)
        ticks = 0
        while cb.result(r_long) is None and ticks < 200:
            cb.tick()
            ticks += 1
        segs = [e for e in events if e["kind"] == "segment"]
        assert all(e["tokens"] <= 8 for e in segs)   # bucket(4)=4 or edge
        assert len(segs) >= 8                        # 32/4 passes
        # the staged prefill spanned multiple ticks (interleaving)
        assert ticks >= len(segs)
        cb.run_all()
        assert cb.result(r_short) is not None or \
            cb.pop_result(r_short) is not None

    def test_backlog_accounting(self, lm, f32_precision):
        gen, toks = lm
        cb = ContinuousBatcher(gen, slots=1, prefill_segment=4,
                               prefill_tick_budget=4)
        cb.submit(toks[0, :33].tolist(), 4)
        cb.submit(toks[1, :21].tolist(), 4)   # queued behind
        assert cb.prefill_backlog_tokens() == 33 + 21
        cb.tick()                             # stage + first segment
        backlog = cb.prefill_backlog_tokens()
        assert backlog < 33 + 21
        assert backlog >= 21                  # queued prompt untouched
        cb.run_all()
        assert cb.prefill_backlog_tokens() == 0

    def test_cancel_mid_staging_frees_slot_and_blocks(self, lm,
                                                      f32_precision):
        gen, toks = lm
        cb = PagedContinuousBatcher(gen, slots=1, block=4,
                                    pool_tokens=48,
                                    prefill_segment=4,
                                    prefill_tick_budget=4)
        free0 = cb.free_blocks()
        rid = cb.submit(toks[0, :33].tolist(), 4)
        cb.tick()
        assert cb.staging_slots() == 1 and cb.free_blocks() < free0
        assert cb.cancel(rid)
        assert cb.staging_slots() == 0
        assert cb.free_blocks() == free0
        # the freed slot admits the next request normally
        r2 = cb.submit(toks[1, :9].tolist(), 4)
        cb.run_all()
        assert cb.pop_result(r2) == gen.generate(
            np.asarray([toks[1, :9].tolist()], np.int32),
            4)[0].tolist()

    def test_reset_pool_clears_staging(self, lm, f32_precision):
        gen, toks = lm
        cb = ContinuousBatcher(gen, slots=1, prefill_segment=4,
                               prefill_tick_budget=4)
        cb.submit(toks[0, :33].tolist(), 4)
        cb.tick()
        assert cb.staging_slots() == 1
        cb.reset_pool()
        assert cb.staging_slots() == 0 and cb.idle()


class TestEnginePrefill:
    @pytest.fixture(scope="class")
    def engine(self, lm):
        from veles_tpu.services.restful import ContinuousEngine
        gen, toks = lm
        eng = ContinuousEngine(gen, slots=2, prefill_segment=6)
        yield eng, toks
        eng.stop()

    def test_metrics_and_flight_events(self, engine, f32_precision):
        from veles_tpu.telemetry import flight
        eng, toks = engine
        out = eng.wait(eng.submit_async(toks[0, :33].tolist(), 4))
        assert len(out) == 37
        m = eng.metrics()
        assert m["prefill_segments_total"] >= 4
        assert m["prefill_tokens_total"] >= 32
        assert m["prefill_ms_per_tok"] > 0
        assert "p99_decode_stall_ms" in m
        assert m["queued_prefill_tokens"] == 0
        phases = {e.get("phase") for e in flight.recorder.snapshot()
                  if e["kind"] == "serve.prefill"}
        assert {"begin", "segment", "admit"} <= phases

    def test_predictive_deadline_includes_prefill(self, engine,
                                                  f32_precision):
        """A long prompt with a deadline its own PREFILL cannot meet
        504s at submit — before burning the prefill (the old check
        only priced decode)."""
        from veles_tpu.services.lifecycle import DeadlineExceeded
        eng, toks = engine
        eng.wait(eng.submit_async(toks[0, :33].tolist(), 4))  # warm
        assert eng._prefill_ms_per_tok > 0
        # a deadline smaller than the measured prefill estimate alone
        est_ms = eng._prefill_ms_per_tok * 33
        h = eng.submit_async(toks[0, :33].tolist(), 4,
                             deadline_ms=max(est_ms * 0.2, 0.1))
        with pytest.raises(DeadlineExceeded):
            eng.wait(h)

    def test_health_status_carries_prefill_surface(self, lm,
                                                   f32_precision):
        from veles_tpu.services.restful import RESTfulAPI
        gen, toks = lm
        api = RESTfulAPI(lambda x: x, (gen.max_len,), port=0,
                         generator=gen, continuous_slots=2,
                         prefill_segment=6)
        try:
            h = api.health_status()
            assert "queued_prefill_tokens" in h
            assert "p50_ms_per_tok" in h
            assert "prefill_ms_per_tok" in h
        finally:
            api.stop()
