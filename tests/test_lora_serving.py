"""LoRA end-to-end serving (r4 verdict #8): an int8-quantized base with
f32 rank-r adapters serves over REST through the continuous-batching
engine, adapters ship as a tiny standalone package with sha256 base
lineage, and merge-at-export folds them away for zero-overhead serving.

Slow-tier (conftest.SLOW_MODULES): two small LM trainings (~40 s on
the 1-core box) — the budget cost is documented there."""

import json
import urllib.request

import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.models import zoo
from veles_tpu.models.generate import LMGenerator
from veles_tpu.models.standard_workflow import StandardWorkflow
from veles_tpu.services.export import (apply_lora_adapters,
                                       export_lora_adapters,
                                       export_workflow,
                                       load_lora_adapters,
                                       merge_lora_params)

N, T, VOCAB = 128, 12, 11


def _tokens(shift):
    """The +shift ramp task: next token = (cur + shift) %% VOCAB."""
    r = np.random.RandomState(3)
    return ((np.arange(T)[None, :] * shift + r.randint(0, 3, N)[:, None])
            % VOCAB).astype(np.int32)


def _train(layers, toks, name, epochs=8, warm=None):
    prng.seed_all(23)
    loader = FullBatchLoader(None, data=toks, labels=toks,
                             minibatch_size=32,
                             class_lengths=[0, 32, 96])
    wf = StandardWorkflow(layers=layers, loader=loader, loss="lm",
                          decision_config={"max_epochs": epochs},
                          name=name)
    wf.initialize()
    if warm is not None:
        n_restored, _ = wf.warm_start({"params": warm})
        assert n_restored > 0
    wf.run()
    return wf


@pytest.fixture(scope="module")
def adapted():
    """Base LM trained on the +1 ramp, then rank-2 q/v adapters
    fine-tuned on the +2 ramp with the base frozen (the r4 CLI drill,
    in-process)."""
    base = _train(zoo.transformer_lm(vocab_size=VOCAB, d_model=16,
                                     n_heads=2, n_layers=1, lr=5e-3,
                                     dropout=0.0),
                  _tokens(1), "lora-base")
    base_host = base.trainer.host_params()
    wf = _train(zoo.transformer_lm(vocab_size=VOCAB, d_model=16,
                                   n_heads=2, n_layers=1, lr=5e-2,
                                   dropout=0.0, lora_rank=2),
                _tokens(2), "lora-adapted", warm=base_host)
    return base, wf


def test_int8_base_f32_adapters_over_rest(adapted):
    """The quant allowlist passes the lora subtree through — proven
    over REST: the continuous engine serves the int8-base adapted
    model, output == the float adapted generator's continuation and
    != the base model's (it learned a different task)."""
    from veles_tpu.ops.quant import QuantWeight
    from veles_tpu.services.restful import RESTfulAPI

    base, wf = adapted
    gen_q = LMGenerator(wf.trainer, max_len=T, weights="int8")
    block = next(k for k in gen_q.params if "transformer" in k)
    assert isinstance(gen_q.params[block]["mha"]["wq"], QuantWeight)
    lora = gen_q.params[block]["mha"]["lora"]
    assert not isinstance(lora["qa"], QuantWeight)  # adapters stay f32

    gen_f = LMGenerator(wf.trainer, max_len=T)
    gen_b = LMGenerator(base.trainer, max_len=T)
    prompt = _tokens(2)[0, :6]
    api = RESTfulAPI(lambda x: x, (T,), port=0, generator=gen_q,
                     continuous_slots=2)
    api.start()
    try:
        req = urllib.request.Request(
            "http://127.0.0.1:%d/service" % api.port,
            json.dumps({"input": prompt.tolist(),
                        "generate": {"max_new": 4}}).encode(),
            headers={"Content-Type": "application/json"})
        out = json.loads(urllib.request.urlopen(req).read())["result"]
    finally:
        api.stop()
    want = gen_f.generate(prompt[None], max_new=4)[0].tolist()
    base_out = gen_b.generate(prompt[None], max_new=4)[0].tolist()
    assert out[0] == want                     # int8+adapters == float
    assert out[0][6:] != base_out[6:]         # adapters changed the task


def test_adapter_package_roundtrip_lineage_and_size(adapted, tmp_path):
    import os

    base, wf = adapted
    ap = str(tmp_path / "adapters.zip")
    meta = export_lora_adapters(wf, ap)
    assert meta["kind"] == "lora_adapters" and meta["layers"]
    full = str(tmp_path / "full.zip")
    export_workflow(wf, full)
    assert os.path.getsize(ap) < os.path.getsize(full) / 4

    tree, meta2 = load_lora_adapters(ap)
    assert meta2["base_sha256"] == meta["base_sha256"]
    blk = next(iter(tree))
    got = tree[blk]["mha"]["lora"]["qb"]
    want = np.asarray(
        wf.trainer.host_params()[blk]["mha"]["lora"]["qb"])
    np.testing.assert_array_equal(got, want)
    assert np.abs(want).max() > 0             # the adapters DID train

    # graft onto a fresh same-base model: outputs == the adapted model
    fresh = _train(zoo.transformer_lm(vocab_size=VOCAB, d_model=16,
                                      n_heads=2, n_layers=1,
                                      dropout=0.0, lora_rank=2),
                   _tokens(2), "lora-fresh", epochs=1,
                   warm=base.trainer.host_params())
    # un-train fresh's own adapter attempt back to the base weights
    fresh.warm_start({"params": base.trainer.host_params()})
    with pytest.raises(ValueError, match="different base"):
        # fresh's 1-epoch run nudged nothing base (frozen) — but ITS
        # sha is computed over base leaves, which warm_start restored;
        # the strict check must still reject a truly different base:
        other = _train(zoo.transformer_lm(vocab_size=VOCAB, d_model=16,
                                          n_heads=2, n_layers=1,
                                          dropout=0.0, lora_rank=2),
                       _tokens(1), "lora-other", epochs=1)
        apply_lora_adapters(other, ap)
    meta3 = apply_lora_adapters(fresh, ap)    # same base: accepted
    assert meta3["base_sha256"] == meta["base_sha256"]
    gen_g = LMGenerator(fresh.trainer, max_len=T)
    gen_f = LMGenerator(wf.trainer, max_len=T)
    prompt = _tokens(2)[1, :6]
    np.testing.assert_array_equal(
        gen_g.generate(prompt[None], max_new=4),
        gen_f.generate(prompt[None], max_new=4))


def test_merge_at_export_drops_adapters_exactly(adapted):
    """W + A·B folded into the base: merged rank-0 model == adapted
    model.  Exact in f32 numpy (x·W + (x·A)·B == x·(W + A·B)); the
    live bf16 forwards agree to bf16 rounding with identical argmax."""
    _, wf = adapted
    host = wf.trainer.host_params()
    merged = merge_lora_params(host)
    blk = next(k for k in merged
               if isinstance(merged[k], dict) and "mha" in merged[k])
    assert "lora" not in merged[blk]["mha"]
    # algebraic exactness in f32 numpy at the projection level
    x = np.random.RandomState(0).randn(5, 16).astype(np.float32)
    lora = host[blk]["mha"]["lora"]
    adapted_q = x @ np.asarray(host[blk]["mha"]["wq"], np.float32) \
        + (x @ np.asarray(lora["qa"], np.float32)) \
        @ np.asarray(lora["qb"], np.float32)
    merged_q = x @ np.asarray(merged[blk]["mha"]["wq"], np.float32)
    np.testing.assert_allclose(merged_q, adapted_q, rtol=1e-5,
                               atol=1e-6)
    # end-to-end through the live (bf16-policy) forward
    plain = _train(zoo.transformer_lm(vocab_size=VOCAB, d_model=16,
                                      n_heads=2, n_layers=1,
                                      dropout=0.0),
                   _tokens(2), "lora-merged", epochs=1)
    plain.trainer.load_params(merged)
    toks = _tokens(2)[:4]
    out_m = np.asarray(plain.forward_fn()(plain.trainer.params, toks))
    out_a = np.asarray(wf.forward_fn()(wf.trainer.params, toks))
    np.testing.assert_allclose(out_m, out_a, rtol=5e-2, atol=5e-2)
    np.testing.assert_array_equal(out_m.argmax(-1), out_a.argmax(-1))


def test_serve_time_adapter_loading_via_config(adapted, tmp_path):
    """root.common.serve.lora_adapters=PATH: the serve path grafts an
    adapters package onto the (base) workflow before the generator
    snapshots params — serving base checkpoint + MB-scale adapters
    reproduces the adapted model exactly."""
    from veles_tpu.__main__ import Main
    from veles_tpu.config import root

    base, wf = adapted
    ap = str(tmp_path / "serve_adapters.zip")
    export_lora_adapters(wf, ap)
    # a same-base workflow with FRESH (random) adapters, as a restart
    # from the base snapshot would produce
    fresh = _train(zoo.transformer_lm(vocab_size=VOCAB, d_model=16,
                                      n_heads=2, n_layers=1,
                                      dropout=0.0, lora_rank=2),
                   _tokens(2), "serve-fresh", epochs=1,
                   warm=base.trainer.host_params())
    fresh.warm_start({"params": base.trainer.host_params()})
    prev = root.common.serve.get("lora_adapters", None)
    root.common.serve.lora_adapters = ap
    try:
        gen = Main._make_generator(fresh)
    finally:
        root.common.serve.lora_adapters = prev
    assert gen is not None
    want = LMGenerator(wf.trainer, max_len=T)
    prompt = _tokens(2)[2, :6]
    np.testing.assert_array_equal(
        gen.generate(prompt[None], max_new=4),
        want.generate(prompt[None], max_new=4))


# --------------------------------------------------------------------------
# Multi-LoRA serving: one slot pool, per-request adapter routing
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def two_adapters(adapted):
    """The module's base + its +2-ramp adapter, plus a SECOND adapter
    fine-tuned on the +3 ramp — three behaviors one pool must route."""
    base, wf2 = adapted
    base_host = base.trainer.host_params()
    wf3 = _train(zoo.transformer_lm(vocab_size=VOCAB, d_model=16,
                                    n_heads=2, n_layers=1, lr=5e-2,
                                    dropout=0.0, lora_rank=2),
                 _tokens(3), "lora-adapted-3", warm=base_host)
    return base, wf2, wf3


def _bank_generator(base, adapters, max_len=12):
    from veles_tpu.models.generate import LMGenerator
    gen = LMGenerator(base.trainer, max_len=max_len)
    n = gen.load_adapter_bank([wf.trainer.host_params()
                               for wf in adapters])
    assert n == len(adapters)
    return gen


@pytest.mark.parametrize("mode", ["dense", "paged", "paged_k4"])
def test_pool_routes_adapters_per_request(two_adapters, mode,
                                          f32_precision):
    """One pool serving base + two adapters interleaved: every stream
    must equal the SOLO generation of its own model (base wf / adapted
    wf with the adapter's params) — adapter routing can neither leak
    across slots nor drift from single-model decoding."""
    from veles_tpu.models.generate import (ContinuousBatcher,
                                           LMGenerator,
                                           PagedContinuousBatcher)
    base, wf2, wf3 = two_adapters
    gen = _bank_generator(base, [wf2, wf3])
    if mode == "dense":
        cb = ContinuousBatcher(gen, slots=3)
    else:
        cb = PagedContinuousBatcher(
            gen, slots=3, block=4, pool_tokens=48,
            ticks_per_dispatch=4 if mode == "paged_k4" else 1)
    prompt = _tokens(1)[0, :4].tolist()
    rids = [cb.submit(prompt, 6, adapter=a) for a in (0, 1, 2)]
    cb.run_all()
    solo = {0: LMGenerator(base.trainer, max_len=12),
            1: LMGenerator(wf2.trainer, max_len=12),
            2: LMGenerator(wf3.trainer, max_len=12)}
    for a, rid in zip((0, 1, 2), rids):
        want = solo[a].generate(
            np.asarray([prompt], np.int32), 6)[0].tolist()
        assert cb.pop_result(rid) == want, "adapter %d (%s)" % (a,
                                                                mode)
    # adapters genuinely distinct behaviors, or routing proved nothing
    outs = [solo[a].generate(np.asarray([prompt], np.int32),
                             6)[0].tolist() for a in (0, 1, 2)]
    assert len({tuple(o) for o in outs}) >= 2


def test_adapter_id_validation(two_adapters, f32_precision):
    from veles_tpu.models.generate import ContinuousBatcher, LMGenerator
    base, wf2, _ = two_adapters
    gen = _bank_generator(base, [wf2])
    cb = ContinuousBatcher(gen, slots=2)
    with pytest.raises(ValueError, match="outside the loaded bank"):
        cb.submit([1, 2], 4, adapter=2)
    bare = ContinuousBatcher(LMGenerator(base.trainer, max_len=12),
                             slots=2)
    with pytest.raises(ValueError, match="outside the loaded bank"):
        bare.submit([1, 2], 4, adapter=1)


def test_bank_rejects_single_lora_params(two_adapters, f32_precision):
    """A generator whose params already carry a live 'lora' subtree
    must not silently double-apply — banks demand explicit members."""
    from veles_tpu.models.generate import LMGenerator
    base, wf2, _ = two_adapters
    gen = LMGenerator(wf2.trainer, max_len=12)    # adapted params
    with pytest.raises(ValueError, match="single 'lora'"):
        gen.load_adapter_bank([wf2.trainer.host_params()])


def test_bank_load_is_atomic_on_bad_adapter(two_adapters,
                                            f32_precision):
    """A mid-list failure (adapter without lora) must leave params
    untouched — never a half-banked generator."""
    from veles_tpu.models.generate import LMGenerator
    base, wf2, _ = two_adapters
    gen = LMGenerator(base.trainer, max_len=12)
    bad = {k: v for k, v in base.trainer.host_params().items()}
    with pytest.raises(ValueError, match="no lora subtree"):
        gen.load_adapter_bank([wf2.trainer.host_params(), bad])
    assert not any("lora_bank" in gen.params[l.name].get("mha", {})
                   for l in gen._blocks)
    assert getattr(gen, "_n_adapters", 0) == 0


def test_engine_blocking_submit_routes_adapter(two_adapters,
                                               f32_precision):
    """ContinuousEngine.submit(..., adapter=k) must actually route —
    the silent-base-model regression."""
    from veles_tpu.models.generate import LMGenerator
    from veles_tpu.services.restful import ContinuousEngine
    base, wf2, _ = two_adapters
    gen = _bank_generator(base, [wf2])
    eng = ContinuousEngine(gen, slots=2)
    try:
        prompt = _tokens(1)[0, :4].tolist()
        got = list(map(int, eng.submit(prompt, 6, adapter=1)))
        want = LMGenerator(wf2.trainer, max_len=12).generate(
            np.asarray([prompt], np.int32), 6)[0].tolist()
        assert got == want
        with pytest.raises(ValueError, match="outside the loaded"):
            eng.submit(prompt, 6, adapter=9)
    finally:
        eng.stop()


def test_prefix_cache_keys_include_adapter(two_adapters,
                                           f32_precision):
    """Same prompt, different adapters -> different prefix K/V: the
    prefix cache must NOT share blocks across adapters, and each
    stream still matches its solo model."""
    from veles_tpu.models.generate import (LMGenerator,
                                           PagedContinuousBatcher)
    base, wf2, _ = two_adapters
    gen = _bank_generator(base, [wf2])
    cb = PagedContinuousBatcher(gen, slots=2, block=4, pool_tokens=48,
                                prefix_cache=True)
    prompt = _tokens(1)[0, :9].tolist()           # 2 shareable blocks
    free0 = cb.free_blocks()
    r0 = cb.submit(prompt, 3, adapter=0)
    r1 = cb.submit(prompt, 3, adapter=1)
    cb.tick()
    # 3 + 3 blocks (12 tokens each), ZERO shared across adapters
    assert free0 - cb.free_blocks() == 6
    cb.run_all()
    assert cb.pop_result(r0) == LMGenerator(
        base.trainer, max_len=12).generate(
            np.asarray([prompt], np.int32), 3)[0].tolist()
    assert cb.pop_result(r1) == LMGenerator(
        wf2.trainer, max_len=12).generate(
            np.asarray([prompt], np.int32), 3)[0].tolist()
    # and WITHIN one adapter sharing still works
    free1 = cb.free_blocks()
    r2 = cb.submit(prompt, 3, adapter=1)
    r3 = cb.submit(prompt, 3, adapter=1)
    cb.tick()
    assert free1 - cb.free_blocks() == 4          # 2 shared
    cb.run_all()
    assert cb.pop_result(r2) == cb.pop_result(r3)
