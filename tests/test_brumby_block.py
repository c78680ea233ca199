"""The block that Brumby-14B-Base needs, as configuration of the one
``TransformerBlock`` — ``mixer="power_retention"`` (a fixed-size
float32 state a slot where attention keeps a cache) and
``ffn="gated_silu"`` — against the plain QUADRATIC reference
``benchmarks/reference_brumby.py`` at a tiny size on the CPU, seeded
weights; through ``LMGenerator.generate``, the dense ``ContinuousBatcher``
and the paged one's slot-major state group; a hybrid of a retention and
an attention layer; and what is refused.

Tolerances: the model is built with float32 parameters that are exact
copies of the bfloat16-representable seeded values and computes in
float32 (``precision_level`` 1), as the reference does; what is left is
the order of float32 sums and the state's ``exp(b_t) S`` where the
quadratic form has ``exp(b_t - b_s)``: under 2e-5 on logits of magnitude
1 — ``atol=1e-4`` on logits (a state kept in bfloat16 reads 9e-4 and
fails it); a ``logit_gap`` reads 0.0 wherever no served token's
reference logit lies under the reference's best."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import build_brumby, reference_brumby  # noqa: E402
from veles_tpu.models import zoo  # noqa: E402
from veles_tpu.models.generate import (  # noqa: E402
    ContinuousBatcher, LMGenerator, PagedContinuousBatcher)
from veles_tpu.ops import retention  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "tests", "data", "tiny_brumby",
                       "benchmarks", "configs", "tiny-brumby.json")) as _f:
    CFG = json.load(_f)
SEED = 2 ** 31 + 7
MAX_LEN = 96
ATOL = 1e-4


@pytest.fixture
def f32_compute():
    from veles_tpu.config import root
    prev = root.common.engine.get("precision_level", 0)
    root.common.engine.precision_level = 1
    try:
        yield
    finally:
        root.common.engine.precision_level = prev


_MODEL = []


@pytest.fixture
def model(f32_compute):
    """The tiny model in float32 compute, its workflow and generator
    (built once)."""
    if not _MODEL:
        wf = build_brumby.build_workflow(CFG, MAX_LEN, param="float32")
        build_brumby.install_weights(wf.trainer, CFG, SEED)
        gen = LMGenerator(wf.trainer, max_len=MAX_LEN)
        gen.prefill_min = 4
        _MODEL.append((wf, gen))
    return _MODEL[0]


def _prompt(n, stream=0):
    return np.random.default_rng([7, stream]).integers(
        0, CFG["vocab_size"], n).tolist()


def _gap(prompt, result):
    return reference_brumby.logit_gaps(
        CFG, SEED, [{"prompt": prompt, "result": result}])


def _solo(gen, prompt, max_new):
    return gen.generate(np.asarray(prompt)[None], max_new)[0].tolist()


def test_the_stack_is_the_configurations(model):
    _, gen = model
    for layer in gen._blocks:
        assert layer.retention and layer.ffn_kind == "gated_silu"
        assert layer.norm_kind == "rms" and layer.norm_eps == 1e-6
        assert layer.cache_leaves() == {} and layer.cache_span() is None
        assert layer.state_leaves() == {"s": (2, 16, 256), "z": (2, 256)}
        p = gen.params[layer.name]
        assert sorted(p) == ["ffn", "ln1", "ln2", "mha"]
        assert sorted(p["mha"]) == ["bg", "k_norm", "q_norm", "wg", "wk",
                                    "wo", "wq", "wv"]
        assert p["mha"]["wg"].shape == (32, 2)
        assert sorted(p["ffn"]) == ["w_down", "w_gate", "w_up"]
        assert p["ffn"]["w_gate"].shape == (32, 64)
    assert gen._stateful and gen._rolling and gen._ring_spans() == ()
    caches = gen._init_caches(3, jnp.bfloat16)
    assert all(isinstance(c, retention.RetentionState) for c in caches)
    # float32 whatever the cache dtype
    assert caches[0].s.shape == (3, 2, 16, 256) \
        and caches[0].s.dtype == jnp.float32


def test_every_default_is_the_block_the_zoo_always_built():
    plain = zoo.transformer_lm(n_layers=1)[2]
    assert "mixer" not in plain and "ffn" not in plain
    both = zoo.transformer_lm(n_layers=2, pos="rope",
                              mixer=["power_retention", None])
    assert [layer.get("mixer") for layer in both[1:3]] == [
        "power_retention", None]


def test_the_full_forward_is_the_references(model):
    wf, _ = model
    prompt = _prompt(70)
    toks = np.zeros((1, MAX_LEN), np.int32)
    toks[0, :70] = prompt
    full = np.asarray(jax.jit(wf.trainer._forward, static_argnums=(2,))(
        wf.trainer.params, jnp.asarray(toks), False, jax.random.key(0)),
        np.float32)[0, :70]
    ref = reference_brumby.forward_logits(CFG, SEED, [prompt],
                                          [list(range(70))])[0]
    assert float(jnp.abs(ref).max()) > 0.5
    np.testing.assert_allclose(full, np.asarray(ref), atol=ATOL)


def test_each_control_parts_from_the_reference():
    """A control that read 0 would guard nothing: every one of them
    moves the logits of a sequence past two pass boundaries."""
    prompt = _prompt(40)
    where = [list(range(20, 40))]
    ref = reference_brumby.forward_logits(CFG, SEED, [prompt], where)[0]
    for control in (dict(state_reset=8), dict(gate_off=True),
                    dict(softmax_attention=True), dict(precision="int8")):
        other = reference_brumby.forward_logits(CFG, SEED, [prompt], where,
                                                **control)[0]
        assert float(jnp.abs(other - ref).max()) > 1e-2, control


def _prefill_then_step(gen, prompt, cut, spoil=None):
    """Logits at positions [cut, len) from a prefill of the first
    ``cut`` tokens (the chunk padded to a power of two, ``valid`` =
    cut) and the recurrence after it.  ``spoil``: applied to the state
    between the two."""
    tp = gen._bucket(cut, gen.max_len)
    toks = np.zeros((1, tp), np.int32)
    toks[0, :min(tp, len(prompt))] = prompt[:tp]
    caches = gen._prefill_fn(1, tp)(gen.params, jnp.asarray(toks),
                                    jnp.int32(cut))
    if spoil:
        caches = jax.tree_util.tree_map(spoil, caches)
    step, out = jax.jit(gen._step), []
    for pos in range(cut, len(prompt)):
        logits, caches = step(gen.params, caches,
                              jnp.asarray(prompt[pos:pos + 1]),
                              jnp.int32(pos))
        out.append(logits[0])
    return np.stack(out)


@pytest.mark.parametrize("cut", [1, 13, 32, 47])
def test_prefill_then_decode_through_the_state_is_the_full_forward(
        model, cut):
    _, gen = model
    prompt = _prompt(60, 3)
    ref = reference_brumby.forward_logits(
        CFG, SEED, [prompt], [list(range(cut, 60))])[0]
    np.testing.assert_allclose(_prefill_then_step(gen, prompt, cut),
                               np.asarray(ref), atol=ATOL)


def test_a_state_kept_in_bfloat16_fails_the_tolerance(model):
    _, gen = model
    prompt = _prompt(60, 3)
    ref = reference_brumby.forward_logits(
        CFG, SEED, [prompt], [list(range(32, 60))])[0]
    rounded = _prefill_then_step(
        gen, prompt, 32,
        spoil=lambda a: a.astype(jnp.bfloat16).astype(jnp.float32))
    assert float(np.abs(rounded - np.asarray(ref)).max()) > 5 * ATOL


def test_generate_is_the_references_first_choice(model):
    _, gen = model
    for n, stream in ((5, 0), (37, 1), (70, 2)):
        prompt = _prompt(n, stream)
        out = _solo(gen, prompt, 12)
        gap, count = _gap(prompt, out)
        assert gap == 0.0 and count == 12
    # the scan without a prefill says the same
    gen_scan = LMGenerator(model[0].trainer, max_len=MAX_LEN)
    gen_scan.prefill_min = 10 ** 9
    assert _solo(gen_scan, _prompt(37, 1), 12) == _solo(gen, _prompt(37, 1),
                                                        12)


REQUESTS = ((5, 12), (37, 20), (50, 9), (20, 30), (70, 12), (2, 6), (1, 5))


def _streams(cb, idle_slot=True, ticks=None):
    rids = [cb.submit(_prompt(n, n), m) for n, m in REQUESTS]
    idle_seen = False
    while not cb.idle():
        cb.tick()
        idle_seen |= cb.last_tick["rows"] < cb.slots
        if ticks is not None:
            ticks.append(cb.last_tick)
    assert idle_seen or not idle_slot   # a tick with an idle slot ran
    return [cb.result(r) for r in rids]


def test_the_dense_batcher_is_the_solo_continuation(model):
    _, gen = model
    solo = [_solo(gen, _prompt(n, n), m) for n, m in REQUESTS]
    assert _streams(ContinuousBatcher(gen, slots=2), False) == solo


@pytest.fixture(params=[False, True], ids=["xla_step", "kernel"])
def step_form(request):
    prev, retention.KERNEL = retention.KERNEL, request.param
    try:
        yield request.param
    finally:
        retention.KERNEL = prev


@pytest.mark.parametrize("segment", [0, 8, 16],
                         ids=["whole", "passes_of_8", "passes_of_16"])
def test_the_paged_batcher_is_the_solo_continuation(model, segment,
                                                    step_form):
    """Seven requests through two slots (every slot reused after a
    release; a whole prefill, staged passes of two lengths, prompts of
    one and two tokens): the solo continuation's tokens, and the
    reference's first choices."""
    _, gen = model
    cb = PagedContinuousBatcher(gen, slots=2, block=8,
                                prefill_segment=segment)
    assert cb.pool_blocks == 0 and cb._blocks_needed(70, 12) == 0
    assert cb.free_blocks() == 0 and cb.blocks_in_use() == (0, 0)
    assert cb._will_segment(50) == bool(segment)
    ticks = []
    got = _streams(cb, ticks=ticks)
    assert got == [_solo(gen, _prompt(n, n), m) for n, m in REQUESTS]
    for (n, _), result in zip(REQUESTS[:3], got):
        assert _gap(_prompt(n, n), result)[0] == 0.0
    assert cb.state_in_use() == (0, 0)
    row = 2 * 4 * (2 * 16 * 256 + 2 * 256)
    assert cb._state_row_bytes == row
    # the last tick that carried a row moved one row's state (the kernel
    # skips the idle slot; XLA's step moves both); the drained tail's
    # dispatch had only the frozen row in it, which the kernel skips too
    moved = 1 if step_form else 2
    carried = [tick for tick in ticks if tick["rows"]][-1]
    assert carried["state_rows"] == moved
    assert carried["state_bytes"] == 2 * moved * row
    assert ticks[-1]["rows"] == 0 and ticks[-1]["drained"] == 0
    assert ticks[-1]["state_rows"] == (0 if step_form else 2)


def test_the_ticks_count_the_state_and_the_chunks(model):
    _, gen = model
    cb = PagedContinuousBatcher(gen, slots=2, block=8, prefill_segment=16)
    cb.submit(_prompt(50, 1), 4)
    chunks, held = 0, []
    while not cb.idle():
        cb.tick()
        chunks += cb.last_tick["staged_chunks"]
        held.append(cb.state_in_use())
    # 49 tokens of prefill work: passes of 16, 16, 16 and a tail of 2
    assert chunks == 4
    assert max(held) == (1, cb._state_row_bytes)


def test_a_hybrid_of_a_retention_and_a_paged_layer_matches(f32_compute):
    """One retention layer, one attention layer: the paged batcher
    builds both kinds of group, and its streams are the solo
    continuation's and the dense batcher's."""
    from veles_tpu import prng
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.models.standard_workflow import StandardWorkflow
    prng.seed_all(11)
    rows = np.random.default_rng(3).integers(0, 64, (8, 48)).astype(
        np.int32)
    wf = StandardWorkflow(
        layers=zoo.transformer_lm(
            vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
            head_dim=16, d_ff=64, pos="rope", norm="rms", bias=False,
            qk_norm=True, mixer=["power_retention", "attention"],
            ffn="gated_silu", dropout=0.0, lr=1e-3),
        loader=FullBatchLoader(None, data=rows, labels=rows,
                               minibatch_size=4, class_lengths=[0, 4, 4]),
        loss="lm", decision_config={"max_epochs": 1}, name="hybrid")
    wf.initialize()
    wf.run()                            # the hybrid trains a step too
    gen = LMGenerator(wf.trainer, max_len=48)
    gen.prefill_min = 4
    assert [bool(layer.state_leaves()) for layer in gen._blocks] == [
        True, False]
    cb = PagedContinuousBatcher(gen, slots=2, block=4, pool_tokens=96,
                                prefill_segment=8)
    assert cb._paged_layers == [1] and cb.pool_blocks == 24
    requests = ((20, 12), (5, 9), (30, 10))
    rids = [cb.submit(rows[i, :n].tolist(), m)
            for i, (n, m) in enumerate(requests)]
    cb.run_all()
    solo = [gen.generate(rows[i:i + 1, :n], m)[0].tolist()
            for i, (n, m) in enumerate(requests)]
    assert [cb.result(r) for r in rids] == solo
    assert cb.blocks_in_use() == (0, 0) and cb.state_in_use() == (0, 0)
    dense = ContinuousBatcher(gen, slots=2)
    rids = [dense.submit(rows[i, :n].tolist(), m)
            for i, (n, m) in enumerate(requests)]
    dense.run_all()
    assert [dense.result(r) for r in rids] == solo


def test_a_tiny_brumby_trains_a_step_and_serves_over_rest(f32_compute):
    """``zoo.transformer_lm`` -> ``StandardWorkflow`` (an epoch of
    training: the chunked scan's backward) -> ``LMGenerator`` ->
    ``RESTfulAPI`` on a paged batcher: the answer is the solo
    continuation."""
    import http.client
    from veles_tpu import prng
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.models.standard_workflow import StandardWorkflow
    from veles_tpu.services.restful import RESTfulAPI
    prng.seed_all(13)
    rows = ((np.arange(32)[None, :] * 3 + np.arange(16)[:, None]) % 50
            ).astype(np.int32)
    wf = StandardWorkflow(
        layers=zoo.transformer_lm(
            vocab_size=50, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
            head_dim=16, d_ff=64, pos="rope", norm="rms", bias=False,
            qk_norm=True, mixer="power_retention", ffn="gated_silu",
            dropout=0.0, lr=3e-3),
        loader=FullBatchLoader(None, data=rows, labels=rows,
                               minibatch_size=8, class_lengths=[0, 8, 8]),
        loss="lm", decision_config={"max_epochs": 3}, name="tiny-brumby")
    wf.initialize()
    before = jax.tree_util.tree_map(np.asarray, wf.trainer.params)
    wf.run()
    moved = jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a) - b).max()),
        wf.trainer.params, before)
    block = wf.trainer.params[[layer.name for layer in wf.trainer.layers
                               if layer.type == "transformer_block"][0]]
    assert all(np.isfinite(v) for v in jax.tree_util.tree_leaves(moved))
    assert float(np.abs(np.asarray(block["mha"]["wg"])
                        - before_leaf(before, wf, "wg")).max()) > 0
    gen = LMGenerator(wf.trainer, max_len=32)
    api = RESTfulAPI(lambda x: x, (32,), port=0, generator=gen,
                     continuous_slots=2, paged_block=4, prefill_segment=8)
    api.start()
    try:
        prompt = rows[0, :14].tolist()
        conn = http.client.HTTPConnection(api.host, api.port, timeout=60)
        conn.request("POST", api.path, json.dumps(
            {"input": prompt, "generate": {"max_new": 6}}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200, body
        assert body["result"][0] == gen.generate(
            rows[:1, :14], 6)[0].tolist()
        metrics = api.engine.metrics()
        assert metrics["state_slots_in_use"] == 0
        assert metrics["p50_tick_state_rows"] > 0
        assert "free_kv_blocks" in metrics and metrics["free_kv_blocks"] == 0
        leaks = api.engine.leak_check()
        assert leaks["kv_blocks_leaked"] == 0 \
            and leaks["state_slots_held"] == 0
    finally:
        api.stop()


def before_leaf(before, wf, name):
    first = [layer.name for layer in wf.trainer.layers
             if layer.type == "transformer_block"][0]
    return before[first]["mha"][name]


# ---------------------------------------------------------------- refusals
def test_prefix_sharing_over_a_state_is_refused(model):
    with pytest.raises(ValueError, match="snapshotted"):
        PagedContinuousBatcher(model[1], slots=2, block=8,
                               prefix_cache=True)


def test_pool_tokens_without_a_paged_layer_is_refused(model):
    with pytest.raises(ValueError, match="no pool"):
        PagedContinuousBatcher(model[1], slots=2, block=8, pool_tokens=64)


@pytest.mark.parametrize("extra,match", [
    (dict(window=8), "window"),
    (dict(indexer={"heads": 2, "head_dim": 8, "topk": 4}), "indexer"),
    (dict(impl="ring"), "sequence-parallel"),
    (dict(lora_rank=2), "lora_rank")])
def test_what_a_retention_block_cannot_take_is_refused(extra, match):
    from veles_tpu.models.layers import make_layer
    cfg = dict(zoo.transformer_lm(n_layers=1, pos="rope",
                                  mixer="power_retention")[1], **extra)
    with pytest.raises(ValueError, match=match):
        make_layer(cfg).setup((16, 64))


def test_speculation_over_a_state_is_refused(model):
    with pytest.raises(ValueError, match="rolling"):
        ContinuousBatcher(model[1], slots=2, speculative_k=4)


def test_gated_silu_is_the_dense_ffns_only():
    from veles_tpu.models.layers import make_layer
    cfg = dict(zoo.transformer_lm(n_layers=1, ffn="gated_silu",
                                  n_experts=4)[2])
    with pytest.raises(ValueError, match="DENSE"):
        make_layer(cfg).setup((16, 64))
