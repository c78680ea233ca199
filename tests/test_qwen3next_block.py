"""The block that Qwen3-Next-80B-A3B needs, as configuration of the one
``TransformerBlock`` — ``mixer="gated_deltanet"`` (a float32 delta-rule
state and a conv tail a slot), a gated attention layer with partial
rotary positions at head_dim 256's shape, zero-centred gains, softmax
top-k routing over a held share of the experts and a sigmoid-gated
shared expert — against the plain reference ``benchmarks/
reference_qwen3next.py`` (the delta rule token by token) at a tiny size
on the CPU, seeded weights: the chunked pass, the decode kernel
(interpret mode), the attention, the held share, prefill in passes then
decode through the paged batcher with state and pool side by side, and
what is refused.

Tolerances: the model is built with float32 parameters that are exact
copies of the bfloat16-representable seeded values and computes in
float32 (``precision_level`` 1), as the reference does; what is left is
the order of float32 sums and the chunked form's products where the
reference steps token by token: under 1e-5 on logits of magnitude 2 —
``atol=1e-4`` (a state rounded to bfloat16 between passes fails it); a
``logit_gap`` reads 0.0 wherever no served token's reference logit lies
under the reference's best."""

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

from benchmarks import (build_qwen3next, flops_qwen3next,  # noqa: E402
                        reference_qwen3next as ref)
from benchmarks.reference import seed_key  # noqa: E402
from veles_tpu.models import zoo  # noqa: E402
from veles_tpu.models.generate import (  # noqa: E402
    ContinuousBatcher, LMGenerator, PagedContinuousBatcher)
from veles_tpu.ops import attention, deltanet  # noqa: E402
from veles_tpu.ops.pallas import deltanet as deltanet_kernel  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "tests", "data",
                       "tiny_qwen3next", "benchmarks", "configs",
                       "tiny-qwen3next.json")) as _f:
    CFG = json.load(_f)
SEED = 2 ** 31 + 11
MAX_LEN = 128
ATOL = 1e-4
SHAPE = dict(k_heads=2, v_heads=4, k_dim=8, v_dim=8, conv=4)


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
        wf = build_qwen3next.build_workflow(CFG, MAX_LEN, param="float32")
        build_qwen3next.install_weights(wf.trainer, CFG, SEED)
        gen = LMGenerator(wf.trainer, max_len=MAX_LEN)
        gen.prefill_min = 4
        _MODEL.append((wf, gen))
    return _MODEL[0]


def _prompt(n, stream=0):
    return np.random.default_rng([43, stream]).integers(
        0, CFG["vocab_size"], n).tolist()


def _solo(gen, prompt, max_new):
    return gen.generate(np.asarray(prompt)[None], max_new)[0].tolist()


def _gap(prompt, result):
    return ref.logit_gaps(CFG, SEED, [{"prompt": prompt, "result": result}])


def _inputs(b, h, t, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(k):
        u = jax.random.normal(k, (b, h, t, d))
        return u / jnp.linalg.norm(u, axis=-1, keepdims=True)

    return (unit(ks[0]) * d ** -0.5, unit(ks[1]),
            jax.random.normal(ks[2], (b, h, t, d)),
            jax.nn.sigmoid(jax.random.normal(ks[3], (b, h, t))),
            -0.2 * jax.nn.softplus(jax.random.normal(ks[4], (b, h, t))),
            0.1 * jax.random.normal(ks[5], (b, h, d, d)))


def _recurrence(q, k, v, beta, g):
    """The reference's token-by-token rule, a row at a time."""
    return jax.vmap(lambda *a: ref._recurrence(*(
        jnp.moveaxis(x, 1, 0) for x in a))[0])(q, k, v, beta, g)


# ----------------------------------------------------------- the mixer
@pytest.mark.parametrize("t,chunk", [(150, 32), (64, 64), (7, 64)])
def test_the_chunked_pass_is_the_recurrence(t, chunk):
    q, k, v, beta, g, _ = _inputs(2, 3, t, 8)
    o, s = deltanet.delta_chunk(q, k, v, beta, g, chunk=chunk)
    want = jnp.moveaxis(_recurrence(q, k, v, beta, g), 1, 2)
    np.testing.assert_allclose(o, want, atol=1e-5)
    # and the state it hands on is the recurrence's after the last token
    s_step = jax.lax.scan(
        lambda s, xs: (deltanet.deltanet_step(*xs, s)[1], None),
        jnp.zeros_like(s), tuple(jnp.moveaxis(a, 2, 0)
                                 for a in (q, k, v, beta, g)))[0]
    np.testing.assert_allclose(s, s_step, atol=1e-5)


def test_passes_with_ragged_valid_hand_the_state_and_tail_on():
    """A sequence through ``mixer_chunk`` in passes of 16 (each padded
    past its ``valid`` with tokens that must not enter S or the tail),
    the state handed from pass to pass, then token by token through
    ``mixer_step``: the whole pass's outputs at every position."""
    from veles_tpu import prng
    params = deltanet.mixer_init(prng.RandomGenerator("deltanet", seed=3),
                                 16, SHAPE)
    params["a_log"] = jnp.log(jnp.asarray([0.3, 0.05, 0.01, 0.002]))
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 45, 16))
    whole, _ = deltanet.mixer_chunk(params, x, None, 0, 0, SHAPE)
    state, outs = None, []
    for start, valid in ((0, 16), (16, 11), (27, 13)):
        part = jnp.pad(x[:, start:start + valid],
                       ((0, 0), (0, 16 - valid), (0, 0)),
                       constant_values=7.0)
        y, state = deltanet.mixer_chunk(params, part, state, start, 0,
                                        SHAPE, valid=valid)
        outs.append(y[:, :valid])
    for t in range(40, 45):
        y, state = deltanet.mixer_step(params, x[:, t:t + 1], state, t, 0,
                                       SHAPE)
        outs.append(y)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), whole,
                               atol=1e-5)


@pytest.mark.parametrize("rows", [1, 3])
def test_the_decode_kernel_is_the_step_and_skips_idle_rows(rows):
    q, k, v, beta, g, s = _inputs(rows, 32, 1, 128, seed=rows)
    q, k, v, beta, g = (a[:, :, 0] for a in (q, k, v, beta, g))
    active = jnp.arange(rows) % 2 == 0
    o, s_new = deltanet_kernel.deltanet_decode(s, q, k, v, beta, g, active,
                                               interpret=True)
    want_o, want_s = deltanet.deltanet_step(q, k, v, beta, g, s)
    for r in range(rows):
        if active[r]:
            np.testing.assert_allclose(o[r], want_o[r], atol=1e-5)
            np.testing.assert_allclose(s_new[r], want_s[r], atol=1e-5)
        else:
            assert float(jnp.abs(o[r]).max()) == 0.0
            np.testing.assert_array_equal(s_new[r], s[r])
    # the XLA form keeps an idle row the same way
    o2, s2 = deltanet.deltanet_step_rows(q, k, v, beta, g, s, active)
    if deltanet.rows_skipped() is False:
        np.testing.assert_allclose(o2, o, atol=1e-5)
        np.testing.assert_allclose(s2, s_new, atol=1e-5)


def test_the_kernel_holds_a_block_of_heads_a_step():
    assert deltanet_kernel.heads_per_step(32) == 16
    assert deltanet_kernel.heads_per_step(4) == 4
    assert deltanet_kernel.heads_per_step(24) == 12


# ------------------------------------------------------- the attention
def test_partial_rope_turns_the_first_dims_alone():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 5, 16))
    pos = jnp.arange(5) + 3
    turned = attention.rope(x, pos, 1e4, dim=8)
    np.testing.assert_array_equal(turned[..., 8:], x[..., 8:])
    np.testing.assert_allclose(turned[..., :8],
                               attention.rope(x[..., :8], pos, 1e4))
    np.testing.assert_allclose(
        jnp.swapaxes(turned, 1, 2)[0],
        ref._rotate(jnp.swapaxes(x, 1, 2)[0], pos, 1e4, 8), atol=1e-6)
    rows = attention._rope_rows(x[:, :, :1], pos[:1], 1e4, 8)
    np.testing.assert_allclose(rows, turned[:, :, :1], atol=1e-6)


def test_the_gated_attention_layer_is_the_references(model):
    """Layer 3 of the tiny model (gated attention, QK-norm zero-centred,
    rope over half of each head) on its own: the program's block ==
    the reference's layer."""
    wf, gen = model
    layer = gen._blocks[3]
    assert layer.mixer == "attention" and layer.cfg["attn_gate"]
    assert layer.cfg["rope_dim"] == 8 and layer.norm_kind == "rms_centered"
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 32))
    lw = ref.layer_weights(CFG, jax.random.fold_in(seed_key(SEED), 0), 3,
                           dtype=jnp.float32)
    got = layer.apply(lw, x)
    want = ref.make_layer(CFG, 64, "full_attention")(x[0], lw, 64)
    np.testing.assert_allclose(got[0], want, atol=1e-4)
    # the output gate matters: without it the layer reads otherwise
    plain = dict(lw, mha={k: v for k, v in lw["mha"].items()
                          if k != "wgate"})
    assert float(jnp.abs(layer.apply(plain, x)[0] - want).max()) > 1e-2


# ------------------------------------------------------- the held share
def test_the_held_shares_add_up_to_the_uncut_layer(model):
    """The guide's test of a share: the program's expert layer holding
    experts 0-3 and holding 4-7 of 8, their outputs summed with the
    gated shared expert counted once, is the uncut reference layer."""
    from veles_tpu.models.layers import make_layer
    wf, gen = model
    block = gen._blocks[0]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 64, 32))
    key = jax.random.fold_in(seed_key(SEED), 7)
    parts = []
    for held in ((0, 4), (4, 4)):
        cfg = dict(block.cfg, experts_held=held)
        share = make_layer(cfg)
        share.setup((MAX_LEN, 32))
        share.policy = block.policy
        lw = ref.layer_weights(CFG, key, 0, held=held, dtype=jnp.float32)
        parts.append(share._ffn(lw, x, False)[0])
    uncut = ref.layer_weights(CFG, key, 0, held=(0, 8), dtype=jnp.float32)
    whole = ref.make_ffn(CFG, 64, held=(0, 8))(x[0], uncut)
    shared_only = whole - ref.make_ffn(CFG, 64, held=(0, 8),
                                       shared=False)(x[0], uncut)
    np.testing.assert_allclose(parts[0][0] + parts[1][0] - shared_only,
                               whole, atol=1e-5)
    # and a share alone is not the whole
    assert float(jnp.abs(parts[0][0] - whole).max()) > 1e-3


# ------------------------------------------------------------ the model
def test_the_stack_is_the_configurations(model):
    wf, gen = model
    kinds = [layer.mixer for layer in gen._blocks]
    assert kinds == ["gated_deltanet"] * 3 + ["attention"]
    for layer in gen._blocks[:3]:
        assert layer.cache_leaves() == {}
        assert layer.state_leaves() == {"s": (4, 8, 8), "conv": (3, 64)}
        assert sorted(gen.params[layer.name]["mha"]) == [
            "a_log", "conv_w", "dt_bias", "norm", "w_ba", "w_qkvz", "wo"]
    attn = gen._blocks[3]
    assert attn.state_leaves() == {} and attn.cache_leaves() == {
        "k": (2, 16), "v": (2, 16)}
    assert sorted(gen.params[attn.name]["mha"]) == [
        "k_norm_centered", "q_norm_centered", "wgate", "wk", "wo", "wq",
        "wv"]
    for layer in gen._blocks:
        p = gen.params[layer.name]
        assert p["moe"]["router"].shape == (32, 8)
        assert p["moe"]["w_gate"].shape == (4, 32, 16)
        assert p["shared_gate"].shape == (32, 1)
        assert layer.router == "softmax_topk_renorm" and layer.top_k == 3
    assert gen._stateful and gen._ring_spans() == ()
    caches = gen._init_caches(2, jnp.bfloat16)
    assert all(isinstance(c, deltanet.DeltaNetState) for c in caches[:3])
    assert caches[0].s.dtype == jnp.float32 == caches[0].conv.dtype
    assert isinstance(caches[3], attention.KVCache)
    # the cut's arithmetic counts every leaf of the tree
    assert flops_qwen3next.parameters(CFG) == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
            wf.trainer.params))


def test_every_default_is_the_block_the_zoo_always_built():
    plain = zoo.transformer_lm(n_layers=1)[2]
    for key in ("mixer", "deltanet", "attn_gate", "rope_dim"):
        assert key not in plain
    hybrid = zoo.transformer_lm(
        n_layers=4, pos="rope", attn_gate="sigmoid", rope_dim=8,
        mixer=["gated_deltanet"] * 3 + ["attention"], deltanet=SHAPE)
    assert [layer.get("deltanet") for layer in hybrid[1:5]] == [SHAPE] * 3 \
        + [None]
    assert [layer.get("attn_gate") for layer in hybrid[1:5]] == [None] * 3 \
        + ["sigmoid"]


def test_the_full_forward_is_the_references(model):
    wf, _ = model
    prompt = _prompt(100)
    toks = np.zeros((1, MAX_LEN), np.int32)
    toks[0, :100] = prompt
    full = np.asarray(jax.jit(wf.trainer._forward, static_argnums=(2,))(
        wf.trainer.params, jnp.asarray(toks), False, jax.random.key(0)),
        np.float32)[0, :100]
    want = ref.forward_logits(CFG, SEED, [prompt], [list(range(100))])[0]
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(full, np.asarray(want), atol=ATOL)


def test_each_control_parts_from_the_reference():
    """A control that read 0 would guard nothing: every one of them
    moves the logits of a sequence past several pass boundaries by ten
    times the tolerance and more (a state kept in bfloat16 the least:
    3e-3 at these widths)."""
    prompt = _prompt(48)
    where = [list(range(36, 48))]
    want = ref.forward_logits(CFG, SEED, [prompt], where)[0]
    for control in (dict(state_reset=16), dict(conv_dropped=16),
                    dict(no_delta=True), dict(gate_off=True),
                    dict(rope_full=True), dict(state_bf16=True),
                    dict(precision="int8")):
        other = ref.forward_logits(CFG, SEED, [prompt], where,
                                   **control)[0]
        assert float(jnp.abs(other - want).max()) > 10 * ATOL, control


def _passes_then_steps(gen, prompt, cut, segment=16, spoil=None):
    """Logits at positions [cut, len): the first ``cut`` tokens through
    the staged passes' program (``segment`` a pass, the state and the
    attention row handed on), then the decode step token by token.
    ``spoil``: applied to the state between the two."""
    caches = gen._init_caches(1, gen._model_dtype())
    for start in range(0, cut, segment):
        n = min(segment, cut - start)
        toks = np.zeros((1, segment), np.int32)
        toks[0, :n] = prompt[start:start + n]
        caches = gen._prefill_resume_fn(segment)(
            gen.params, caches, jnp.asarray(toks), jnp.int32(start),
            jnp.int32(n))
    if spoil:
        caches = [c if isinstance(c, attention.KVCache)
                  else jax.tree_util.tree_map(spoil, c) for c in caches]
    step, out = jax.jit(gen._step), []
    for pos in range(cut, len(prompt)):
        logits, caches = step(gen.params, caches,
                              jnp.asarray(prompt[pos:pos + 1]),
                              jnp.int32(pos))
        out.append(logits[0])
    return np.stack(out)


@pytest.mark.parametrize("cut", [1, 13, 47, 80])
def test_prefill_in_passes_then_decode_is_the_full_forward(model, cut):
    _, gen = model
    prompt = _prompt(96, 3)
    want = ref.forward_logits(CFG, SEED, [prompt],
                              [list(range(cut, 96))])[0]
    np.testing.assert_allclose(_passes_then_steps(gen, prompt, cut),
                               np.asarray(want), atol=ATOL)


def test_a_state_rounded_to_bfloat16_fails_the_tolerance(model):
    _, gen = model
    prompt = _prompt(96, 3)
    want = ref.forward_logits(CFG, SEED, [prompt], [list(range(80, 96))])[0]
    rounded = _passes_then_steps(
        gen, prompt, 80,
        spoil=lambda a: a.astype(jnp.bfloat16).astype(jnp.float32))
    assert float(np.abs(rounded - np.asarray(want)).max()) > 5 * ATOL


REQUESTS = ((5, 12), (37, 20), (50, 9), (70, 12), (2, 6))
_SOLO = {}


@pytest.mark.parametrize("segment,step_form", [(0, False), (16, True)],
                         ids=["whole_xla_step", "passes_of_16_kernel"])
def test_the_paged_batcher_holds_state_and_pool_side_by_side(
        model, segment, step_form, monkeypatch):
    """Five requests through two slots (every slot reused after a
    release; whole prefills and the XLA step, or staged passes and the
    decode kernel in interpret mode): the solo continuation's tokens and
    the reference's first choices, the attention layer's blocks claimed
    and given back, the state's slots likewise, and the ticks' counts of
    both."""
    _, gen = model
    monkeypatch.setattr(deltanet, "KERNEL", step_form)
    for n, m in REQUESTS:
        if (n, m) not in _SOLO:
            _SOLO[n, m] = _solo(gen, _prompt(n, n), m)
    cb = PagedContinuousBatcher(gen, slots=2, block=8, pool_tokens=256,
                                prefill_segment=segment)
    assert cb._paged_layers == [3] and cb.pool_blocks == 32
    row = 3 * 4 * (4 * 8 * 8 + 3 * 64)
    assert cb._state_row_bytes == row
    rids = [cb.submit(_prompt(n, n), m) for n, m in REQUESTS]
    ticks, held = [], []
    while not cb.idle():
        cb.tick()
        ticks.append(cb.last_tick)
        held.append((cb.blocks_in_use()[0], cb.state_in_use()[0]))
    got = [cb.result(r) for r in rids]
    assert got == [_SOLO[request] for request in REQUESTS]
    assert _gap(_prompt(70, 70), got[3])[0] == 0.0
    assert cb.blocks_in_use() == (0, 0) and cb.state_in_use() == (0, 0)
    assert max(h[0] for h in held) > 0 and max(h[1] for h in held) == 2
    # admission's two costs: every request's state written once, the
    # blocks of prompt + output claimed once
    assert sum(t["admit_state_bytes"] for t in ticks) == 5 * row
    assert sum(t["admit_blocks"] for t in ticks) == sum(
        cb._blocks_needed(n, m) for n, m in REQUESTS)
    # one decode-kernel launch a linear layer and tick where it runs
    carried = [t for t in ticks if t["rows"]]
    assert {t["state_launches"] for t in carried} == ({3} if step_form
                                                      else {0})


@pytest.mark.parametrize("segment,step_form", [(0, False), (16, True)],
                         ids=["whole_xla_step", "passes_of_16_kernel"])
def test_the_batcher_keeps_a_finished_requests_state(
        model, segment, step_form, monkeypatch):
    """``keep_state``: of three requests through two slots, the one
    asked for hands back each DeltaNet layer's S and conv tail as its
    last decode step left them — the reference's recurrence over the
    same tokens — while a neighbour still decodes; the others keep
    nothing."""
    _, gen = model
    monkeypatch.setattr(deltanet, "KERNEL", step_form)
    cb = PagedContinuousBatcher(gen, slots=2, block=8, pool_tokens=256,
                                prefill_segment=segment)
    rids = [cb.submit(_prompt(n, n), m)
            for n, m in ((45, 9), (30, 20), (21, 5))]
    cb.keep_state(rids[0])
    while not cb.idle():
        cb.tick()
    kept = cb.kept_state(rids[0])
    assert cb.kept_state(rids[0]) is None
    assert cb.kept_state(rids[1]) is None
    result = cb.result(rids[0])
    want = ref.final_states(CFG, SEED, result[:-1], 44)
    assert len(kept) == len(want) == 3
    for (s, tail), (s_ref, tail_ref) in zip(kept, want):
        assert s.shape == s_ref.shape and tail.shape == tail_ref.shape
        np.testing.assert_allclose(s, s_ref, atol=ATOL)
        np.testing.assert_allclose(tail, tail_ref, atol=ATOL)
    assert ref.state_gap(kept, want) < 1e-4
    assert ref.state_gap(kept[:2], want) == float("inf")


def test_a_tiny_qwen3next_serves_over_rest(model):
    """``LMGenerator`` -> ``RESTfulAPI`` on a paged batcher: the answer
    is the solo continuation, and the two gauges read both kinds of
    serving memory."""
    import http.client
    from veles_tpu import telemetry
    from veles_tpu.services.restful import RESTfulAPI
    _, gen = model
    api = RESTfulAPI(lambda x: x, (MAX_LEN,), port=0, generator=gen,
                     continuous_slots=2, paged_block=8, pool_tokens=256,
                     prefill_segment=16)
    api.start()
    try:
        prompt = _prompt(40, 9)
        conn = http.client.HTTPConnection(api.host, api.port, timeout=60)
        conn.request("POST", api.path, json.dumps(
            {"input": prompt, "generate": {"max_new": 6}}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200, body
        assert body["result"][0] == _solo(gen, prompt, 6)
        metrics = api.engine.metrics()
        assert metrics["state_slots_in_use"] == 0
        assert metrics["pool_blocks_full_in_use"] == 0
        assert metrics["p50_tick_state_rows"] > 0
        assert metrics["admit_state_bytes"] == api.engine.cb._state_row_bytes
        assert metrics["admit_blocks"] == api.engine.cb._blocks_needed(40, 6)
        leaks = api.engine.leak_check()
        assert leaks["kv_blocks_leaked"] == 0 \
            and leaks["state_slots_held"] == 0
        text = telemetry.registry.render_prometheus()
        assert "veles_serve_pool_blocks_in_use" in text
        assert "veles_serve_state_bytes_in_use" in text
    finally:
        api.stop()


# ---------------------------------------------------------------- refusals
def test_prefix_sharing_over_the_state_is_refused(model):
    with pytest.raises(ValueError, match="snapshotted"):
        PagedContinuousBatcher(model[1], slots=2, block=8,
                               prefix_cache=True)


def test_speculation_over_the_state_is_refused(model):
    with pytest.raises(ValueError, match="rolling"):
        ContinuousBatcher(model[1], slots=2, speculative_k=4)


@pytest.mark.parametrize("extra,match", [
    (dict(window=8), "window"),
    (dict(attn_gate="sigmoid"), "attn_gate"),
    (dict(rope_dim=4), "rope_dim"),
    (dict(impl="ring"), "sequence-parallel"),
    (dict(deltanet=None), "go together")])
def test_what_a_deltanet_block_cannot_take_is_refused(extra, match):
    from veles_tpu.models.layers import make_layer
    cfg = dict(zoo.transformer_lm(n_layers=1, pos="rope",
                                  mixer="gated_deltanet",
                                  deltanet=SHAPE)[1], **extra)
    with pytest.raises(ValueError, match=match):
        make_layer(cfg).setup((16, 64))
