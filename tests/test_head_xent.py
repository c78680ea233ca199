"""The LM head and its loss as one op (``ops.losses.lm_head_xent``,
kernel ``veles_head_xent`` in ``ops.pallas.head_xent``) against the
layer-then-loss path it replaces — ``masked_seq_xent`` of the head's
logits, which stays in the tree as the oracle — and the trainer's
choice between the two."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veles_tpu.ops import losses
from veles_tpu.ops.pallas import head_xent


def head_logits(h, w, transposed):
    """The head as the layers compute it: bf16 operands, f32 logits."""
    w_t = w if transposed else w.T
    return jnp.dot(h.astype(jnp.bfloat16), w_t.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def oracle(h, w, labels, valid, transposed):
    z = head_logits(h, w, transposed)
    return losses.masked_seq_xent(z[:, :-1], labels, valid)


@pytest.fixture
def kernel(monkeypatch):
    """The op's forward through the Pallas kernel (interpret mode on the
    CPU) at tiles small enough that every shape below has several of
    each, the vocabulary's last one ragged."""
    monkeypatch.setattr(losses, "_kernel_here", lambda: True)
    tiles(monkeypatch, 16, 128)
    losses._head_xent_op.cache_clear()
    yield
    losses._head_xent_op.cache_clear()


def tiles(monkeypatch, tm, tv):
    """The kernel's tiles, ``(tm, tv)`` whatever the shapes."""
    monkeypatch.setattr(head_xent, "head_tiles",
                        lambda n, v, d, itemsize=2: (tm, tv))


def case(transposed, b=3, t=16, d=32, v=300, seed=0):
    """Rows whose argmax ties — within a tile (a zero row: every logit
    0) and across tiles (tokens 3 and 3 + 128 share a row of W, the
    largest) — a sample with ``valid`` 0, and V = 300 over tiles of
    128."""
    rng = np.random.default_rng(seed)
    h = rng.normal(size=(b, t, d)).astype(np.float32)
    h[0, 2] = 0.0
    h[1, 5] = np.abs(h[1, 5])
    w = (rng.normal(size=(v, d)) * 0.1).astype(np.float32)
    w[3] = w[3 + 128] = 0.5
    labels = rng.integers(0, v, (b, t)).astype(np.int32)
    valid = np.array([1.0, 1.0, 0.0][:b] + [1.0] * max(0, b - 3),
                     np.float32)
    w = w.T.copy() if transposed else w
    # the next token of each position but the last
    return (jnp.asarray(h), jnp.asarray(w), jnp.asarray(labels[:, 1:]),
            jnp.asarray(valid))


@pytest.mark.parametrize("path", ["jnp", "kernel"])
@pytest.mark.parametrize("transposed", [False, True],
                         ids=["tied", "untied"])
def test_matches_the_layer_then_loss(path, transposed, request):
    if path == "kernel":
        request.getfixturevalue("kernel")
    h, w, labels, valid = case(transposed)
    want = oracle(h, w, labels, valid, transposed)
    got = losses.lm_head_xent(h, w, labels, valid, transposed=transposed)
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-6)
    assert float(got[1]) == float(want[1])
    assert float(got[2]) == float(want[2]) == 2 * 15

    # the loss's cotangent scales both matmuls: take the mean's
    def grads(fn):
        return jax.grad(lambda h, w: (lambda s: s[0] / s[2])(
            fn(h, w, labels, valid, transposed=transposed)),
            argnums=(0, 1))(h, w)

    want_g = grads(lambda h, w, *a, transposed: oracle(
        h, w, *a, transposed))
    got_g = grads(losses.lm_head_xent)
    for a, b in zip(want_g, got_g):
        assert b.dtype == jnp.float32
        # the oracle rounds its cotangents to bf16 (the casts' transpose)
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-2 * float(jnp.max(jnp.abs(a))))
    # the masked sample and the last position take no gradient
    assert float(jnp.max(jnp.abs(got_g[0][2]))) == 0.0
    assert float(jnp.max(jnp.abs(got_g[0][:, -1]))) == 0.0


def test_first_index_argmax_on_ties(kernel, monkeypatch):
    """A zero row ties every logit (argmax 0); a row whose largest
    logits sit in tokens 3 and 131 ties across tiles (the first tile
    wins) — as ``jnp.argmax`` says, in the kernel and the jnp forward."""
    h, w, labels, _ = case(False)
    hc = h.reshape(-1, h.shape[-1]).astype(jnp.bfloat16)
    wc = w.astype(jnp.bfloat16)
    z = head_logits(h, w, False).reshape(hc.shape[0], -1)
    want = jnp.argmax(z, axis=-1)
    assert int(want[2]) == 0 and int(want[16 + 5]) == 3
    nxt = jnp.pad(labels, ((0, 0), (0, 1))).reshape(-1)
    got = [losses._head_stats(hc, wc, nxt, False, True, None)]
    # row tiles of one chunk, two chunks, past the rows' end; a single
    # vocabulary tile past the vocabulary's end
    for tm, tv in ((16, 128), (64, 256), (32, 384)):
        tiles(monkeypatch, tm, tv)
        got.append(head_xent.head_xent(hc, wc, nxt, interpret=True))
    for stats in got:
        np.testing.assert_array_equal(np.asarray(stats[2]),
                                      np.asarray(want))
        np.testing.assert_allclose(np.asarray(stats[3]), np.asarray(z))


def test_exact_in_float32(kernel):
    """Under float32 operands the op and the oracle compute the same
    numbers in another order: the gradients agree to float32."""
    h, w, labels, valid = case(False, seed=3)

    def f(fn):
        return jax.grad(lambda h, w: fn(h, w)[0], argnums=(0, 1))(h, w)

    def ref(h, w):
        z = jnp.dot(h, w.T, preferred_element_type=jnp.float32)
        return losses.masked_seq_xent(z[:, :-1], labels, valid)

    got = f(lambda h, w: losses.lm_head_xent(h, w, labels, valid,
                                             compute=jnp.float32))
    for a, b in zip(f(ref), got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=1e-6)


def test_evaluation_keeps_no_logits(kernel):
    """The primal alone (no gradient asked) runs the kernel without its
    logits output."""
    h, w, labels, valid = case(False)
    jaxpr = jax.make_jaxpr(lambda h, w: losses.lm_head_xent(
        h, w, labels, valid))(h, w)

    def kernel_outputs(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return [v.aval.shape for v in eqn.outvars]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found = kernel_outputs(sub)
                if found:
                    return found
        return None

    assert kernel_outputs(jaxpr.jaxpr) == [(48, 1), (48, 1), (48, 1)]


@pytest.mark.parametrize("n,v,d", [(16 * 1024, 50257, 768),
                                   (4 * 2048, 50257, 2048)])
def test_configured_launch_audits_clean(n, v, d, monkeypatch):
    """The VP6xx rules over the launches of the two training shapes at
    the tiles the code gives them, and the registered hook reports the
    kernel."""
    from veles_tpu.analysis.numerics_audit import audit_kernel_launch
    from veles_tpu.ops.pallas import kernel_audit_launches
    launch, = head_xent.audit_launch(n, v, d)
    assert audit_kernel_launch(launch) == []
    tm, tv = head_xent.head_tiles(n, v, d)
    assert (n % tm, tv % 128) == (0, 0)
    assert head_xent.KERNEL_NAMES["forward"][1] in {
        l["kernel"] for l in kernel_audit_launches()}
    # tiles too fat for the budget the kernel asks for are refused
    tiles(monkeypatch, 4096, 2048)
    fat, = head_xent.audit_launch(n, v, d)
    assert [f.rule for f in audit_kernel_launch(fat)] == ["VP602"]


# --------------------------------------------------------------------------
# the trainer's choice, and its steps
# --------------------------------------------------------------------------

def lm_workflow(tie=True, bias=False, loss="lm", mesh_config=None,
                spd=4, vocab=200):
    from veles_tpu import prng
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.models import zoo
    from veles_tpu.models.standard_workflow import StandardWorkflow
    prng.seed_all(11)
    rng = np.random.default_rng(5)
    tok = rng.integers(0, 200, size=(64, 16)).astype(np.int32)
    loader = FullBatchLoader(None, data=tok, labels=tok, minibatch_size=8,
                             class_lengths=[0, 0, 64])
    layers = zoo.transformer_lm(vocab_size=vocab, d_model=32, n_heads=2,
                                n_layers=1, tie_embeddings=tie, bias=bias,
                                solver="adamw", lr=1e-2)
    wf = StandardWorkflow(layers=layers, loader=loader, loss=loss,
                          decision_config={"max_epochs": 1},
                          mesh_config=mesh_config, steps_per_dispatch=spd,
                          name="head-xent")
    wf.initialize()
    return wf


def sweep(wf, k=4):
    """k steps through the fused sweep, as the benchmark's feed drives
    them: (the sweep's loss sum, the parameters after it)."""
    from veles_tpu.loader.base import TRAIN
    before = wf.trainer.host_params()
    for _ in range(k):
        wf.loader.run()
        wf.trainer.run()
    stats = wf.trainer.read_class_stats(TRAIN)
    return stats, before, wf.trainer.host_params()


def gauge():
    from veles_tpu import telemetry
    return telemetry.registry.gauge("veles_train_head_fused").value()


@pytest.mark.parametrize("mesh,path", [
    (None, "jnp"), ({"data": 2}, "jnp"), ({"data": 2}, "kernel"),
    ({"model": 2}, "kernel")], ids=["one-jnp", "data2-jnp",
                                    "data2-kernel", "model2-kernel"])
def test_trainer_fused_matches_unfused(mesh, path, monkeypatch, request):
    """Under a mesh the kernel runs per device in a ``shard_map``: rows
    over the data axis, or all of them on each device of a model mesh,
    whose untied head of an odd vocabulary no axis shards."""
    from veles_tpu.models.nn_units import StagedTrainer
    from veles_tpu.parallel import MeshConfig, make_mesh
    if path == "kernel":
        request.getfixturevalue("kernel")
    tie = mesh != {"model": 2}
    vocab = 200 if tie else 201

    def mc():
        return (None if mesh is None else
                MeshConfig(make_mesh(mesh, jax.devices()[:2])))

    wf = lm_workflow(tie=tie, vocab=vocab, mesh_config=mc())
    head = wf.trainer._fused_head(wf.trainer.params)
    assert (head.shard is None) == (mesh is None)
    fused, before, after = sweep(wf)
    assert wf.trainer.head_loss == "fused" and gauge() == 1.0
    monkeypatch.setattr(StagedTrainer, "_fused_head", lambda self, p: None)
    wf = lm_workflow(tie=tie, vocab=vocab, mesh_config=mc())
    plain, before2, after2 = sweep(wf)
    assert wf.trainer.head_loss == "xla" and gauge() == 0.0
    assert fused["count"] == plain["count"] == 4 * 8 * 15
    np.testing.assert_allclose(fused["loss"], plain["loss"], rtol=2e-4)
    flat = jax.tree_util.tree_leaves_with_path
    for (path, x1), y1, x2, y2 in zip(flat(before), *map(
            jax.tree_util.tree_leaves, (after, before2, after2))):
        np.testing.assert_array_equal(x1, x2)
        a, b = y1 - x1, y2 - x2
        # AdamW's first steps move a weight by about lr whatever its
        # gradient's size, so where a gradient is near nought the two
        # paths' bf16 cotangents may move it differently: the change
        # agrees as a whole, not weight by weight
        gap = np.linalg.norm(a - b) / np.linalg.norm(b)
        assert gap < 0.1, (jax.tree_util.keystr(path), gap)
        assert abs(np.linalg.norm(a) / np.linalg.norm(b) - 1) < 0.01


@pytest.mark.parametrize("tie", [True, False], ids=["tied", "untied"])
def test_fused_step_audits_clean(tie):
    """The numerics audit over the train step that takes the op: the
    backward's ``exp(z - lse)`` is bounded by 1, and the lattice sees
    it (no suppression)."""
    from veles_tpu.analysis import lint_workflow
    wf = lm_workflow(tie=tie)
    assert wf.trainer._fused_head(wf.trainer.params) is not None
    assert wf.trainer.lint_numerics_spec()["suppress"] == ()
    assert [f for f in lint_workflow(wf)
            if f.rule.startswith(("VN", "VR", "VP"))] == []


@pytest.mark.parametrize("what", ["bias", "quantised", "softmax"])
def test_other_heads_keep_the_layer(what):
    """A head with a bias, a quantised table and another loss each take
    the layer and the registered loss; the tied table and the bias-free
    untied head take the op."""
    from veles_tpu.ops import quant
    tr = lm_workflow(tie=what != "bias", bias=what == "bias",
                     loss="softmax" if what == "softmax" else "lm").trainer
    params = dict(tr.params)
    if what == "quantised":
        assert tr._fused_head(params) is not None
        emb = tr.layers[0].name
        params[emb] = dict(params[emb], table=quant.quantize_weight(
            params[emb]["table"]))
    assert tr._fused_head(params) is None
    untied = lm_workflow(tie=False).trainer
    assert untied._fused_head(untied.params).transposed is True


def test_the_fused_head_takes_the_loss_through_masked_seq_xent(
        monkeypatch):
    """The fused head reaches its loss through ``masked_seq_xent`` as the
    layer's logits do: half of each batch left out there is left out of
    the fused step too."""
    real = losses.masked_seq_xent

    def half(logits, labels, valid):
        assert isinstance(logits, losses.HeadLogits)
        keep = jnp.arange(valid.shape[0]) < valid.shape[0] // 2
        return real(logits, labels, valid * keep.astype(valid.dtype))

    monkeypatch.setattr(losses, "masked_seq_xent", half)
    wf = lm_workflow()
    stats, _, _ = sweep(wf)
    assert wf.trainer.head_loss == "fused"
    assert stats["count"] == 4 * 4 * 15
