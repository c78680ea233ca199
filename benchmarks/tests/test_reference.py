"""The plain reference against itself: the layer-by-layer gradient that
fits a 700M-parameter stack on one chip is ``jax.grad`` of the plain
loss."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny", "benchmarks", "configs", "tiny.json")


@pytest.mark.parametrize("precision", ["f32", "int8"])
def test_add_grads_is_the_gradient_of_the_plain_loss(precision):
    with open(TINY) as f:
        cfg = json.load(f)
    w = reference.make_weights(cfg, 2 ** 31 + 7)
    w = {k: v + 0.01 * jax.random.normal(jax.random.key(i), v.shape)
         for i, (k, v) in enumerate(sorted(w.items()))}
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 64), dtype=np.int32))
    loss, grads = jax.value_and_grad(reference.nll_sum)(
        w, tokens, cfg, precision)
    acc = jax.tree_util.tree_map(jnp.ones_like, w)
    got_loss, got = jax.jit(lambda w, acc, t: reference.add_grads(
        w, acc, t, cfg, precision))(w, acc, tokens)
    assert float(got_loss) == pytest.approx(float(loss), rel=1e-6)
    scale = max(float(jnp.max(jnp.abs(g))) for g in grads.values())
    for name, g in grads.items():
        # added INTO acc (ones), not written over it
        err = float(jnp.max(jnp.abs(got[name] - 1.0 - g)))
        assert err <= 1e-5 * scale + 1e-6, (name, err)
