"""Builds Brumby-14B-Base under test from its configuration file:
``zoo.transformer_lm`` -> ``StandardWorkflow`` (the same path as every
other model; no training), parameters bfloat16 from the build on
(``build_keye.param_dtype`` / ``no_host_draw``), then the seeded weights
of ``reference_brumby`` copied in one layer at a time.

The seeded weights (``assumed.seeded_weights`` of the configuration,
drawn in ``reference_brumby.layer_weights``): normal draws of std 0.02
and unit norm gains, the embedding of std 1, and two departures under
which the layer's mechanisms decide the logits — the gate's bias spreads
the eight KV heads' half-lives from tens to thousands of tokens, and
W_o hears a head by the root of its half-life."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import reference_brumby
from benchmarks.build_keye import no_host_draw, param_dtype
from benchmarks.reference import seed_key


def zoo_kwargs(cfg):
    """The published keys as ``zoo.transformer_lm`` takes them."""
    if cfg["hidden_act"] != "silu":
        raise ValueError("the dense FFN here is gated SiLU")
    return dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["intermediate_size"],
        pos="rope", rope_base=float(cfg["rope_theta"]), norm="rms",
        norm_eps=float(cfg["rms_norm_eps"]),
        bias=bool(cfg["attention_bias"]), qk_norm=True,
        mixer="power_retention", ffn="gated_silu",
        tie_embeddings=bool(cfg["tie_word_embeddings"]), dropout=0.0)


def build_workflow(cfg, max_len, name="bench-serve-brumby",
                   param="bfloat16"):
    """``StandardWorkflow`` -> ``StagedTrainer`` over one row of
    ``max_len`` tokens (it trains nothing: adafactor's factored slots
    are the smallest optimizer state the program can be built with)."""
    from veles_tpu import prng
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.models import zoo
    from veles_tpu.models.standard_workflow import StandardWorkflow

    prng.seed_all(5)
    rows = np.zeros((1, max_len), np.int32)
    loader = FullBatchLoader(None, data=rows, labels=rows,
                             minibatch_size=1, class_lengths=[0, 0, 1])
    with param_dtype(param), no_host_draw(jnp.dtype(param)):
        wf = StandardWorkflow(
            layers=zoo.transformer_lm(solver="adafactor", lr=0.0,
                                      **zoo_kwargs(cfg)),
            loader=loader, loss="lm", gd_defaults={"clip_norm": 1.0},
            decision_config={"max_epochs": 10 ** 9}, name=name)
        wf.initialize()
    return wf


def install_weights(trainer, cfg, seed):
    """The reference's seeded weights into the trainer's tree, one
    layer at a time (the program's zeros of a layer go as its seeded
    leaves come), each leaf in the dtype the program gave it."""
    key = seed_key(seed)
    names = {}
    for layer in trainer.layers:
        names.setdefault(layer.type, []).append(layer.name)

    def like(new, shapes):
        def leaf(a, b):
            if a.shape != b.shape:
                raise ValueError("seeded leaf %s does not match the "
                                 "program's %s" % (a.shape, b.shape))
            return a.astype(b.dtype)
        return jax.tree_util.tree_map(leaf, new, shapes)

    def shapes_of(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    blocks = names["transformer_block"]
    block_shapes = shapes_of(trainer.params[blocks[0]])
    make_layer = jax.jit(lambda k, i: like(
        reference_brumby.layer_weights(cfg, k, i), block_shapes))
    for i, lname in enumerate(blocks):
        trainer.params[lname] = None      # the zeros go first
        trainer.params[lname] = make_layer(key, i)

    def outer(lname, leaf, which):
        shapes = shapes_of(trainer.params[lname])
        if set(shapes) != {leaf}:
            raise ValueError("%s holds %s, expected only %r"
                             % (lname, sorted(shapes), leaf))
        trainer.params[lname] = None
        trainer.params[lname] = jax.jit(lambda k: like(
            {leaf: reference_brumby.outer_weights(cfg, k, which)},
            shapes))(key)

    outer(names["embedding"][0], "table", "embed")
    outer(names["layer_norm"][0], "gamma", "norm")
    outer(names["timestep_dense"][0], "weights", "head")
