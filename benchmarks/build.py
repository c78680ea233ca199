"""Builds the system under test from a configuration file.

``build_workflow`` is ``chip_smoke.build_flagship`` (the only
construction proven on this chip, PR 21) taking a configuration and a
traffic file instead of ``Sizes``, at GPT-2's own learned positions.
The program would draw its own initial weights on the host as it
initializes (``no_host_draw`` hands it zeros instead);
``install_weights`` then replaces them with the benchmark's, made on the
device from ``--seed`` by ``reference.make_weights`` — so that the
reference can regenerate the same tree without taking anything from the
program."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_PATHS = {
    "ln1_g": ("ln1", "gamma"), "ln1_b": ("ln1", "beta"),
    "ln2_g": ("ln2", "gamma"), "ln2_b": ("ln2", "beta"),
    "wq": ("mha", "wq"), "wk": ("mha", "wk"), "wv": ("mha", "wv"),
    "wo": ("mha", "wo"), "bq": ("mha", "bq"), "bk": ("mha", "bk"),
    "bv": ("mha", "bv"), "bo": ("mha", "bo"),
    "w1": ("w1",), "b1": ("b1",), "w2": ("w2",), "b2": ("b2",),
}


def build_workflow(cfg, rows, batch, steps_per_dispatch=1, opt=None,
                   remat=False, name="bench-lm", solver="adamw"):
    """``StandardWorkflow`` -> ``StagedTrainer`` over the token rows
    ``rows`` [n, T] (int32, made from the seed by the caller).  A
    serving cell passes ``solver="adafactor"``: it trains nothing, and
    adafactor's factored slots are the smallest optimizer state the
    program can be built with (every other solver allocates two dense
    slots, 8 B a parameter, which would set the process's memory peak
    before the first request)."""
    from veles_tpu import prng
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.models import zoo
    from veles_tpu.models.standard_workflow import StandardWorkflow

    opt = opt or {"learning_rate": 0.0, "clip_norm": 1.0}
    prng.seed_all(5)
    n = rows.shape[0]
    loader = FullBatchLoader(None, data=rows, labels=rows,
                             minibatch_size=batch,
                             class_lengths=[0, 0, n])
    wf = StandardWorkflow(
        layers=zoo.transformer_lm(
            vocab_size=cfg["vocab_size"], d_model=cfg["n_embd"],
            n_heads=cfg["n_head"], n_layers=cfg["n_layer"],
            d_ff=cfg["n_inner"], dropout=0.0, impl="flash", pos="learned",
            solver=solver, lr=opt["learning_rate"], tie_embeddings=True,
            remat=remat),
        loader=loader, loss="lm",
        gd_defaults={"clip_norm": opt["clip_norm"]},
        decision_config={"max_epochs": 10 ** 9},
        steps_per_dispatch=steps_per_dispatch, name=name)
    with no_host_draw():
        wf.initialize()
    return wf


@contextlib.contextmanager
def no_host_draw():
    """While it is open the program's ``weights`` stream hands out
    zeros where it would draw normals with numpy on the host.  Every
    weight the program draws as it initializes is replaced by
    ``install_weights`` before anything reads it; at 712 M parameters
    the draw alone was 15 s of every run's set-up (PERF.md)."""
    from veles_tpu import prng
    stream = prng.get("weights")
    stream.normal = lambda loc=0.0, scale=1.0, size=None: np.zeros(
        size, np.float32)
    try:
        yield
    finally:
        del stream.normal


def _layer_names(trainer):
    by_type = {}
    for layer in trainer.layers:
        by_type.setdefault(layer.type, []).append(layer.name)
    return by_type


def to_program_tree(trainer, ref_tree):
    """The reference's flat tree (block leaves stacked over layers)
    arranged as the trainer's ``params``: {layer name: {...}}."""
    names = _layer_names(trainer)
    out = {names["embedding"][0]: {"table": ref_tree["wte"]},
           names["positional_encoding"][0]: {"pos": ref_tree["wpe"]},
           names["layer_norm"][0]: {"gamma": ref_tree["lnf_g"],
                                    "beta": ref_tree["lnf_b"]}}
    for i, lname in enumerate(names["transformer_block"]):
        layer = {}
        for ref_name, path in BLOCK_PATHS.items():
            node = layer
            for key in path[:-1]:
                node = node.setdefault(key, {})
            node[path[-1]] = ref_tree[ref_name][i]
        out[lname] = layer
    return out


def from_program_tree(trainer, tree):
    """Inverse of :func:`to_program_tree` (block leaves re-stacked)."""
    names = _layer_names(trainer)
    out = {"wte": tree[names["embedding"][0]]["table"],
           "wpe": tree[names["positional_encoding"][0]]["pos"],
           "lnf_g": tree[names["layer_norm"][0]]["gamma"],
           "lnf_b": tree[names["layer_norm"][0]]["beta"]}
    for ref_name, path in BLOCK_PATHS.items():
        leaves = []
        for lname in names["transformer_block"]:
            node = tree[lname]
            for key in path:
                node = node[key]
            leaves.append(node)
        out[ref_name] = jnp.stack(leaves)
    return out


def install_weights(trainer, cfg, seed):
    """Replace the trainer's host-drawn parameters by the benchmark's
    seeded ones, made on the device in one jitted call (same structure,
    shapes and dtype as the program's own — checked)."""
    from benchmarks import reference
    make = jax.jit(lambda key: to_program_tree(
        trainer, reference.weights_from_key(cfg, key)))
    key = reference.seed_key(seed)

    def shapes(tree):
        return jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), tree)

    old, new = shapes(trainer.params), shapes(jax.eval_shape(make, key))
    if old != new:
        raise ValueError("seeded weights do not match the program's "
                         "parameter tree: %r vs %r" % (new, old))
    # the program's own draw goes before the seeded one comes: the two
    # never sit on the device together
    trainer.params = None
    trainer.params = make(key)


def token_rows(cfg, n_rows, seq, seed, stream=0):
    """[n_rows, seq] int32 tokens uniform over the vocabulary."""
    rng = np.random.default_rng([int(seed), int(stream)])
    return rng.integers(0, cfg["vocab_size"], (n_rows, seq),
                        dtype=np.int32)
