"""Builds Qwen3-Next-80B-A3B-Instruct's language model under test from
its configuration file: ``zoo.transformer_lm`` -> ``StandardWorkflow``
(the same path as every other model; no training), parameters bfloat16
from the build on (``build_keye.param_dtype`` / ``no_host_draw``), then
the seeded weights of ``reference_qwen3next`` copied in one layer at a
time."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import reference_qwen3next
from benchmarks.build_keye import no_host_draw, param_dtype
from benchmarks.reference import seed_key


def zoo_kwargs(cfg):
    """The published keys as ``zoo.transformer_lm`` takes them: the
    layer pattern as a list of mixers cycled over the layers (three
    gated-DeltaNet layers, then a gated-attention one)."""
    if cfg["hidden_act"] != "silu":
        raise ValueError("the experts here are gated SiLU")
    if cfg["shared_expert_intermediate_size"] != cfg["moe_intermediate_size"]:
        raise ValueError("the shared expert here is as wide as a routed one")
    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"]:
        raise ValueError("every layer here is an expert layer")
    if not cfg["norm_topk_prob"]:
        raise ValueError("the router here renormalises its top k")
    period = cfg["full_attention_interval"]
    return dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["intermediate_size"],
        pos="rope", rope_base=float(cfg["rope_theta"]),
        rope_dim=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        norm="rms_centered", norm_eps=float(cfg["rms_norm_eps"]),
        bias=False, qk_norm="centered", attn_gate="sigmoid",
        mixer=["gated_deltanet"] * (period - 1) + ["attention"],
        deltanet=dict(k_heads=cfg["linear_num_key_heads"],
                      v_heads=cfg["linear_num_value_heads"],
                      k_dim=cfg["linear_key_head_dim"],
                      v_dim=cfg["linear_value_head_dim"],
                      conv=cfg["linear_conv_kernel_dim"]),
        n_experts=cfg["num_experts_routed"],
        top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        router="softmax_topk_renorm",
        experts_held=tuple(cfg["experts_held"]), n_shared=1,
        shared_combine="sigmoid_gate",
        tie_embeddings=bool(cfg["tie_word_embeddings"]), dropout=0.0)


def build_workflow(cfg, max_len, name="bench-serve-qwen3next",
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

    makers = {}                 # one program a layer kind
    for i, lname in enumerate(names["transformer_block"]):
        kind = reference_qwen3next.layer_kind(cfg, i)
        shapes = shapes_of(trainer.params[lname])
        if kind not in makers:
            makers[kind] = jax.jit(lambda k, i, kind=kind, shapes=shapes:
                                   like(reference_qwen3next.layer_weights(
                                       cfg, k, i, kind=kind), shapes))
        trainer.params[lname] = None      # the zeros go first
        trainer.params[lname] = makers[kind](key, i)

    def outer(lname, leaf, which):
        shapes = shapes_of(trainer.params[lname])
        if set(shapes) != {leaf}:
            raise ValueError("%s holds %s, expected only %r"
                             % (lname, sorted(shapes), leaf))
        trainer.params[lname] = None
        trainer.params[lname] = jax.jit(lambda k: like(
            {leaf: reference_qwen3next.outer_weights(cfg, k, which)},
            shapes))(key)

    outer(names["embedding"][0], "table", "embed")
    outer(names["layer_norm"][0], "gamma", "norm")
    outer(names["timestep_dense"][0], "weights", "head")
