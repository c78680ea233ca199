"""Model zoo — layer configs for the reference's baseline workflows
(BASELINE.md: MNIST MLP, CIFAR-10 conv, ImageNet AlexNet; ref Znicz sample
workflows documented in manualrst_veles_algorithms.rst)."""


def mnist_mlp(hidden=100, lr=0.03, moment=0.9):
    """MnistSimple: 784-<hidden>-10 softmax net
    (ref docs/source/manualrst_veles_algorithms.rst:26-33; BASELINE
    'MNIST 784-100-10 fully-connected')."""
    return [
        {"type": "all2all_tanh", "output_sample_shape": hidden,
         "learning_rate": lr, "gradient_moment": moment},
        {"type": "softmax", "output_sample_shape": 10,
         "learning_rate": lr, "gradient_moment": moment},
    ]


def resnet_gn(n_classes=10, width=16, blocks_per_stage=2, stages=3,
              pool=8, lr=0.05, moment=0.9, wd=1e-4):
    """Small pre-activation ResNet with GroupNorm (He et al. v2 blocks
    via the conv_residual_block composite; residual conv families are
    beyond the reference's 2015-era registry).  Defaults fit 32×32
    inputs: stem conv, ``stages`` stages of ``blocks_per_stage`` blocks
    (channel double + stride-2 transition between stages), global
    ``pool``×``pool`` average pool, softmax head."""
    gd = {"learning_rate": lr, "gradient_moment": moment,
          "weights_decay": wd}
    layers = [dict({"type": "conv", "n_kernels": width, "kx": 3,
                    "ky": 3, "padding": (1, 1, 1, 1)}, **gd)]
    ch = width
    for stage in range(stages):
        for b in range(blocks_per_stage):
            cfg = {"type": "conv_residual_block", "n_kernels": ch}
            if stage > 0 and b == 0:
                cfg["sliding"] = (2, 2)     # transition: downsample
            layers.append(dict(cfg, **gd))
        ch *= 2
    layers += [
        # He v2: pre-activation blocks emit a raw residual sum — one
        # final norm+relu bounds the feature scale before the head
        dict({"type": "group_norm"}, **gd),
        {"type": "activation_strict_relu"},
        {"type": "avg_pooling", "kx": pool, "ky": pool},
        dict({"type": "softmax", "output_sample_shape": n_classes},
             **gd),
    ]
    return layers


def cifar_conv(lr=0.001, moment=0.9, wd=0.004):
    """cifar_caffe-style quick net for 32×32×3 inputs
    (ref manualrst_veles_algorithms.rst:45-52: 17.21% validation error)."""
    return [
        {"type": "conv", "n_kernels": 32, "kx": 5, "ky": 5,
         "padding": (2, 2, 2, 2), "learning_rate": lr,
         "gradient_moment": moment, "weights_decay": wd},
        {"type": "max_pooling", "kx": 3, "ky": 3, "sliding": (2, 2)},
        {"type": "activation_strict_relu"},
        {"type": "conv_strict_relu", "n_kernels": 32, "kx": 5, "ky": 5,
         "padding": (2, 2, 2, 2), "learning_rate": lr,
         "gradient_moment": moment, "weights_decay": wd},
        {"type": "avg_pooling", "kx": 3, "ky": 3, "sliding": (2, 2)},
        {"type": "conv_strict_relu", "n_kernels": 64, "kx": 5, "ky": 5,
         "padding": (2, 2, 2, 2), "learning_rate": lr,
         "gradient_moment": moment, "weights_decay": wd},
        {"type": "avg_pooling", "kx": 3, "ky": 3, "sliding": (2, 2)},
        {"type": "all2all", "output_sample_shape": 64,
         "learning_rate": lr, "gradient_moment": moment,
         "weights_decay": wd},
        {"type": "softmax", "output_sample_shape": 10,
         "learning_rate": lr, "gradient_moment": moment,
         "weights_decay": wd},
    ]


def alexnet(n_classes=1000, lr=0.01, moment=0.9, wd=5e-4):
    """AlexNet for 227×227×3 ImageNet (ref BASELINE 'ImageNet AlexNet';
    Znicz imagenet workflow).  Single-tower (no grouped convs)."""
    def conv(k, kx, pad, stride=(1, 1), **kw):
        c = {"type": "conv_strict_relu", "n_kernels": k, "kx": kx, "ky": kx,
             "padding": (pad,) * 4, "sliding": stride, "learning_rate": lr,
             "gradient_moment": moment, "weights_decay": wd}
        c.update(kw)
        return c

    return [
        conv(96, 11, 0, stride=(4, 4)),
        {"type": "norm", "alpha": 1e-4, "beta": 0.75, "n": 5, "k": 2.0},
        {"type": "max_pooling", "kx": 3, "ky": 3, "sliding": (2, 2)},
        conv(256, 5, 2),
        {"type": "norm", "alpha": 1e-4, "beta": 0.75, "n": 5, "k": 2.0},
        {"type": "max_pooling", "kx": 3, "ky": 3, "sliding": (2, 2)},
        conv(384, 3, 1),
        conv(384, 3, 1),
        conv(256, 3, 1),
        {"type": "max_pooling", "kx": 3, "ky": 3, "sliding": (2, 2)},
        {"type": "all2all_strict_relu", "output_sample_shape": 4096,
         "learning_rate": lr, "gradient_moment": moment,
         "weights_decay": wd},
        {"type": "dropout", "dropout_ratio": 0.5},
        {"type": "all2all_strict_relu", "output_sample_shape": 4096,
         "learning_rate": lr, "gradient_moment": moment,
         "weights_decay": wd},
        {"type": "dropout", "dropout_ratio": 0.5},
        {"type": "softmax", "output_sample_shape": n_classes,
         "learning_rate": lr, "gradient_moment": moment,
         "weights_decay": wd},
    ]


def transformer_classifier(n_classes=10, d_model=64, n_heads=4, n_layers=2,
                           d_ff=None, lr=0.001, moment=0.9, causal=False,
                           dropout=0.1, impl="blockwise", solver="adam",
                           n_experts=0, n_kv_heads=None, remat=False):
    """Transformer encoder classifier over [T, F] sequence samples — new
    capability beyond the reference (its RNN/LSTM support was 'in
    progress', manualrst_veles_algorithms.rst:105-112; attention postdates
    it).  ``impl`` picks the attention path: blockwise / flash (Pallas) /
    ring / ulysses (sequence-parallel over a mesh 'seq' axis)."""
    gd = {"learning_rate": lr, "gradient_moment": moment, "solver": solver}
    layers = [dict({"type": "timestep_dense", "output_sample_shape": d_model},
                   **gd),
              {"type": "positional_encoding"}]
    for _ in range(n_layers):
        layers.append(dict({"type": "transformer_block",
                            "n_heads": n_heads,
                            "n_kv_heads": n_kv_heads or n_heads,
                            "d_ff": d_ff or 4 * d_model,
                            "causal": causal, "dropout_ratio": dropout,
                            "impl": impl, "n_experts": n_experts,
                            "remat": remat}, **gd))
    layers.append(dict({"type": "layer_norm"}, **gd))
    layers.append({"type": "seq_pool", "mode": "mean"})
    layers.append(dict({"type": "softmax", "output_sample_shape": n_classes},
                       **gd))
    return layers


def transformer_lm(vocab_size=256, d_model=64, n_heads=4, n_layers=2,
                   d_ff=None, lr=0.001, moment=0.9, dropout=0.0,
                   impl="blockwise", solver="adam", n_experts=0,
                   n_kv_heads=None, remat=False, pos="learned",
                   window=None, tie_embeddings=False, lora_rank=0,
                   norm="layer", bias=True, qk_norm=False,
                   rope_base=10000.0, head_dim=None, top_k=2,
                   d_expert=None, router="gshard", experts_held=None,
                   indexer=None, norm_eps=None, parallel_block=False,
                   n_shared=0, shared_combine="sum", rope=None,
                   mixer=None, ffn=None, deltanet=None, attn_gate=None,
                   rope_dim=None):
    """Decoder-only causal LM over int token samples [T].
    ``n_kv_heads`` < n_heads = grouped-query attention; ``remat=True``
    rematerializes each block's activations in the backward pass
    (jax.checkpoint — long-context memory for FLOPs), ``remat="dots"``
    keeps matmul outputs and recomputes only elementwise ops
    (dots_saveable — near-no-remat step time, far less memory); ``pos`` =
    "learned" | "sinusoid" position table, or "rope" (rotary q/k in
    every block, no table — extrapolates past the train length);
    ``tie_embeddings`` reuses the embedding table as the LM head
    (saves vocab×d_model params); ``lora_rank`` > 0 = parameter-
    efficient fine-tuning: every block gains rank-r q/v adapters, the
    blocks' base weights freeze via stop_gradient, and the
    embedding/position/norm/head layers freeze via learning_rate 0 —
    pair with ``--warm-start base_snapshot`` so only the adapters
    train (Hu et al. 2021).

    The block is one class, configured (``TransformerBlock``): ``norm``
    "layer" | "rms" (the final norm too), ``bias=False`` (no bias leaf
    anywhere, the head included), ``qk_norm``, ``rope_base``,
    ``head_dim``, and for expert layers ``n_experts``, ``top_k``,
    ``d_expert``, ``router`` "gshard" | "softmax_topk_renorm" (dropless,
    gated-SiLU experts), ``experts_held`` = (first, count);
    ``indexer`` = {"heads", "head_dim", "topk"} adds the learned
    sparse-attention indexer; ``norm`` "layer_nobias" and ``norm_eps``,
    ``parallel_block``, ``router`` "sigmoid_topk_renorm", ``n_shared``
    / ``shared_combine`` as the block documents them.  Layers that
    differ within one stack: ``window`` and ``rope`` (with
    ``pos="rope"``: which layers rotate q and k; default all) may each
    be a list, cycled over the layers — ``window=[4096, 4096, 4096,
    None], rope=[True, True, True, False]`` is three sliding-window
    layers with rotary positions, then a full-attention layer with no
    positional encoding at all.  ``mixer="power_retention"`` swaps
    every block's softmax attention for ``ops.retention``'s decayed
    degree-2 attention with a fixed-size state (it may be a list too,
    cycled: ``["power_retention", "attention"]`` is a hybrid stack);
    ``ffn="gated_silu"`` the dense GELU FFN for ``silu(gate) * up``.
    ``mixer="gated_deltanet"`` layers take ``ops.deltanet``'s gated
    delta rule shaped by ``deltanet`` = {"k_heads", "v_heads", "k_dim",
    "v_dim", "conv"} (``["gated_deltanet"] * 3 + ["attention"]`` is
    three of them to one attention layer); ``attn_gate="sigmoid"`` and
    ``rope_dim`` (partial rotation) go to the attention layers only;
    ``norm="rms_centered"`` / ``qk_norm="centered"`` store the gains
    zero-centred; ``shared_combine="sigmoid_gate"`` gates the shared
    experts a token.  Every default is the block the zoo always built,
    parameter for parameter."""
    if pos not in ("learned", "sinusoid", "rope"):
        raise ValueError("pos must be learned|sinusoid|rope")
    gd = {"learning_rate": lr, "gradient_moment": moment, "solver": solver}
    # resolve_hyper falls learning_rate_bias back to learning_rate, so
    # zeroing the one lr freezes weights AND biases of the outer layers
    outer = dict(gd, learning_rate=0.0) if lora_rank else gd
    layers = [dict({"type": "embedding", "vocab_size": vocab_size,
                    "d_model": d_model}, **outer)]
    if pos != "rope":
        layers.append(dict({"type": "positional_encoding",
                            "learned": pos == "learned"}, **outer))
    def of_layer(value, i):
        """``value``, or its i-th entry (cycled) where it is a list."""
        return value[i % len(value)] if isinstance(value, (list, tuple)) \
            else value

    if rope is not None and pos != "rope":
        raise ValueError("rope (which layers rotate) needs pos='rope'")
    # what this PR's options add to a block's config, left out at
    # their defaults: the block the zoo always built stays that config
    extra = {key: value for key, value in (
        ("norm_eps", norm_eps), ("parallel_block", parallel_block),
        ("n_shared", n_shared),
        ("shared_combine", shared_combine if n_shared else None),
        ("ffn", ffn))
        if value}
    for i in range(n_layers):
        mix = of_layer(mixer, i)
        # what only one kind of mixer takes, on that kind's layers
        own = {"deltanet": deltanet} if mix == "gated_deltanet" else {
            key: value for key, value in (("attn_gate", attn_gate),
                                          ("rope_dim", rope_dim))
            if value}
        layers.append(dict({"type": "transformer_block",
                            "n_heads": n_heads,
                            "n_kv_heads": n_kv_heads or n_heads,
                            "d_ff": d_ff or 4 * d_model,
                            "causal": True, "dropout_ratio": dropout,
                            "impl": impl, "n_experts": n_experts,
                            "remat": remat,
                            "rope": pos == "rope" and bool(
                                True if rope is None else of_layer(rope, i)),
                            "lora_rank": lora_rank,
                            "window": of_layer(window, i),
                            "norm": norm, "bias": bias,
                            "qk_norm": qk_norm, "rope_base": rope_base,
                            "head_dim": head_dim,
                            "top_k": top_k, "d_expert": d_expert,
                            "router": router,
                            "experts_held": experts_held,
                            "indexer": indexer},
                           **extra, **({"mixer": mix} if mix else {}),
                           **own, **gd))
    layers.append(dict({"type": "layer_norm", "norm": norm},
                       **({"norm_eps": norm_eps} if norm_eps else {}),
                       **outer))
    if tie_embeddings:
        # tie_to by TYPE — the trainer resolves it to the layer's
        # assigned name at initialize
        layers.append({"type": "tied_lm_head", "vocab_size": vocab_size,
                       "tie_to": "embedding"})
    else:
        layers.append(dict({"type": "timestep_dense",
                            "output_sample_shape": vocab_size,
                            "include_bias": bias}, **outer))
    return layers


def mnist_autoencoder(bottleneck=16, lr=0.01, moment=0.9):
    """MNIST-style autoencoder (ref manualrst_veles_algorithms.rst:55-70,
    validation RMSE 0.5478)."""
    return [
        {"type": "all2all_tanh", "output_sample_shape": bottleneck,
         "learning_rate": lr, "gradient_moment": moment},
        {"type": "all2all", "output_sample_shape": 784,
         "learning_rate": lr, "gradient_moment": moment},
    ]


def conv_autoencoder(n_kernels=8, kx=3, ky=3, lr=0.01, moment=0.9,
                     out_channels=1):
    """Convolutional autoencoder (ref manualrst_veles_algorithms.rst:86-94
    "convolutional autoencoder"): conv+pool encoder, depool+deconv decoder,
    trained with loss="mse" reconstructing the input."""
    gd = {"learning_rate": lr, "gradient_moment": moment}
    return [
        dict({"type": "conv_relu", "n_kernels": n_kernels, "kx": kx,
              "ky": ky}, **gd),
        {"type": "max_pooling", "kx": 2, "ky": 2},
        {"type": "depooling", "kx": 2, "ky": 2},
        dict({"type": "deconv", "n_kernels": out_channels, "kx": kx,
              "ky": ky}, **gd),
    ]
