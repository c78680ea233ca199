"""Layer descriptors — the Znicz layer-type registry
(ref docs/source/manualrst_veles_workflow_creation.rst:107-150 and the unit
inventory in manualrst_veles_workflow_parameters.rst:467-504).

A layer descriptor is pure configuration + three pure functions:
``setup(input_shape)`` infers the static output shape, ``init_params(rng)``
builds the parameter pytree, ``apply(params, x, train, key)`` is the traced
forward.  StandardWorkflow composes them into one jitted step — layers are
*not* units; the per-layer Forward units exist only as introspection
handles.

Config dicts accept both the reference's flat style
(``{"type": "all2all_tanh", "output_sample_shape": 100, "learning_rate":
0.1}``) and its newer split style (``{"type": ..., "->": {forward params},
"<-": {gd params}}``)."""

import math

import jax
import jax.numpy as jnp
import numpy as np

from veles_tpu.ops import activations, conv, dropout, linear, lrn, misc, pooling
from veles_tpu.ops.policy import default_policy


def _flatten_config(cfg):
    out = dict(cfg)
    for split_key in ("->", "<-"):
        sub = out.pop(split_key, None)
        if sub:
            out.update(sub)
    return out


class Layer(object):
    """Base descriptor.  Subclasses set TYPES = {registry names}."""

    TYPES = ()
    needs_rng = False      # dropout / stochastic pooling want a key
    has_params = False
    #: apply() receives the WHOLE param tree instead of its own slice —
    #: the seam weight tying uses (TiedLMHead reads the embedding table)
    needs_full_params = False

    def __init__(self, cfg):
        cfg = _flatten_config(cfg)
        self.type = cfg["type"]
        self.cfg = cfg
        self.name = cfg.get("name", self.type)
        # per-layer GD hyperparameters (ref Znicz GD unit kwargs); None
        # falls back to workflow-level defaults in the optimizer.  The
        # key set IS optimizer.DEFAULTS (which includes the *_bias
        # variants) so a new solver knob can never be silently dropped
        # by a stale hand-maintained whitelist.
        from veles_tpu.models import optimizer as _opt
        self.gd = {k: cfg[k] for k in _opt.DEFAULTS if k in cfg}
        self.input_shape = None
        self.output_shape = None
        self.policy = default_policy()

    def setup(self, input_shape):
        self.input_shape = tuple(input_shape)
        self.output_shape = self._infer(self.input_shape)
        return self.output_shape

    def _infer(self, input_shape):
        return input_shape

    def init_params(self, rng):
        return {}

    def param_partition_specs(self, mesh_shape):
        """Optional override of the default (model-axis) parameter
        sharding rule: return a PartitionSpec applied to every param
        leaf, or a partial dict mirroring init_params' structure.  None =
        default rule (parallel.sharding.param_spec)."""
        return None

    def apply(self, params, x, train=False, key=None):
        raise NotImplementedError

    def _activation(self):
        # longest suffix first: "_strict_relu" must not match "_relu"
        for suffix in sorted(activations.ACTIVATIONS, key=len, reverse=True):
            if self.type.endswith("_" + suffix):
                return activations.ACTIVATIONS[suffix]
        return activations.ACTIVATIONS["linear"]


class All2All(Layer):
    """Dense family (ref Znicz All2All*, SURVEY §2.9 "Dense").  ``softmax``
    maps here too: it emits logits; the softmax lives in the evaluator and
    in the serve-time head."""

    TYPES = ("all2all", "all2all_tanh", "all2all_sigmoid", "all2all_relu",
             "all2all_strict_relu", "softmax")
    has_params = True

    def _infer(self, input_shape):
        oss = self.cfg["output_sample_shape"]
        self.n_in = int(math.prod(input_shape))
        if isinstance(oss, int):
            return (oss,)
        return tuple(oss)

    def init_params(self, rng):
        n_out = int(math.prod(self.output_shape))
        params = linear.init_params(
            rng, self.n_in, n_out, bias=self.cfg.get("include_bias", True),
            weights_stddev=self.cfg.get("weights_stddev"),
            dtype=self.policy.param)
        r = int(self.cfg.get("lora_rank", 0))
        if r > 0:
            # LoRA: base W/b freeze (ops.linear stop_gradients them);
            # B = 0 makes the adapted layer exactly the base at init —
            # pair with --warm-start to fine-tune a pretrained model
            # training only these rank-r factors
            params["lora_a"] = jnp.asarray(
                rng.normal(0.0, self.n_in ** -0.5, (self.n_in, r)),
                self.policy.param)
            params["lora_b"] = jnp.zeros((r, n_out), self.policy.param)
        return params

    def apply(self, params, x, train=False, key=None):
        y = linear.forward(params, x, self.policy)
        y = self._activation()(y)
        return y.reshape((x.shape[0],) + self.output_shape)


class Conv(Layer):
    """Conv family (ref Znicz Conv*).  NHWC; ``sliding``=(sy, sx) stride;
    ``padding``=(top, left, bottom, right) explicit pixels."""

    TYPES = ("conv", "conv_tanh", "conv_sigmoid", "conv_relu",
             "conv_strict_relu")
    has_params = True

    def _infer(self, input_shape):
        h, w, c = input_shape
        self.kx = int(self.cfg["kx"])
        self.ky = int(self.cfg["ky"])
        self.n_kernels = int(self.cfg["n_kernels"])
        self.stride = tuple(self.cfg.get("sliding", (1, 1)))
        self.padding = tuple(self.cfg.get("padding", (0, 0, 0, 0)))
        pt, pl, pb, pr = self.padding
        ho = (h + pt + pb - self.ky) // self.stride[0] + 1
        wo = (w + pl + pr - self.kx) // self.stride[1] + 1
        self.n_channels = c
        return (ho, wo, self.n_kernels)

    def init_params(self, rng):
        return conv.init_params(
            rng, self.kx, self.ky, self.n_channels, self.n_kernels,
            bias=self.cfg.get("include_bias", True),
            weights_stddev=self.cfg.get("weights_stddev"),
            dtype=self.policy.param)

    def apply(self, params, x, train=False, key=None):
        y = conv.forward(params, x, self.stride, self.padding, self.policy)
        return self._activation()(y)


class Deconv(Layer):
    """Transposed conv (ref Znicz Deconv — conv-autoencoder decoder)."""

    TYPES = ("deconv", "deconv_tanh", "deconv_sigmoid", "deconv_relu")
    has_params = True

    def _infer(self, input_shape):
        h, w, c = input_shape
        self.kx = int(self.cfg["kx"])
        self.ky = int(self.cfg["ky"])
        self.n_kernels = int(self.cfg["n_kernels"])
        self.stride = tuple(self.cfg.get("sliding", (1, 1)))
        self.n_channels = c
        ho = (h - 1) * self.stride[0] + self.ky
        wo = (w - 1) * self.stride[1] + self.kx
        return (ho, wo, self.n_kernels)

    def init_params(self, rng):
        return conv.init_params(
            rng, self.kx, self.ky, self.n_channels, self.n_kernels,
            bias=self.cfg.get("include_bias", True),
            weights_stddev=self.cfg.get("weights_stddev"),
            dtype=self.policy.param)

    def apply(self, params, x, train=False, key=None):
        y = conv.deconv_forward(params, x, self.stride, "VALID", self.policy)
        return self._activation()(y)


class Pooling(Layer):
    TYPES = ("max_pooling", "avg_pooling", "maxabs_pooling",
             "stochastic_pooling", "stochastic_abs_pooling")

    @property
    def needs_rng(self):
        return self.type.startswith("stochastic")

    def _infer(self, input_shape):
        h, w, c = input_shape
        self.kx = int(self.cfg["kx"])
        self.ky = int(self.cfg["ky"])
        self.stride = tuple(self.cfg.get("sliding", (self.ky, self.kx)))
        ho = (h - self.ky) // self.stride[0] + 1
        wo = (w - self.kx) // self.stride[1] + 1
        return (ho, wo, c)

    def apply(self, params, x, train=False, key=None):
        if self.type == "max_pooling":
            return pooling.max_pool(x, self.ky, self.kx, self.stride)
        if self.type == "avg_pooling":
            return pooling.avg_pool(x, self.ky, self.kx, self.stride)
        if self.type == "maxabs_pooling":
            return pooling.max_abs_pool(x, self.ky, self.kx, self.stride)
        absolute = self.type == "stochastic_abs_pooling"
        if train:
            return pooling.stochastic_pool(x, self.ky, self.kx, key,
                                           self.stride, absolute)
        return pooling.stochastic_pool_infer(x, self.ky, self.kx,
                                             self.stride, absolute)


class Depooling(Layer):
    TYPES = ("depooling",)

    def _infer(self, input_shape):
        h, w, c = input_shape
        self.kx = int(self.cfg["kx"])
        self.ky = int(self.cfg["ky"])
        return (h * self.ky, w * self.kx, c)

    def apply(self, params, x, train=False, key=None):
        return pooling.depool(x, self.ky, self.kx)


class StochasticPoolDepool(Layer):
    """Fused stochastic pooling + depooling (ref Znicz
    StochasticPoolingDepooling) — keeps one sampled element per window in
    place, zeroes the rest; shape-preserving."""

    TYPES = ("stochastic_pooling_depooling", "stochastic_abs_pooling_depooling")
    needs_rng = True

    def _infer(self, input_shape):
        self.kx = int(self.cfg["kx"])
        self.ky = int(self.cfg["ky"])
        return input_shape

    def apply(self, params, x, train=False, key=None):
        if not train:
            return x
        absolute = "abs" in self.type
        return pooling.stochastic_pool_depool(x, self.ky, self.kx, key,
                                              absolute)


class ChannelSplitter(Layer):
    """ChannelSplitter (ref Znicz): (H, W, C) samples become (C, H, W, 1) —
    channels move to a leading per-sample axis so downstream per-channel
    branches can vmap/slice; ChannelMerger inverts it."""

    TYPES = ("channel_splitter",)

    def _infer(self, input_shape):
        h, w, c = input_shape
        return (c, h, w, 1)

    def apply(self, params, x, train=False, key=None):
        return jnp.transpose(x, (0, 3, 1, 2))[..., None]


class ChannelMerger(Layer):
    """Inverse of ChannelSplitter: (C, H, W, 1) -> (H, W, C)."""

    TYPES = ("channel_merger",)

    def _infer(self, input_shape):
        c, h, w, _ = input_shape
        return (h, w, c)

    def apply(self, params, x, train=False, key=None):
        return jnp.transpose(x[..., 0], (0, 2, 3, 1))


class ResizableAll2All(All2All):
    """All2All whose output width can change between training stages (ref
    Znicz ResizableAll2All, used when growing autoencoder bottlenecks).
    ``resize(params, new_output, rng)`` returns an updated parameter dict
    preserving the overlapping weight slice; call it *between* jitted
    stages (it changes shapes, so the next stage recompiles)."""

    TYPES = ("resizable_all2all",)

    def resize(self, params, new_output, rng):
        new_out = (int(new_output) if isinstance(new_output, int)
                   else int(math.prod(new_output)))
        self.output_shape = ((new_output,) if isinstance(new_output, int)
                             else tuple(new_output))
        # keep cfg in sync so a later setup()/_infer re-derives this shape
        self.cfg["output_sample_shape"] = new_output
        fresh = linear.init_params(
            rng, self.n_in, new_out, bias="bias" in params,
            weights_stddev=self.cfg.get("weights_stddev"),
            dtype=self.policy.param)
        keep = min(new_out, params["weights"].shape[1])
        w = np.array(fresh["weights"])
        w[:, :keep] = np.asarray(params["weights"])[:, :keep]
        fresh["weights"] = jnp.asarray(w)
        if "bias" in params:
            b = np.array(fresh["bias"])
            b[:keep] = np.asarray(params["bias"])[:keep]
            fresh["bias"] = jnp.asarray(b)
        return fresh


class LRN(Layer):
    """Local response normalization, the "norm" layer type."""

    TYPES = ("norm",)

    def apply(self, params, x, train=False, key=None):
        return lrn.forward(x, self.cfg.get("alpha", 1e-4),
                           self.cfg.get("beta", 0.75),
                           self.cfg.get("n", 15), self.cfg.get("k", 2.0))


class Dropout(Layer):
    TYPES = ("dropout",)
    needs_rng = True

    def apply(self, params, x, train=False, key=None):
        if not train:
            return x
        return dropout.forward(x, key, self.cfg.get("dropout_ratio", 0.5))


class Activation(Layer):
    """Standalone activation units (ref Znicz activation.*)."""

    TYPES = tuple("activation_" + n for n in activations.ACTIVATIONS)

    def apply(self, params, x, train=False, key=None):
        name = self.type[len("activation_"):]
        return activations.ACTIVATIONS[name](x)


class Cutter(Layer):
    TYPES = ("cutter",)

    def _infer(self, input_shape):
        self.oy, self.ox = self.cfg.get("offset", (0, 0))
        self.h, self.w = self.cfg["size"]
        return (self.h, self.w, input_shape[2])

    def apply(self, params, x, train=False, key=None):
        return misc.cut(x, self.oy, self.ox, self.h, self.w)


class LSTM(Layer):
    """LSTM layer over [T, F] samples (ref Veles RNN/LSTM engines).
    ``output_sample_shape`` = hidden units; ``return_sequences`` keeps the
    whole [T, H] output for stacking."""

    TYPES = ("lstm", "rnn_tanh")
    has_params = True

    def _infer(self, input_shape):
        if len(input_shape) != 2:
            raise ValueError("%s wants [T, F] samples, got %s"
                             % (self.type, input_shape))
        self.n_hidden = int(self.cfg["output_sample_shape"])
        self.return_sequences = bool(self.cfg.get("return_sequences",
                                                  False))
        t, f = input_shape
        self.n_in = f
        return ((t, self.n_hidden) if self.return_sequences
                else (self.n_hidden,))

    def init_params(self, rng):
        from veles_tpu.ops import recurrent
        if self.type == "lstm":
            return recurrent.lstm_init(rng, self.n_in, self.n_hidden,
                                       self.policy.param)
        return recurrent.rnn_init(rng, self.n_in, self.n_hidden,
                                  self.policy.param)

    def apply(self, params, x, train=False, key=None):
        from veles_tpu.ops import recurrent
        fn = (recurrent.lstm_forward if self.type == "lstm"
              else recurrent.rnn_forward)
        return fn(params, x, self.policy, self.return_sequences)


#: ``norm`` of a block or of the ``layer_norm`` layer: LayerNorm (gain
#: and shift), RMSNorm (a gain, no mean), LayerNorm without its shift
#: (the mean subtracted, a gain)
NORM_KINDS = ("layer", "rms", "layer_nobias")


def _norm_init(kind, width):
    from veles_tpu.ops import norm
    if kind not in NORM_KINDS:
        raise ValueError("norm must be one of %s" % "|".join(NORM_KINDS))
    if kind == "layer":
        return norm.layer_norm_init((width,))
    return {"gamma": jnp.ones((width,), jnp.float32)}


def _norm_apply(p, x, kind="layer", eps=1e-6):
    """The norm ``kind`` names (``NORM_KINDS``) over the feature axis."""
    from veles_tpu.ops import norm
    if kind == "rms":
        return norm.rms_norm(x, p["gamma"], eps=eps)
    return norm.layer_norm(x, p["gamma"], p.get("beta"), eps=eps)


class LayerNorm(Layer):
    """Layer normalization over the feature axis (ops.norm);
    ``norm="rms"`` = RMSNorm (a gain, no mean, no shift),
    ``norm="layer_nobias"`` = LayerNorm without the shift; ``norm_eps``
    (default 1e-6)."""

    TYPES = ("layer_norm",)
    has_params = True

    def init_params(self, rng):
        return _norm_init(self.cfg.get("norm", "layer"),
                          self.input_shape[-1])

    def apply(self, params, x, train=False, key=None):
        return _norm_apply(params, x, self.cfg.get("norm", "layer"),
                           float(self.cfg.get("norm_eps") or 1e-6))


class GroupNorm(Layer):
    """Group normalization (Wu & He 2018) over the channel axis —
    batch-size independent, no running statistics, so it fits the
    stateless functional layer contract where batch norm's mutable
    running mean/var cannot.  The modern conv-stack normalizer
    (capability beyond the reference's LRN-era registry).  The
    effective group count is the largest divisor of C <= ``groups``
    (default 32)."""

    TYPES = ("group_norm",)
    has_params = True

    def init_params(self, rng):
        from veles_tpu.ops import norm
        return norm.layer_norm_init((self.input_shape[-1],))

    def apply(self, params, x, train=False, key=None):
        from veles_tpu.ops import norm
        return norm.group_norm(x, params["gamma"], params["beta"],
                               groups=self.cfg.get("groups", 32))


class ConvResidualBlock(Layer):
    """Pre-activation residual conv block (He et al. 2016 "identity
    mappings" v2, with GroupNorm standing in for batch norm so the
    block stays stateless): gn→relu→conv3×3 → gn→relu→conv3×3, added
    to the skip path.  ``n_kernels`` sets the output channels (default:
    keep input channels); ``sliding`` strides the FIRST conv, and a
    stride or channel change routes the skip through a 1×1 projection.
    Composite like TransformerBlock — residual conv families (ResNet)
    are capability beyond the reference's 2015-era registry."""

    TYPES = ("conv_residual_block",)
    has_params = True

    def _infer(self, input_shape):
        h, w, c = input_shape
        self.n_kernels = int(self.cfg.get("n_kernels", c))
        self.stride = tuple(self.cfg.get("sliding", (1, 1)))
        # same default as the standalone group_norm layer; the op
        # degrades to the largest divisor of C automatically
        self.groups = int(self.cfg.get("groups", 32))
        self.n_channels = c
        sy, sx = self.stride
        # both convs are 3x3 SAME (padding 1); only the first strides
        ho = (h + 2 - 3) // sy + 1
        wo = (w + 2 - 3) // sx + 1
        self.needs_proj = self.stride != (1, 1) or self.n_kernels != c
        return (ho, wo, self.n_kernels)

    def init_params(self, rng):
        from veles_tpu.ops import norm
        c, k = self.n_channels, self.n_kernels
        params = {
            "gn1": norm.layer_norm_init((c,)),
            "conv1": conv.init_params(rng, 3, 3, c, k,
                                      dtype=self.policy.param),
            "gn2": norm.layer_norm_init((k,)),
            "conv2": conv.init_params(rng, 3, 3, k, k,
                                      dtype=self.policy.param),
        }
        if self.needs_proj:
            params["proj"] = conv.init_params(
                rng, 1, 1, c, k, bias=False, dtype=self.policy.param)
        return params

    def apply(self, params, x, train=False, key=None):
        from veles_tpu.ops import activations, norm
        relu = activations.ACTIVATIONS["strict_relu"]
        h = relu(norm.group_norm(x, params["gn1"]["gamma"],
                                 params["gn1"]["beta"],
                                 groups=self.groups))
        h = conv.forward(params["conv1"], h, self.stride, (1, 1, 1, 1),
                         self.policy)
        h = relu(norm.group_norm(h, params["gn2"]["gamma"],
                                 params["gn2"]["beta"],
                                 groups=self.groups))
        h = conv.forward(params["conv2"], h, (1, 1), (1, 1, 1, 1),
                         self.policy)
        skip = x
        if self.needs_proj:
            # 1x1 strided projection aligns shape AND resolution
            skip = conv.forward(params["proj"], x, self.stride,
                                (0, 0, 0, 0), self.policy)
        return h + skip


class Embedding(Layer):
    """Token embedding: int ids [T] → [T, d_model]."""

    TYPES = ("embedding",)
    has_params = True

    def _infer(self, input_shape):
        self.vocab = int(self.cfg["vocab_size"])
        self.d_model = int(self.cfg["d_model"])
        return tuple(input_shape) + (self.d_model,)

    def init_params(self, rng):
        import jax.numpy as jnp
        std = self.cfg.get("weights_stddev")
        if std is None:
            std = self.d_model ** -0.5
        table = rng.normal(0.0, std, (self.vocab, self.d_model))
        return {"table": jnp.asarray(table, self.policy.param)}

    def apply(self, params, x, train=False, key=None):
        return jnp.take(params["table"], x.astype(jnp.int32), axis=0)


class PositionalEncoding(Layer):
    """Add position information to [T, F] activations: ``learned`` table
    or fixed sinusoidal (default) — without this a pooled transformer is
    permutation-invariant over time."""

    TYPES = ("positional_encoding",)

    def _infer(self, input_shape):
        self.learned = bool(self.cfg.get("learned", False))
        return tuple(input_shape)

    @property
    def has_params(self):
        return self.learned

    def init_params(self, rng):
        if not self.learned:
            return {}
        t, f = self.input_shape
        return {"pos": jnp.asarray(rng.normal(0.0, 0.02, (t, f)),
                                   self.policy.param)}

    def _sinusoid(self):
        import numpy as np
        t, f = self.input_shape
        pos = np.arange(t)[:, None]
        i = np.arange(f)[None, :]
        angle = pos / np.power(10000.0, (2 * (i // 2)) / f)
        pe = np.where(i % 2 == 0, np.sin(angle), np.cos(angle))
        return jnp.asarray(pe, jnp.float32)

    def apply(self, params, x, train=False, key=None):
        pe = params["pos"] if self.learned else self._sinusoid()
        return x + pe.astype(x.dtype)


def _seq_parallel_attn_fn(layer):
    """impl="ring"/"ulysses": core attention runs sequence-parallel over
    the mesh's ``seq`` axis (parallel.ring — ring attention rotates k/v
    shards over ICI; Ulysses all-to-alls to head sharding).  The trainer
    injects ``layer.mesh`` when its mesh has a ``seq`` axis."""
    impl = layer.cfg.get("impl", "blockwise")
    if impl not in ("ring", "ulysses"):
        return None
    if getattr(layer, "mesh", None) is None or \
            "seq" not in layer.mesh.shape:
        raise ValueError(
            "impl=%r needs sequence parallelism: pass the trainer a "
            "mesh_config whose mesh has a 'seq' axis" % impl)
    from veles_tpu.parallel import ring as seqpar
    fn = (seqpar.ring_attention_sharded if impl == "ring"
          else seqpar.ulysses_attention_sharded)
    mesh = layer.mesh

    def attn(q, k, v, causal=False):
        return fn(q, k, v, mesh, causal=causal)
    return attn


def _flash_shard(layer):
    """``ops.attention.flash_attention``'s ``shard`` triple for a layer
    the trainer gave a mesh: the Pallas kernel runs per device over
    the ``data`` (batch rows) and ``model`` (heads) axes instead of
    sitting un-partitionable inside the GSPMD program."""
    mesh = getattr(layer, "mesh", None)
    return None if mesh is None else (mesh, "data", "model")


class MultiHeadAttention(Layer):
    """Self-attention over [T, F] samples (ops.attention).  ``impl``
    selects naive / blockwise / flash (Pallas) / ring / ulysses (the
    sequence-parallel paths); causal via ``causal``."""

    TYPES = ("multihead_attention",)
    has_params = True
    mesh = None   # injected by the trainer (flash shard, ring/ulysses)

    def _infer(self, input_shape):
        t, f = input_shape
        self.n_heads = int(self.cfg.get("n_heads", 8))
        self.n_kv_heads = int(self.cfg.get("n_kv_heads", self.n_heads))
        if f % self.n_heads:
            raise ValueError("d_model %d %% n_heads %d != 0"
                             % (f, self.n_heads))
        return (t, f)

    def init_params(self, rng):
        from veles_tpu.ops import attention
        return attention.mha_init(rng, self.input_shape[-1], self.n_heads,
                                  self.policy.param,
                                  n_kv_heads=self.n_kv_heads)

    def apply(self, params, x, train=False, key=None):
        from veles_tpu.ops import attention
        return attention.mha_forward(
            params, x, self.n_heads,
            causal=bool(self.cfg.get("causal", False)),
            impl=self.cfg.get("impl", "blockwise"),
            attn_fn=_seq_parallel_attn_fn(self), policy=self.policy,
            n_kv_heads=self.n_kv_heads,
            use_rope=bool(self.cfg.get("rope", False)),
            window=self.cfg.get("window"),
            flash_shard=_flash_shard(self))


class MoE(Layer):
    """Position-wise mixture-of-experts feed-forward over [T, D] samples
    (ops.moe — GShard/Switch dense-dispatch MoE).  With a mesh carrying an
    ``expert`` axis (trainer-injected), experts run expert-parallel via
    all_to_all; otherwise all experts compute locally.  The router's
    load-balancing loss lands in ``last_aux`` and is added to the
    training loss scaled by ``aux_weight``."""

    TYPES = ("moe",)
    has_params = True
    mesh = None   # injected by the trainer when the mesh has 'expert'

    def _infer(self, input_shape):
        t, f = input_shape
        self.n_experts = int(self.cfg.get("n_experts", 8))
        self.d_ff = int(self.cfg.get("d_ff", 4 * f))
        self.top_k = int(self.cfg.get("top_k", 2))
        self.capacity_factor = float(self.cfg.get("capacity_factor", 2.0))
        self.last_aux = None
        return (t, f)

    def init_params(self, rng):
        from veles_tpu.ops import moe as moe_ops
        return moe_ops.moe_init(rng, self.input_shape[-1], self.d_ff,
                                self.n_experts, self.policy.param)

    def param_partition_specs(self, mesh_shape):
        if "expert" not in mesh_shape:
            return None
        from jax.sharding import PartitionSpec as P
        e = P("expert")
        return {"router": P(), "w1": e, "b1": e, "w2": e, "b2": e}

    def apply(self, params, x, train=False, key=None):
        from veles_tpu.ops import moe as moe_ops
        if self.mesh is not None and "expert" in self.mesh.shape:
            y, aux = moe_ops.moe_forward_sharded(
                params, x, self.mesh, top_k=self.top_k,
                capacity_factor=self.capacity_factor, policy=self.policy)
        else:
            y, aux = moe_ops.moe_forward(
                params, x, top_k=self.top_k,
                capacity_factor=self.capacity_factor, policy=self.policy)
        self.last_aux = aux
        return y


class TransformerBlock(Layer):
    """Pre-norm transformer block: norm→MHA→residual, norm→FFN→residual.
    ``impl`` as in MultiHeadAttention; optional dropout on both branches.
    ``n_experts`` > 0 swaps the dense MLP for a mixture-of-experts FFN
    (ops.moe), expert-parallel when the mesh has an ``expert`` axis.

    One class, configured (every default is the GPT-2 block):
    ``norm`` "layer" | "rms" | "layer_nobias" (``NORM_KINDS``) with
    ``norm_eps`` (default 1e-6); ``bias`` (False leaves every bias leaf
    out); ``head_dim`` (default d_model // n_heads); ``qk_norm``
    (per-head RMSNorm of q and k before the rotation); ``rope`` and
    ``rope_base``; ``window`` (sliding-window attention: a query
    attends the last ``window`` keys);
    ``parallel_block`` (ONE norm feeds both branches: ``x + attn(h) +
    ffn(h)``, no ``ln2`` leaf);
    ``router`` "gshard" (dense [N, E, C] dispatch, GELU experts) |
    "softmax_topk_renorm" | "sigmoid_topk_renorm" (dropless:
    ``ops.moe.moe_dropless_forward``, gated-SiLU experts of width
    ``d_expert``, ``top_k`` a token, gates a softmax over all experts
    or a sigmoid of each);
    ``experts_held`` = (first, count): the experts this layer holds and
    computes (default all); ``n_shared`` experts of width ``d_expert``
    that every token takes beside the routed ones, their results added
    (``shared_combine`` "sum") or averaged ("average") — computed whole
    on every share; ``indexer`` = {"heads", "head_dim", "topk"}:
    a learned sparse-attention indexer whose keys are a third per-token
    cache leaf (``cache_leaves``);
    ``mixer`` "attention" | "power_retention": the token mixer —
    ``ops.retention``'s decayed degree-2 attention in place of the
    softmax (``n_heads``, ``n_kv_heads``, ``head_dim``, ``qk_norm``,
    ``rope``, ``rope_base``, ``bias`` mean what they mean for attention;
    gate leaves ``mha.wg`` / ``mha.bg``), whose serve-time state is
    fixed-size a slot (``state_leaves``); not with ``window``,
    ``indexer`` or a sequence-parallel ``impl``;
    ``ffn`` "gelu" | "gated_silu": the dense FFN — ``w1`` / ``w2`` with
    GELU, or the three leaves ``ffn.w_gate`` / ``w_up`` / ``w_down`` and
    ``silu(gate) * up`` (the shared experts' expression).

    THE SERVE-TIME STATE PROTOCOL, one for every block: what it keeps a
    token (``cache_leaves``), how far back that is read (``cache_span``)
    and what it keeps a slot (``state_leaves``).  ``LMGenerator.
    _init_caches`` allocates from it and the paged batcher groups by it
    (whole-context pages, window rings, slot-major state)."""

    TYPES = ("transformer_block",)
    has_params = True
    mesh = None   # injected by the trainer (flash shard, ring/ulysses, moe)

    @property
    def needs_rng(self):
        return self.cfg.get("dropout_ratio", 0.0) > 0.0

    def _infer(self, input_shape):
        t, f = input_shape
        cfg = self.cfg
        self.n_heads = int(cfg.get("n_heads", 8))
        self.n_kv_heads = int(cfg.get("n_kv_heads", self.n_heads))
        self.head_dim = int(cfg.get("head_dim") or f // self.n_heads)
        self.d_ff = int(cfg.get("d_ff", 4 * f))
        self.n_experts = int(cfg.get("n_experts", 0))
        self.indexer = cfg.get("indexer") or None
        from veles_tpu.ops.moe import DROPLESS_ROUTERS
        self.router = cfg.get("router") or "gshard"
        if self.router != "gshard" and self.router not in DROPLESS_ROUTERS:
            raise ValueError("router must be gshard|%s"
                             % "|".join(DROPLESS_ROUTERS))
        self.dropless = self.router in DROPLESS_ROUTERS
        if self.dropless and not self.n_experts:
            raise ValueError("router=%s needs n_experts" % self.router)
        self.norm_kind = cfg.get("norm") or "layer"
        self.norm_eps = float(cfg.get("norm_eps") or 1e-6)
        self.parallel_block = bool(cfg.get("parallel_block", False))
        self.window = cfg.get("window") or None
        self.mixer = cfg.get("mixer") or "attention"
        if self.mixer not in ("attention", "power_retention"):
            raise ValueError("mixer must be attention|power_retention")
        self.retention = self.mixer == "power_retention"
        if self.retention:
            for key, what in (
                    ("window", "its gate is its window"),
                    ("indexer", "it selects no keys: it keeps none"),
                    ("lora_rank", "its state has no adapter path yet")):
                if cfg.get(key):
                    raise ValueError("mixer=power_retention cannot take "
                                     "%s (%s)" % (key, what))
            if cfg.get("impl") in ("ring", "ulysses"):
                raise ValueError(
                    "mixer=power_retention has no sequence-parallel "
                    "form (impl=%s): its state is handed on in order"
                    % cfg.get("impl"))
        self.ffn_kind = cfg.get("ffn") or "gelu"
        if self.ffn_kind not in ("gelu", "gated_silu"):
            raise ValueError("ffn must be gelu|gated_silu")
        if self.ffn_kind != "gelu" and self.n_experts:
            raise ValueError("ffn=%s is the DENSE FFN's: an expert layer "
                             "has its own" % self.ffn_kind)
        self.n_shared = int(cfg.get("n_shared") or 0)
        if self.n_shared and not self.dropless:
            raise ValueError("n_shared needs a dropless router (%s)"
                             % "|".join(DROPLESS_ROUTERS))
        if cfg.get("shared_combine", "sum") not in ("sum", "average"):
            raise ValueError("shared_combine must be sum|average")
        self.shared_scale = (1.0 / self.n_shared if self.n_shared and
                             cfg.get("shared_combine") == "average"
                             else 1.0)
        self.last_aux = None
        if self.dropless:
            held = cfg.get("experts_held") or (0, self.n_experts)
            self.experts_first, self.experts_count = map(int, held)
            self.top_k = int(cfg.get("top_k", 2))
            self.d_expert = int(cfg.get("d_expert") or self.d_ff)
        elif self.n_experts:
            # the FFN is a full MoE layer instance — one implementation of
            # the dispatch/fallback logic, shared with the standalone type
            self._moe = MoE({"type": "moe", "n_experts": self.n_experts,
                             "d_ff": self.d_ff,
                             "top_k": self.cfg.get("top_k", 2),
                             "capacity_factor":
                                 self.cfg.get("capacity_factor", 2.0)})
            self._moe.setup(input_shape)
        return (t, f)

    def _attn_kwargs(self):
        """What every attention entry point of this block takes."""
        return dict(n_kv_heads=self.n_kv_heads, policy=self.policy,
                    use_rope=bool(self.cfg.get("rope", False)),
                    rope_base=float(self.cfg.get("rope_base") or 10000.0),
                    indexer=self.indexer)

    def _mixer_kwargs(self):
        """``_attn_kwargs`` as ``ops.retention``'s mixer takes them."""
        kw = self._attn_kwargs()
        del kw["indexer"]
        return kw

    def cache_span(self):
        """How far back this block's per-token state is read: None =
        the whole context, else the last ``window`` positions (what the
        dense generator keeps as a rolling cache and the paged pool as
        a ring of blocks)."""
        return self.window

    def cache_leaves(self):
        """The per-token serve-time state this block keeps, leaf name ->
        (heads, width): what ``LMGenerator._init_caches`` allocates and
        the paged pool pages.  A retention block keeps none."""
        if self.retention:
            return {}
        leaves = {"k": (self.n_kv_heads, self.head_dim),
                  "v": (self.n_kv_heads, self.head_dim)}
        if self.indexer:
            leaves["idx"] = (1, int(self.indexer["head_dim"]))
        return leaves

    def state_leaves(self):
        """The serve-time state this block keeps A SLOT, leaf name ->
        shape, float32 whatever the cache dtype: a retention block's
        ``s`` and ``z`` (``ops.retention.RetentionState``); ``{}`` for an
        attention block."""
        if not self.retention:
            return {}
        from veles_tpu.ops import retention
        return retention.state_shapes(self.n_kv_heads, self.head_dim)

    def param_partition_specs(self, mesh_shape):
        if not self.n_experts or self.dropless:
            return None
        sub = self._moe.param_partition_specs(mesh_shape)
        return None if sub is None else {"moe": sub}

    def init_params(self, rng):
        from veles_tpu.ops import attention
        from veles_tpu.ops import moe as moe_ops
        f = self.input_shape[-1]
        std = f ** -0.5
        kind = self.cfg.get("norm", "layer")
        bias = bool(self.cfg.get("bias", True))
        dtype = self.policy.param

        def w(shape, s):
            return jnp.asarray(rng.normal(0.0, s, shape), dtype)

        mixer = dict(n_kv_heads=self.n_kv_heads, bias=bias,
                     head_dim=self.cfg.get("head_dim"),
                     qk_norm=bool(self.cfg.get("qk_norm", False)))
        if self.retention:
            from veles_tpu.ops import retention
            mha = retention.mixer_init(rng, f, self.n_heads, dtype, **mixer)
        else:
            mha = attention.mha_init(rng, f, self.n_heads, dtype,
                                     indexer=self.indexer, **mixer)
        params = {"ln1": _norm_init(kind, f), "mha": mha}
        if not self.parallel_block:
            params["ln2"] = _norm_init(kind, f)
        if self.dropless:
            params["moe"] = moe_ops.moe_dropless_init(
                rng, f, self.d_expert, self.n_experts, dtype,
                n_held=self.experts_count)
            if self.n_shared:
                params["shared"] = moe_ops.shared_experts_init(
                    rng, f, self.d_expert, self.n_shared, dtype)
        elif self.n_experts:
            params["moe"] = self._moe.init_params(rng)
        elif self.ffn_kind == "gated_silu":
            params["ffn"] = moe_ops.shared_experts_init(
                rng, f, self.d_ff, 1, dtype)
        else:
            params.update(w1=w((f, self.d_ff), std),
                          w2=w((self.d_ff, f), self.d_ff ** -0.5))
            if bias:
                params.update(b1=jnp.zeros((self.d_ff,), dtype),
                              b2=jnp.zeros((f,), dtype))
        r = int(self.cfg.get("lora_rank", 0))
        if r > 0:
            # LoRA q/v adapters (Hu et al. 2021): rank-r factors added
            # to the attention's q and v projections; qb/vb start at
            # ZERO so the adapted block computes exactly the base.
            # At train time apply() freezes every base leaf — pair
            # with --warm-start to fine-tune a pretrained checkpoint
            # updating only ~2·2·f·r params per block.
            d_q = self.head_dim * self.n_heads
            d_kv = self.head_dim * self.n_kv_heads
            params["mha"]["lora"] = {
                "qa": w((f, r), std),
                "qb": jnp.zeros((r, d_q), dtype),
                "va": w((f, r), std),
                "vb": jnp.zeros((r, d_kv), dtype),
            }
        return params

    @staticmethod
    def _lora_freeze(params):
        """stop_gradient every base leaf, keeping only the lora subtree
        trainable (the standard LoRA contract)."""
        lora = params["mha"]["lora"]
        base = {k: ({mk: mv for mk, mv in v.items() if mk != "lora"}
                    if k == "mha" else v)
                for k, v in params.items()}
        frozen = jax.tree_util.tree_map(jax.lax.stop_gradient, base)
        frozen["mha"]["lora"] = lora
        return frozen

    def apply(self, params, x, train=False, key=None):
        from veles_tpu.ops import attention
        if train and "lora" in params.get("mha", {}):
            params = self._lora_freeze(params)
        ratio = self.cfg.get("dropout_ratio", 0.0)
        k1 = k2 = None
        if train and ratio > 0.0 and key is not None:
            k1, k2 = jax.random.split(key)
        x = self._residual(x)
        normed = self._norm(params["ln1"], x)
        if self.retention:
            if not self.cfg.get("causal", False):
                raise ValueError("mixer=power_retention is causal")
            from veles_tpu.ops import retention
            h = retention.mixer_forward(params["mha"], normed, self.n_heads,
                                        **self._mixer_kwargs())
        else:
            h = attention.mha_forward(
                params["mha"], normed, self.n_heads,
                causal=bool(self.cfg.get("causal", False)),
                impl=self.cfg.get("impl", "blockwise"),
                attn_fn=_seq_parallel_attn_fn(self),
                window=self.cfg.get("window"),
                flash_shard=_flash_shard(self), **self._attn_kwargs())
        if k1 is not None:
            h = dropout.forward(h, k1, ratio)
        if not self.parallel_block:
            x = x + h
            normed = self._norm(params["ln2"], x)
        f, _ = self._ffn(params, normed, train)
        if k2 is not None:
            f = dropout.forward(f, k2, ratio)
        # a parallel block adds both branches to the stream it read
        return x + h + f if self.parallel_block else x + f

    def _norm(self, p, x):
        return _norm_apply(p, x, self.norm_kind, self.norm_eps)

    def _residual(self, x):
        """The residual stream in the accumulation dtype: a model built
        with bfloat16 parameters embeds to bfloat16, and its first norm
        would round its output to that (every later block already sees
        the float32 sum of its predecessor)."""
        return x.astype(jnp.promote_types(x.dtype, self.policy.accum))

    def _ffn(self, params, h, train):
        """The post-norm branch, shared by apply() and step() so training
        and incremental decoding can never diverge.  Returns ``(h,
        counts)``: with dropless routing ``experts_touched``, the held
        experts that got a token, and where the layer holds a share of
        the experts ``expert_pairs``, the pairs that landed on them;
        ``{}`` otherwise.  GShard MoE: the router aux loss lands in
        self.last_aux unconditionally — eval loss includes it, same as
        the standalone ``moe`` layer type."""
        if self.dropless:
            from veles_tpu.ops import moe as moe_ops
            counts = {}
            y, counts["experts_touched"] = moe_ops.moe_dropless_forward(
                params["moe"], h, top_k=self.top_k,
                first=self.experts_first, policy=self.policy,
                router=self.router, counts=counts)
            if self.n_shared:
                y = y + moe_ops.shared_experts_forward(
                    params["shared"], h, self.shared_scale, self.policy)
            return y, counts
        if self.n_experts:
            self._moe.mesh = self.mesh
            h = self._moe.apply(params["moe"], h, train=train)
            self.last_aux = self._moe.last_aux
            self._moe.last_aux = None
            return h, {}
        if self.ffn_kind == "gated_silu":
            from veles_tpu.ops import moe as moe_ops
            return moe_ops.shared_experts_forward(
                params["ffn"], h, 1.0, self.policy), {}
        h = linear.matmul(h, params["w1"], self.policy)
        if "b1" in params:
            h = h + params["b1"]
        h = linear.matmul(jax.nn.gelu(h), params["w2"], self.policy)
        return (h + params["b2"] if "b2" in params else h), {}

    def _cached_attn_block(self, params, x, attn_call):
        """Shared serve-time block body (step + prefill — they must
        never diverge): norm → cached attention → residual, norm → FFN →
        residual; with ``parallel_block`` one norm, both branches on it,
        one residual.  ``attn_call(h) -> (h, cache, ...)``; returns
        ``((x, cache, ...), counts)`` with ``_ffn``'s counts."""
        x = self._residual(x)
        normed = self._norm(params["ln1"], x)
        h, *rest = attn_call(normed)
        if self.parallel_block:
            f, counts = self._ffn(params, normed, train=False)
            return (x + h + f, *rest), counts
        x = x + h
        h, counts = self._ffn(params, self._norm(params["ln2"], x),
                              train=False)
        return (x + h, *rest), counts

    def step(self, params, x, cache, pos):
        """Incremental-decoding step: x [B, 1, F] at position ``pos``
        against the block's cache (models.generate; a tuple of the
        leaves ``cache_leaves`` names).  Dropout off (serve time); MoE
        FFN works unchanged on the single position."""
        from veles_tpu.ops import attention, retention
        if self.retention:
            return self._cached_attn_block(
                params, x, lambda h: retention.mixer_step(
                    params["mha"], h, cache, pos, self.n_heads,
                    **self._mixer_kwargs()))[0]
        return self._cached_attn_block(
            params, x,
            lambda h: attention.mha_step(
                params["mha"], h, cache, pos, self.n_heads,
                window=self.cfg.get("window"), **self._attn_kwargs()))[0]

    def step_paged(self, params, x, pool, table, pos, ring=False,
                   active=None):
        """Incremental-decoding step against a PAGED pool: x
        [B, 1, F], every row at its own position ``pos[b]`` (the
        paged batcher's tick — attention.mha_step_paged reads the
        shared block pool through the table).  Same block body as
        step() via _cached_attn_block, so the two can never diverge.
        ``table``: this block's group's — the whole-context table, or
        with ``ring`` (a block whose ``cache_span`` the pool keeps as a
        ring) the ring's, which position ``p`` enters at ``(p // block)
        mod ring``.  Returns
        ``(x, pool, counts)``: what the step counted where the work ran
        — ``attended`` [B], the keys each row's softmax ran over
        (``win_attended`` beside it from a ring's block), and
        with dropless routing ``experts_touched``, the experts that got
        a token, and ``expert_pairs`` (``_ffn``).  A retention block's
        ``pool`` is its slot-major state (no table, no position to
        enter at); ``active`` [B] says whose rows the tick advances —
        the others' state is left as it is."""
        from veles_tpu.ops import attention, retention
        if self.retention:
            (x, pool), counts = self._cached_attn_block(
                params, x, lambda h: retention.mixer_step(
                    params["mha"], h, pool, pos, self.n_heads, rows=True,
                    active=active, **self._mixer_kwargs()))
            return x, pool, counts
        (x, pool, attended), counts = self._cached_attn_block(
            params, x,
            lambda h: attention.mha_step_paged(
                params["mha"], h, pool, table, pos, self.n_heads,
                window=self.window if ring else None,
                **self._attn_kwargs()))
        counts = dict({"attended": attended}, **counts)
        if ring:
            counts["win_attended"] = attended
        return x, pool, counts

    def prefill(self, params, x, cache, valid=None):
        """Chunked prefill: the whole prompt chunk x [B, Tp, F] in one
        parallel pass, its per-token state written into cache positions
        [0, Tp) — equivalent to Tp step() calls at full-forward cost
        (models.generate's serving prefill).  ``valid``: the tokens
        before it alone enter a retention block's state (a cache row's
        padding is overwritten before it is read; a state's cannot be)."""
        from veles_tpu.ops import attention, retention
        if self.retention:
            return self._cached_attn_block(
                params, x, lambda h: retention.mixer_chunk(
                    params["mha"], h, None, 0, self.n_heads, valid=valid,
                    **self._mixer_kwargs()))[0]
        return self._cached_attn_block(
            params, x,
            lambda h: attention.mha_prefill(
                params["mha"], h, cache, self.n_heads,
                window=self.cfg.get("window"), **self._attn_kwargs()))[0]

    def chunk_step(self, params, x, cache, start, counts=None, valid=None):
        """K positions [start, start+K) in one parallel pass against
        the existing cache — the speculative-decoding verify step and
        a staged prefill pass (equivalent to K step() calls).
        ``counts``: a dict that takes ``_ffn``'s counts; ``valid``:
        ``prefill``'s."""
        from veles_tpu.ops import attention, retention
        if self.retention:
            def mix(h):
                return retention.mixer_chunk(
                    params["mha"], h, cache, start, self.n_heads,
                    valid=valid, **self._mixer_kwargs())
        else:
            def mix(h):
                return attention.mha_chunk_step(
                    params["mha"], h, cache, start, self.n_heads,
                    window=self.cfg.get("window"), **self._attn_kwargs())
        out, counted = self._cached_attn_block(params, x, mix)
        if counts is not None:
            counts.update(counted)
        return out


class PipelinedTransformer(Layer):
    """N identical transformer blocks run as pipeline stages
    (parallel.pipeline — GPipe microbatch schedule over the mesh's
    ``pipe`` axis; sequential ``lax.scan`` over stages without one).
    Stage params stack on a leading [n_blocks, ...] axis so the pipe
    sharding is one PartitionSpec.  Dropout inside pipelined stages is
    unsupported (keys would need per-stage plumbing); keep it in
    surrounding layers."""

    TYPES = ("pipelined_transformer",)
    has_params = True
    mesh = None   # injected by the trainer when the mesh has 'pipe'

    def _infer(self, input_shape):
        t, f = input_shape
        self.n_blocks = int(self.cfg.get("n_blocks", 2))
        self.n_microbatches = int(self.cfg.get("n_microbatches", 4))
        # forward EVERY TransformerBlock option the caller set (a
        # hand-maintained whitelist silently dropped rope/window/
        # n_kv_heads in past revisions); only the pipeline's own keys
        # and the unsupported dropout are withheld.  Options the
        # pipelined wrapper genuinely cannot honor must FAIL, not
        # silently degrade:
        if int(self.cfg.get("n_experts", 0)):
            raise ValueError(
                "pipelined_transformer does not support MoE stages (the "
                "router aux loss cannot cross the stage scan) — use "
                "transformer_block layers with an 'expert' mesh axis")
        if self.cfg.get("impl") in ("ring", "ulysses"):
            raise ValueError(
                "pipelined_transformer does not support sequence-"
                "parallel attention inside stages — shard the sequence "
                "with plain transformer_block layers instead")
        own = {"type", "n_blocks", "n_microbatches", "dropout_ratio",
               "name"}
        block_cfg = {k: v for k, v in self.cfg.items() if k not in own}
        block_cfg.update({"type": "transformer_block",
                          "dropout_ratio": 0.0})
        # per-stage remat rides the whole pipelined layer: set
        # {"remat": true} on THIS layer and the trainer checkpoints the
        # full stage scan (stages recompute during the backward sweep)
        self._block = TransformerBlock(block_cfg)
        self._block.setup(input_shape)
        return (t, f)

    def init_params(self, rng):
        stages = [self._block.init_params(rng)
                  for _ in range(self.n_blocks)]
        return {"stages": jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *stages)}

    def param_partition_specs(self, mesh_shape):
        if "pipe" not in mesh_shape:
            return None
        from jax.sharding import PartitionSpec as P
        return P("pipe")   # every stacked [S, ...] leaf shards its stage

    def apply(self, params, x, train=False, key=None):
        block = self._block

        def fn(p, h):
            return block.apply(p, h, train=False, key=None)

        if self.mesh is not None and "pipe" in self.mesh.shape:
            from veles_tpu.parallel import pipeline
            # combined data x pipe mesh: keep each data slice's batch
            # rows local to its own pipeline instance
            batch_axis = ("data" if self.mesh.shape.get("data", 1) > 1
                          else None)
            return pipeline.pipeline_apply_sharded(
                fn, params["stages"], x, self.mesh,
                n_microbatches=self.n_microbatches,
                batch_axis=batch_axis)
        h, _ = jax.lax.scan(lambda h, p: (fn(p, h), None), x,
                            params["stages"])
        return h


class TiedLMHead(Layer):
    """LM head that reuses the embedding table transposed
    (``tie_to`` = the embedding layer's name): logits = x @ tableᵀ.
    Weight tying saves vocab×d_model params and regularizes; gradients
    flow to the table through both uses."""

    TYPES = ("tied_lm_head",)
    needs_full_params = True

    def _infer(self, input_shape):
        t, f = input_shape
        self.tie_to = self.cfg["tie_to"]
        self.n_in = f
        self.n_out = int(self.cfg["vocab_size"])
        return (t, self.n_out)

    def apply(self, params, x, train=False, key=None):
        # ``params`` is the FULL tree (needs_full_params)
        table = params[self.tie_to]["table"]        # [vocab, d_model]
        from veles_tpu.ops.quant import (QuantWeight4, is_quant,
                                         quant_matmul_t)
        if isinstance(table, QuantWeight4):
            # nibble-packed: the payload's packed axis is d/2, so the
            # logical shape is (vocab, table.n)
            shape = (table.q.shape[0], table.n)
        elif is_quant(table):
            shape = table.q.shape
        else:
            shape = table.shape
        if shape != (self.n_out, self.n_in):
            raise ValueError("tied table %s does not match head (%d, %d)"
                             % (shape, self.n_out, self.n_in))
        if is_quant(table):
            # quantized serving: the per-ROW table scales are exactly
            # the head's per-output-channel scales (ops.quant)
            return quant_matmul_t(x, table)
        return linear.matmul(x, table.T, self.policy)


class TimestepDense(Layer):
    """Per-timestep dense over [T, F] samples: [B, T, F] → [B, T, out]
    (the transformer projection / LM head; weight shared across time)."""

    TYPES = ("timestep_dense", "timestep_dense_tanh", "timestep_dense_relu")
    has_params = True

    def _infer(self, input_shape):
        t, f = input_shape
        self.n_in = f
        self.n_out = int(self.cfg["output_sample_shape"])
        return (t, self.n_out)

    def init_params(self, rng):
        return linear.init_params(
            rng, self.n_in, self.n_out,
            bias=self.cfg.get("include_bias", True),
            weights_stddev=self.cfg.get("weights_stddev"),
            dtype=self.policy.param)

    def apply(self, params, x, train=False, key=None):
        y = linear.matmul(x, params["weights"], self.policy)
        if "bias" in params:
            y = y + params["bias"].astype(y.dtype)
        return self._activation()(y)


class SeqPool(Layer):
    """Collapse the time axis: mean / max / last (classifier head input)."""

    TYPES = ("seq_pool",)

    def _infer(self, input_shape):
        self.mode = self.cfg.get("mode", "mean")
        return tuple(input_shape[1:])

    def apply(self, params, x, train=False, key=None):
        if self.mode == "mean":
            return jnp.mean(x, axis=1)
        if self.mode == "max":
            return jnp.max(x, axis=1)
        return x[:, -1]


class ZeroFiller(Layer):
    """Weight-mask regularizer: masks the *previous* parametric layer's
    weights after every update (ref Znicz ZeroFiller).  Carries no forward
    compute."""

    TYPES = ("zerofiller",)

    def apply(self, params, x, train=False, key=None):
        return x


LAYER_TYPES = {}
for _cls in (All2All, ResizableAll2All, Conv, Deconv, Pooling, Depooling,
             StochasticPoolDepool, ChannelSplitter, ChannelMerger, LRN,
             Dropout, Activation, Cutter, LSTM, ZeroFiller, LayerNorm,
             GroupNorm, ConvResidualBlock,
             Embedding, PositionalEncoding, MultiHeadAttention, MoE,
             TransformerBlock, PipelinedTransformer, TimestepDense,
             TiedLMHead, SeqPool):
    for _t in _cls.TYPES:
        LAYER_TYPES[_t] = _cls


def make_layer(cfg):
    cfg_flat = _flatten_config(cfg)
    t = cfg_flat["type"]
    if t not in LAYER_TYPES:
        raise KeyError("unknown layer type %r (known: %s)"
                       % (t, ", ".join(sorted(LAYER_TYPES))))
    return LAYER_TYPES[t](cfg)
