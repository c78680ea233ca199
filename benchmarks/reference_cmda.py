"""The plain reference of command-a-plus-05-2026's language model
(``model_type`` ``cohere2_moe``): a PARALLEL block whose one LayerNorm
(mean subtracted, a gain, no shift) feeds grouped-query attention and a
mixture of experts alike; sliding-window layers with rotary positions
and full-attention layers with no positional encoding at all, three to
one; 128 routed experts chosen eight a token by a sigmoid router, and
four shared experts every token takes, averaged — in straightforward
``jax.numpy`` and float32.  No cache, no kernels, no batching, no
paging, no sorting of tokens by expert.

    h  = LN(x)                       (x - mean) / sqrt(var + eps) * g
    q, k, v = h Wq, h Wk, h Wv       128 query / 8 KV heads of 128
    sliding layer: q, k rotated (features (2i, 2i+1) paired, theta
        50,000, all 128 dims); query i attends keys 0 <= i - j < 4096
    full layer:    no rotation;     query i attends keys j <= i
    A  = Wo softmax(q k / sqrt(128)) v
    s  = sigmoid(h Wr)               128 scores, float32
    g  = the 8 largest of s divided by their sum (ties towards the
         lower expert id)
    F  = sum_e g_e E_e(h) + 1/4 sum_{s<4} S_s(h)
         E(h) = W_down (silu(W_gate h) * W_up h), expert width 4096
    x' = x + A + F
    logits = LN(x_last) E^T          the tied embedding, logit_scale 1

The chip's SHARE of the deployment (the configuration file's
``deployment``): routing is over all 128 experts, the experts held are
``experts_held`` = (first, count) and only their part of the routed sum
is added; the four shared experts are whole on every share; the
vocabulary is the held slice.  ``held=(0, 128)`` is the uncut layer (the
CPU tests add the shares up against it).

It imports nothing of the program and takes nothing the program made.
The weights come from :func:`layer_weights` / :func:`outer_weights`
(from the seed, on the device, ONE LAYER AT A TIME, in bfloat16 — the
configuration's parameter dtype — and upcast to float32 one matrix at
a time where a matmul reads it: a layer in float32 is 4.6 GB); the
benchmark's build copies the same values into the program.  Each layer
is applied to every checked sequence before the next is made.  The
attention goes through in blocks of queries, the experts in blocks of
tokens, each held expert as one dense pass (gate nought where a token
did not choose it).

Every matmul goes through one of ``reference.PRECISIONS`` (``f32``:
float32 at ``Precision.HIGHEST``; ``int8``: the control, both operands
on a per-tensor int8 grid).  Three more controls switch a mechanism
off: ``window_off`` (the sliding layers attend the whole context),
``rope_on_full`` (the full layers rotate q and k as the sliding ones
do), ``shared_dropped`` (the shared experts' average left out).

What the published config does not give, and the convention taken
(also the configuration file's ``assumed``): the width of an expert and
of a shared expert is ``intermediate_size``; the shared experts'
average is added to the routed sum with weight 1; no router bias and no
score correction; the rotation pairs features (2i, 2i+1)
(``position_embedding_type`` ``rope_gptj``), as the program's ``rope``
does; the shared experts' matrices lie side by side as one gated MLP of
width 4 x 4096 (expert s is columns ``[s x 4096, (s + 1) x 4096)``) —
the reference runs the four and averages."""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import PRECISIONS, seed_key

#: queries a block of the attention holds (128 heads x 64 x 32k keys of
#: float32 scores are 1.1 GB), tokens a block of the experts
QUERY_BLOCK = 64
TOKEN_BLOCK = 2048
#: groups of query blocks, each given only the keys up to its own end
GROUPS = 4

#: the controls of ``logit_gap``: keyword arguments of ``make_layer``
CONTROL_KEYS = ("window_off", "rope_on_full", "shared_dropped")


# ----------------------------------------------------------------- weights
def _sizes(cfg):
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        e=cfg["num_experts_routed"], k=cfg["num_experts_per_tok"],
        f=cfg["intermediate_size"], shared=cfg["num_shared_experts"],
        vocab=cfg["vocab_size"], eps=float(cfg["layer_norm_eps"]),
        theta=float(cfg["rope_theta"]), window=cfg["sliding_window"])


def _seeded(cfg):
    return cfg["assumed"]["seeded_weights"]


def layer_kind(cfg, i):
    return cfg["layer_types"][i % len(cfg["layer_types"])]


def layer_weights(cfg, key, i, held=None, dtype=jnp.bfloat16):
    """Layer ``i``'s leaves, arranged as the program's block tree, with
    the routed experts ``held`` = (first, count) (default the
    configuration's ``experts_held``).  Matrices are normal draws of
    ``initializer_std`` cast to ``dtype`` (the router's times
    ``router_gain``), the gain ones in float32.  An expert's draws
    depend on its own id alone, so a share's experts are the uncut
    layer's."""
    z = _sizes(cfg)
    std = float(_seeded(cfg)["initializer_std"])
    first, count = held or cfg["experts_held"]
    key = jax.random.fold_in(key, 1 + i)

    def w(name, *shape, k=key):
        return (std * jax.random.normal(jax.random.fold_in(k, name), shape,
                                        jnp.float32)).astype(dtype)

    def experts(name, *shape):
        return jnp.stack([w(name, *shape,
                            k=jax.random.fold_in(key, 1000 + first + e))
                          for e in range(count)])

    d, dq, dkv = z["d"], z["heads"] * z["hd"], z["kv"] * z["hd"]
    wide = z["shared"] * z["f"]
    return {
        "ln1": {"gamma": jnp.ones((d,), jnp.float32)},
        "mha": {"wq": w(0, d, dq), "wk": w(1, d, dkv), "wv": w(2, d, dkv),
                "wo": w(3, dq, d)},
        "moe": {"router": w(4, d, z["e"]) * jnp.asarray(
                    _seeded(cfg).get("router_gain", 1.0), dtype),
                "w_gate": experts(5, d, z["f"]),
                "w_up": experts(6, d, z["f"]),
                "w_down": experts(7, z["f"], d)},
        "shared": {"w_gate": w(8, d, wide), "w_up": w(9, d, wide),
                   "w_down": w(10, wide, d)},
    }


def outer_weights(cfg, key, which, dtype=jnp.bfloat16):
    """``which``: "embed" -> the [vocab, d] table (the head is its
    transpose); "norm" -> the final LayerNorm's gain."""
    z = _sizes(cfg)
    if which == "norm":
        return jnp.ones((z["d"],), jnp.float32)
    std = float(_seeded(cfg).get("embedding_std",
                                 _seeded(cfg)["initializer_std"]))
    return (std * jax.random.normal(jax.random.fold_in(key, 1001),
                                    (z["vocab"], z["d"]), jnp.float32)
            ).astype(dtype)


def _f32(a):
    return a.astype(jnp.float32)


# ----------------------------------------------------------------- forward
def _layer_norm(x, g, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g


def _rotate(x, positions, theta):
    """x [T, H, D]: features (2i, 2i+1) turned by position * theta^(-i /
    (D/2))."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def route(cfg, h, router):
    """Gates [t, E]: the sigmoid scores of the ``num_experts_per_tok``
    largest divided by their sum, nought elsewhere (ties towards the
    lower expert id: a stable sort)."""
    z = _sizes(cfg)
    scores = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", h, _f32(router), precision=jax.lax.Precision.HIGHEST))
    order = jnp.argsort(-scores, axis=-1, stable=True)[:, :z["k"]]
    top = jnp.take_along_axis(scores, order, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], order].set(top)


def make_ffn(cfg, t, precision="f32", held=None, shared=True):
    """``ffn(h [t, d], lw) -> [t, d]``: the routed experts ``held`` =
    (first, count)'s part of the mixture (``lw``'s expert leaves hold
    exactly those) plus, with ``shared``, the four shared experts'
    average."""
    z = _sizes(cfg)
    mm = PRECISIONS[precision]
    first, count = held or cfg["experts_held"]
    tb = min(TOKEN_BLOCK, t)
    if t % tb:
        raise ValueError("sequence length %d must divide into blocks" % t)

    def gated(hb, w_gate, w_up, w_down):
        up = mm(hb, _f32(w_up), "td,df->tf")
        gate = mm(hb, _f32(w_gate), "td,df->tf")
        return mm(jax.nn.silu(gate) * up, _f32(w_down), "tf,fd->td")

    def ffn(h, lw):
        m, sh = lw["moe"], lw["shared"]
        gates = route(cfg, h, m["router"])

        def tokens(at):
            hb = jax.lax.dynamic_slice_in_dim(h, at, tb)
            gb = jax.lax.dynamic_slice_in_dim(gates, at, tb)

            def one(y, e):
                out = gated(hb, m["w_gate"][e], m["w_up"][e],
                            m["w_down"][e])
                return y + gb[:, first + e, None] * out, None

            y = jax.lax.scan(one, jnp.zeros_like(hb),
                             jnp.arange(count))[0]
            if shared:
                f = z["f"]
                for s in range(z["shared"]):
                    cols = slice(s * f, (s + 1) * f)
                    y = y + gated(hb, sh["w_gate"][:, cols],
                                  sh["w_up"][:, cols],
                                  sh["w_down"][cols]) / z["shared"]
            return y

        return jax.lax.map(tokens, jnp.arange(0, t, tb)).reshape(t, -1)

    return ffn


def make_layer(cfg, t, kind, precision="f32", held=None,
               window_off=False, rope_on_full=False, shared_dropped=False):
    """``layer(x [t, d], lw) -> x`` for one layer's leaves ``lw`` of
    ``kind`` ("sliding_attention" | "full_attention"); trailing padding
    is never attended (it lies after every real query)."""
    z = _sizes(cfg)
    mm = PRECISIONS[precision]
    g = z["heads"] // z["kv"]
    qb = min(QUERY_BLOCK, t)
    if t % qb:
        raise ValueError("sequence length %d must divide into blocks" % t)
    sliding = kind == "sliding_attention"
    rotate = sliding or rope_on_full
    window = z["window"] if sliding and not window_off else None
    pos = jnp.arange(t)
    ffn = make_ffn(cfg, t, precision, held, shared=not shared_dropped)

    def attention(h, lw):
        a = lw["mha"]
        q = mm(h, _f32(a["wq"]), "td,de->te").reshape(t, z["heads"],
                                                      z["hd"])
        k = mm(h, _f32(a["wk"]), "td,de->te").reshape(t, z["kv"], z["hd"])
        v = mm(h, _f32(a["wv"]), "td,de->te").reshape(t, z["kv"], z["hd"])
        if rotate:
            q = _rotate(q, pos, z["theta"])
            k = _rotate(k, pos, z["theta"])

        def make_block(ext):
            # queries [at, at + qb) against keys [0, ext): a key past
            # every query of the group is masked anyway
            kx, vx, px = k[:ext], v[:ext], pos[:ext]

            def block(at):
                qq = jax.lax.dynamic_slice_in_dim(q, at, qb).reshape(
                    qb, z["kv"], g, z["hd"])
                qpos = at + jnp.arange(qb)
                keep = px[None, :] <= qpos[:, None]
                if window is not None:
                    keep = keep & (qpos[:, None] - px[None, :] < window)
                att = mm(qq, kx, "qkgd,skd->kgqs") / math.sqrt(z["hd"])
                att = jnp.where(keep[None, None], att, -jnp.inf)
                o = mm(jax.nn.softmax(att, axis=-1), vx, "kgqs,skd->qkgd")
                return o.reshape(qb, z["heads"] * z["hd"])

            return block

        n_blocks = t // qb
        groups = GROUPS if n_blocks >= 4 * GROUPS else 1
        outs = []
        for gi in range(groups):
            lo = gi * n_blocks // groups
            hi = (gi + 1) * n_blocks // groups
            outs.append(jax.lax.map(make_block(hi * qb),
                                    jnp.arange(lo, hi) * qb))
        o = jnp.concatenate(outs).reshape(t, -1)
        return mm(o, _f32(a["wo"]), "te,ed->td")

    def layer(x, lw):
        h = _layer_norm(x, lw["ln1"]["gamma"], z["eps"])
        return x + attention(h, lw) + ffn(h, lw)

    return layer


def pad_length(n):
    """Sequence lengths the layers are compiled for: multiples of
    ``TOKEN_BLOCK`` (a handful of programs whatever the answers'
    lengths); short ones, as the tests use, whole query blocks."""
    if n <= TOKEN_BLOCK:
        return -(-n // QUERY_BLOCK) * QUERY_BLOCK if n > QUERY_BLOCK \
            else max(16, n)
    return -(-n // TOKEN_BLOCK) * TOKEN_BLOCK


def forward_logits(cfg, seed, sequences, positions, precision="f32",
                   held=None, **controls):
    """Logits [len(positions[i]), vocab] of each token sequence at the
    given positions.  One layer's weights at a time, each applied to
    every sequence before the next is made.  ``controls``: keyword
    arguments of ``make_layer`` (``CONTROL_KEYS``)."""
    z = _sizes(cfg)
    key = seed_key(seed)
    mm = PRECISIONS[precision]
    table = jax.jit(lambda: outer_weights(cfg, key, "embed"))()
    xs = []
    for seq in sequences:
        toks = np.zeros((pad_length(len(seq)),), np.int32)
        toks[:len(seq)] = seq
        xs.append(_f32(table[jnp.asarray(toks)]))
    layer_maker = jax.jit(lambda k, i: layer_weights(cfg, k, i, held))
    layers = {}                 # one compiled layer a (kind, length)
    for i in range(cfg["num_hidden_layers"]):
        lw = layer_maker(key, i)
        kind = layer_kind(cfg, i)
        for n, x in enumerate(xs):
            at = (kind, x.shape[0])
            if at not in layers:
                layers[at] = jax.jit(make_layer(
                    cfg, x.shape[0], kind, precision, held, **controls))
            xs[n] = layers[at](x, lw)
        del lw
    norm = outer_weights(cfg, key, "norm")
    out = []
    for x, where in zip(xs, positions):
        rows = _layer_norm(x[jnp.asarray(np.asarray(where, np.int32))],
                           norm, z["eps"])
        out.append(cfg.get("logit_scale", 1) * mm(rows, _f32(table),
                                                  "td,vd->tv"))
    return out


def served(samples):
    """What a sample ``{"prompt", "result"}`` asks of the reference:
    the sequence fed (all but the last token) and the positions whose
    logits chose the served tokens."""
    return ([s["result"][:-1] for s in samples],
            [list(range(len(s["prompt"]) - 1, len(s["result"]) - 1))
             for s in samples])


def reference_logits(cfg, seed, samples):
    """The float32 reference's logits at every served position."""
    return forward_logits(cfg, seed, *served(samples))


def logit_gaps(cfg, seed, samples, probe_precision=None, reference=None,
               **controls):
    """For each sample ``{"prompt", "result"}``: at every served
    position, how far the served token's reference logit lies below the
    reference's best.  ``probe_precision`` / ``controls`` build a
    CONTROL: the tokens that a lesser reference (int8 operands; a
    mechanism switched off, ``CONTROL_KEYS``) puts first at those
    positions take the served tokens' place, and the float32 reference
    with every mechanism on judges them.  ``reference``:
    :func:`reference_logits` of the same samples, where the caller
    already has them.  Returns ``(widest gap, tokens compared)``."""
    logits = reference or reference_logits(cfg, seed, samples)
    if probe_precision is not None or any(controls.values()):
        lesser = forward_logits(cfg, seed, *served(samples),
                                precision=probe_precision or "f32",
                                **controls)
        tokens = [jnp.argmax(lg, axis=-1) for lg in lesser]
    else:
        tokens = [jnp.asarray(s["result"][len(s["prompt"]):], jnp.int32)
                  for s in samples]
    worst, n = 0.0, 0
    for lg, tok in zip(logits, tokens):
        gap = jnp.max(lg, axis=-1) - jnp.take_along_axis(
            lg, tok[:, None], axis=-1)[:, 0]
        worst = max(worst, float(jnp.max(gap)))
        n += int(tok.shape[0])
    return worst, n
