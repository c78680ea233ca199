"""The plain reference of Qwen3-Next-80B-A3B-Instruct's language model
(``model_type`` ``qwen3_next``): periods of three GATED DELTANET layers
and one GATED ATTENTION layer, every layer with a mixture of experts and
a sigmoid-gated shared expert — in straightforward ``jax.numpy`` and
float32, the delta rule as its PER-TOKEN RECURRENCE:

    h = RMSNorm(x)                 x̂ (1 + w), w zero-centred, eps 1e-6
  linear layer (16 key / 32 value heads of 128, conv 4):
    [q; k; v], z = h W_qkvz        q, k 16 x 128; v, z 32 x 128
    b, a = h W_ba                  32 each
    [q; k; v] = silu(causal depthwise conv_4([q; k; v]))   no bias
    q = l2norm(q) / sqrt(128), k = l2norm(k)    a key head per 2 value heads
    beta = sigmoid(b), g = -exp(A_log) softplus(a + dt_bias)
    for t in order:  S = e^g S;  S += beta k (v - S^T k)^T;  o_t = S^T q
    A = W_o (RMSNorm_head(o) * silu(z))         the norm's gain per dim
  gated attention layer (16 query / 2 KV heads of 256):
    q, gate = h W_q, h W_gate      q and k: QK-norm x̂ (1 + w) a head
    rope (theta 1e7) over the first 64 of 256 dims, pairs (2i, 2i+1)
    A = W_o (softmax(q k / 16) v * sigmoid(gate))   causal
  x' = x + A
  n = RMSNorm(x');  p = softmax(n W_r) over 512 experts (float32)
  F = sum_{e in the 10 largest, e held} p_e / sum_10 p * E_e(n)
      + sigmoid(n w_sg) E_shared(n)       E(n) = W_d (silu(W_g n) * W_u n)
  x'' = x' + F;   logits = RMSNorm(x_last) W_head   untied

The chip's SHARE of the deployment (the configuration file's
``deployment``): routing is over all ``num_experts_routed`` experts, the
experts held are ``experts_held`` = (first, count) and only their part
of the routed sum is added; the shared expert is whole on every share;
the vocabulary is the held slice.  ``held=(0, 512)`` is the uncut layer
(the CPU tests add the shares up against it).

No chunks, no WY form, no inverse, no state handed between passes, no
cache, no kernels, no batching, no sorting of tokens by expert: it
shares no formulation with the program (``veles_tpu/ops/deltanet.py``
serves the linear layers in a chunked form and a decode kernel).  It
imports nothing of the program and takes nothing the program made.  The
weights come from :func:`layer_weights` / :func:`outer_weights` (from
the seed, on the device, ONE LAYER AT A TIME, in bfloat16 — the
configuration's parameter dtype — and upcast to float32 one matrix at a
time where a matmul reads it); the benchmark's build copies the same
values into the program.  The attention goes through in blocks of
queries, the experts in blocks of tokens, each held expert as one dense
pass (gate nought where a token did not choose it).

Every matmul goes through one of ``reference.PRECISIONS`` (``f32``:
float32 at ``Precision.HIGHEST``; ``int8``: the control, both operands
on a per-tensor int8 grid).  Six more controls switch a mechanism off,
each what a program that lost it would compute: ``state_reset`` = n
(S zeroed at every multiple of n: the state not handed from pass to
pass), ``conv_dropped`` = n (the conv's tail dropped at every multiple
of n and for every decoded token: a conv that sees only the token
itself there), ``no_delta`` (S += beta k v^T: plain gated linear
attention), ``gate_off`` (the attention's output gate at 1),
``rope_full`` (rope over all 256 dims) and ``state_bf16`` (S rounded to
bfloat16 after every token).

What the published config does not give, and the convention taken
(also the configuration file's ``assumed``): the columns of W_qkvz and
W_ba lie as [q | k | v | z] and [b | a] (the published layout groups
them by key head: a fixed permutation, with seeded weights the same
model), the attention gate is a matrix of its own (published: the
second half of each head's q_proj columns); the rotation pairs features
(2i, 2i+1) within the first 64 (published: halves); the gated norm's
gain is plain (published: ones at init), every other norm's
zero-centred; l2norm's eps 1e-6."""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import PRECISIONS, seed_key

#: queries a block of the attention holds (16 heads x 64 x 33k keys of
#: float32 scores are 140 MB), tokens a block of the experts
QUERY_BLOCK = 64
TOKEN_BLOCK = 2048
#: groups of query blocks, each given only the keys up to its own end
GROUPS = 4
#: l2norm's eps and the norms' (the config's rms_norm_eps is read too)
L2_EPS = 1e-6

#: the controls of ``logit_gap``: keyword arguments of ``make_layer``
CONTROL_KEYS = ("state_reset", "conv_dropped", "no_delta", "gate_off",
                "rope_full", "state_bf16")

_HI = jax.lax.Precision.HIGHEST


# ----------------------------------------------------------------- weights
def _sizes(cfg):
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        hk=cfg["linear_num_key_heads"], hv=cfg["linear_num_value_heads"],
        dk=cfg["linear_key_head_dim"], dv=cfg["linear_value_head_dim"],
        conv=cfg["linear_conv_kernel_dim"],
        e=cfg["num_experts_routed"], k=cfg["num_experts_per_tok"],
        f=cfg["moe_intermediate_size"],
        fs=cfg["shared_expert_intermediate_size"],
        vocab=cfg["vocab_size"], eps=float(cfg["rms_norm_eps"]),
        theta=float(cfg["rope_theta"]),
        rot=int(cfg["head_dim"] * cfg["partial_rotary_factor"]))


def _seeded(cfg):
    return cfg["assumed"]["seeded_weights"]


def layer_kind(cfg, i):
    """"linear_attention" | "full_attention": every
    ``full_attention_interval``-th layer is full."""
    return "linear_attention" if (i + 1) % cfg["full_attention_interval"] \
        else "full_attention"


def half_lives(cfg):
    """Tokens after which a value head's gate has halved the state at a
    zero input: log-spaced over the heads between ``value_half_life``'s
    ends."""
    lo, hi = map(float, _seeded(cfg)["value_half_life"])
    n = cfg["linear_num_value_heads"]
    return [lo * (hi / lo) ** (j / max(1, n - 1)) for j in range(n)]


def layer_weights(cfg, key, i, held=None, dtype=jnp.bfloat16, kind=None):
    """Layer ``i``'s leaves, arranged as the program's block tree, with
    the routed experts ``held`` = (first, count) (default the
    configuration's ``experts_held``).  Matrices are normal draws of
    ``initializer_std`` cast to ``dtype``, zero-centred gains noughts,
    the gated norm's gain ones, with the departures of
    ``assumed.seeded_weights``: ``dt_bias`` = softplus^-1(1) and
    ``A_log`` = log(ln 2 / half-life), so that at a zero input value
    head j halves its state every ``half_lives(cfg)[j]`` tokens; the
    QK-norm's gains all ``qk_norm_gain``.  An
    expert's draws depend on its own id alone, so a share's experts are
    the uncut layer's.  ``kind``: ``layer_kind(cfg, i)``, given where
    ``i`` is traced."""
    z = _sizes(cfg)
    kind = kind or layer_kind(cfg, i)
    std = float(_seeded(cfg)["initializer_std"])
    first, count = held or cfg["experts_held"]
    key = jax.random.fold_in(key, 1 + i)

    def w(name, *shape, k=key):
        return (std * jax.random.normal(jax.random.fold_in(k, name), shape,
                                        jnp.float32)).astype(dtype)

    def experts(name, *shape):
        return jax.vmap(lambda e: w(name, *shape, k=jax.random.fold_in(
            key, 1000 + e)))(first + jnp.arange(count))

    d = z["d"]
    zero = jnp.zeros((d,), jnp.float32)
    out = {
        "ln1": {"gamma": zero}, "ln2": {"gamma": zero},
        "moe": {"router": w(4, d, z["e"]),
                "w_gate": experts(5, d, z["f"]),
                "w_up": experts(6, d, z["f"]),
                "w_down": experts(7, z["f"], d)},
        "shared": {"w_gate": w(8, d, z["fs"]), "w_up": w(9, d, z["fs"]),
                   "w_down": w(10, z["fs"], d)},
        "shared_gate": w(11, d, 1),
    }
    if kind == "linear_attention":
        wide = 2 * z["hk"] * z["dk"] + z["hv"] * z["dv"]
        lives = jnp.asarray(half_lives(cfg), jnp.float32)
        out["mha"] = {
            "w_qkvz": w(0, d, wide + z["hv"] * z["dv"]),
            "w_ba": w(1, d, 2 * z["hv"]),
            "conv_w": w(2, z["conv"], wide),
            "a_log": jnp.log(math.log(2.0) / lives),
            "dt_bias": jnp.full((z["hv"],), math.log(math.e - 1.0),
                                jnp.float32),
            "norm": jnp.ones((z["dv"],), jnp.float32),
            "wo": w(3, z["hv"] * z["dv"], d)}
    else:
        dq, dkv = z["heads"] * z["hd"], z["kv"] * z["hd"]
        # the QK-norm's gains, stored zero-centred: x̂ (1 + w) = x̂ gain
        qk_gain = jnp.full((z["hd"],), float(_seeded(cfg)["qk_norm_gain"])
                           - 1.0, jnp.float32)
        out["mha"] = {
            "wq": w(0, d, dq), "wk": w(1, d, dkv), "wv": w(2, d, dkv),
            "wo": w(3, dq, d), "wgate": w(12, d, dq),
            "q_norm_centered": qk_gain, "k_norm_centered": qk_gain}
    return out


def outer_weights(cfg, key, which, dtype=jnp.bfloat16):
    """``which``: "embed" -> the [vocab, d] table; "head" -> the untied
    [d, vocab] head; "norm" -> the final norm's zero-centred gain."""
    z = _sizes(cfg)
    std = float(_seeded(cfg)["initializer_std"])
    if which == "norm":
        return jnp.zeros((z["d"],), jnp.float32)
    shape = (z["vocab"], z["d"]) if which == "embed" else (z["d"],
                                                           z["vocab"])
    fold = {"embed": 1001, "head": 1002}[which]
    if which == "embed":
        std = float(_seeded(cfg).get("embedding_std", std))
    return (std * jax.random.normal(jax.random.fold_in(key, fold), shape,
                                    jnp.float32)).astype(dtype)


def _f32(a):
    return a.astype(jnp.float32)


# ----------------------------------------------------------------- forward
def _rms(x, g, eps):
    """x̂ (1 + g): the zero-centred RMSNorm."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * (1.0 + g)


def _rotate(x, positions, theta, rot):
    """x [T, H, D]: the first ``rot`` features turned in pairs (2i,
    2i+1) by position * theta^(-i / (rot/2)); the rest as they are."""
    half = rot // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    turned = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                       axis=-1).reshape(x.shape[:-1] + (rot,))
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def route(cfg, h, router):
    """Gates [t, E]: the softmax over all experts (float32) of the
    ``num_experts_per_tok`` largest divided by their sum, nought
    elsewhere (ties towards the lower expert id: a stable sort)."""
    z = _sizes(cfg)
    probs = jax.nn.softmax(jnp.einsum("td,de->te", h, _f32(router),
                                      precision=_HI), axis=-1)
    order = jnp.argsort(-probs, axis=-1, stable=True)[:, :z["k"]]
    top = jnp.take_along_axis(probs, order, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], order].set(top)


def make_ffn(cfg, t, precision="f32", held=None, shared=True):
    """``ffn(h [t, d], lw) -> [t, d]``: the routed experts ``held`` =
    (first, count)'s part of the mixture (``lw``'s expert leaves hold
    exactly those) plus, with ``shared``, the gated shared expert."""
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
                y = y + jax.nn.sigmoid(mm(hb, _f32(lw["shared_gate"]),
                                          "td,do->to")) * gated(
                    hb, sh["w_gate"], sh["w_up"], sh["w_down"])
            return y

        return jax.lax.map(tokens, jnp.arange(0, t, tb)).reshape(t, -1)

    return ffn


def _recurrence(q, k, v, beta, g, state_reset=0, no_delta=False,
                state_bf16=False, n=None):
    """The delta rule token by token: q / k [t, Hv, dk], v [t, Hv, dv],
    beta / g [t, Hv] -> ``(o [t, Hv, dv], S [Hv, dk, dv])``; S float32
    from zeros, as the first ``n`` tokens (all where None) leave it."""
    t, hv, dk = k.shape
    n = t if n is None else n
    s0 = jnp.zeros((hv, dk, v.shape[-1]), jnp.float32)

    def one(s, xs):
        p, qt, kt, vt, bt, gt = xs
        was = s
        if state_reset:
            s = jnp.where(p % state_reset == 0, 0.0, s)
        s = s * jnp.exp(gt)[:, None, None]
        if no_delta:
            write = vt * bt[:, None]
        else:
            read = jnp.einsum("hk,hkv->hv", kt, s, precision=_HI)
            write = (vt - read) * bt[:, None]
        s = s + kt[:, :, None] * write[:, None, :]
        if state_bf16:
            # an explicit rounding: XLA may drop a convert pair to
            # bfloat16 and back (excess precision), and on the TPU did
            s = jax.lax.reduce_precision(s, exponent_bits=8,
                                         mantissa_bits=7)
        o = jnp.einsum("hk,hkv->hv", qt, s, precision=_HI)
        return jnp.where(p < n, s, was), o

    s, o = jax.lax.scan(one, s0, (jnp.arange(t), q, k, v, beta, g))
    return o, s


def make_layer(cfg, t, kind, precision="f32", held=None, state_reset=0,
               conv_dropped=0, no_delta=False, gate_off=False,
               rope_full=False, state_bf16=False, keep_state=False):
    """``layer(x [t, d], lw, decode_from) -> x`` for one layer's leaves
    ``lw`` of ``kind`` ("linear_attention" | "full_attention");
    ``decode_from``: the first position the program decodes token by
    token (read by ``conv_dropped`` alone).  Trailing padding is never
    attended (it lies after every real query).  With ``keep_state``,
    ``layer(x, lw, decode_from, n) -> (x, (S, tail))`` for a linear
    layer: S and the conv's tail (the pre-conv inputs of positions
    ``n - conv + 1 .. n - 1``, oldest first) as the first ``n`` tokens
    leave them."""
    z = _sizes(cfg)
    mm = PRECISIONS[precision]
    g = z["heads"] // z["kv"]
    qb = min(QUERY_BLOCK, t)
    if t % qb:
        raise ValueError("sequence length %d must divide into blocks" % t)
    pos = jnp.arange(t)
    ffn = make_ffn(cfg, t, precision, held)
    rot = z["hd"] if rope_full else z["rot"]

    def linear(h, lw, decode_from, n=t):
        a = lw["mha"]
        hk, hv, dk, dv = z["hk"], z["hv"], z["dk"], z["dv"]
        wide = 2 * hk * dk + hv * dv
        qkvz = mm(h, _f32(a["w_qkvz"]), "td,de->te")
        ba = mm(h, _f32(a["w_ba"]), "td,de->te")
        pre, zg = qkvz[:, :wide], qkvz[:, wide:].reshape(t, hv, dv)
        taps = _f32(a["conv_w"])                       # [conv, wide]
        conv = jnp.zeros_like(pre)
        for j in range(z["conv"]):                     # j tokens back
            back = jnp.pad(pre, ((j, 0), (0, 0)))[:t]
            if conv_dropped and j:
                # the tail a pass starts with, and a decoded token's,
                # dropped: only the token itself and what its own pass
                # saw before it reach the conv
                kept = (pos - j >= (pos // conv_dropped) * conv_dropped) \
                    & (pos < decode_from)
                back = jnp.where(kept[:, None], back, 0.0)
            conv = conv + back * taps[z["conv"] - 1 - j]
        conv = jax.nn.silu(conv)
        q = conv[:, :hk * dk].reshape(t, hk, dk)
        k = conv[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
        v = conv[:, 2 * hk * dk:].reshape(t, hv, dv)

        def l2(u):
            u = u * jax.lax.rsqrt(jnp.sum(u * u, axis=-1, keepdims=True)
                                  + L2_EPS)
            return jnp.repeat(u, hv // hk, axis=1)

        q, k = l2(q) / math.sqrt(dk), l2(k)
        beta = jax.nn.sigmoid(ba[:, :hv])
        gate = -jnp.exp(a["a_log"]) * jax.nn.softplus(ba[:, hv:]
                                                      + a["dt_bias"])
        o, s = _recurrence(q, k, v, beta, gate, state_reset, no_delta,
                           state_bf16, n)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + z["eps"]) * a["norm"] * jax.nn.silu(zg)
        tail = jax.lax.dynamic_slice_in_dim(
            jnp.pad(pre, ((z["conv"] - 1, 0), (0, 0))), n, z["conv"] - 1)
        return mm(o.reshape(t, hv * dv), _f32(a["wo"]), "te,ed->td"), \
            (s, tail)

    def attention(h, lw, decode_from, n=t):
        del decode_from, n
        a = lw["mha"]
        q = mm(h, _f32(a["wq"]), "td,de->te").reshape(t, z["heads"],
                                                      z["hd"])
        k = mm(h, _f32(a["wk"]), "td,de->te").reshape(t, z["kv"], z["hd"])
        v = mm(h, _f32(a["wv"]), "td,de->te").reshape(t, z["kv"], z["hd"])
        q = _rotate(_rms(q, a["q_norm_centered"], z["eps"]), pos,
                    z["theta"], rot)
        k = _rotate(_rms(k, a["k_norm_centered"], z["eps"]), pos,
                    z["theta"], rot)

        def make_block(ext):
            # queries [at, at + qb) against keys [0, ext): a key past
            # every query of the group is masked anyway
            kx, vx, px = k[:ext], v[:ext], pos[:ext]

            def block(at):
                qq = jax.lax.dynamic_slice_in_dim(q, at, qb).reshape(
                    qb, z["kv"], g, z["hd"])
                qpos = at + jnp.arange(qb)
                keep = px[None, :] <= qpos[:, None]
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
        if not gate_off:
            o = o * jax.nn.sigmoid(mm(h, _f32(a["wgate"]), "td,de->te"))
        return mm(o, _f32(a["wo"]), "te,ed->td"), None

    mixer = linear if kind == "linear_attention" else attention

    def layer(x, lw, decode_from, n=t):
        y, state = mixer(_rms(x, lw["ln1"]["gamma"], z["eps"]), lw,
                         decode_from, n)
        x = x + y
        x = x + ffn(_rms(x, lw["ln2"]["gamma"], z["eps"]), lw)
        return (x, state) if keep_state else x

    return layer


def pad_length(n):
    """Sequence lengths the layers are compiled for: multiples of
    ``TOKEN_BLOCK`` (a handful of programs whatever the answers'
    lengths); short ones, as the tests use, whole query blocks."""
    if n <= TOKEN_BLOCK:
        return -(-n // QUERY_BLOCK) * QUERY_BLOCK if n > QUERY_BLOCK \
            else max(16, n)
    return -(-n // TOKEN_BLOCK) * TOKEN_BLOCK


@functools.lru_cache(maxsize=None)
def _programs(cfg_json, held):
    """One jitted weight maker a layer kind and one compiled layer a
    (kind, length, precision, controls), kept across calls: the
    controls and the answers of a run share them."""
    cfg = json.loads(cfg_json)
    makers = {kind: jax.jit(functools.partial(
        lambda k, i, kind: layer_weights(cfg, k, i, held, kind=kind),
        kind=kind)) for kind in ("linear_attention", "full_attention")}

    @functools.lru_cache(maxsize=None)
    def layer(kind, t, precision, controls):
        return jax.jit(make_layer(cfg, t, kind, precision, held,
                                  **dict(controls)))

    return makers, layer


def forward_logits(cfg, seed, sequences, positions, precision="f32",
                   held=None, **controls):
    """Logits [len(positions[i]), vocab] of each token sequence at the
    given positions (the first of them is where decoding starts).  One
    layer's weights at a time, each applied to every sequence before the
    next is made.  ``controls``: keyword arguments of ``make_layer``
    (``CONTROL_KEYS``)."""
    z = _sizes(cfg)
    key = seed_key(seed)
    mm = PRECISIONS[precision]
    table = jax.jit(lambda: outer_weights(cfg, key, "embed"))()
    xs = []
    for seq in sequences:
        toks = np.zeros((pad_length(len(seq)),), np.int32)
        toks[:len(seq)] = seq
        xs.append(_f32(table[jnp.asarray(toks)]))
    del table
    makers, layer = _programs(json.dumps(cfg, sort_keys=True),
                              None if held is None else tuple(held))
    controls = tuple(sorted(controls.items()))
    for i in range(cfg["num_hidden_layers"]):
        kind = layer_kind(cfg, i)
        lw = makers[kind](key, i)
        for n, (x, where) in enumerate(zip(xs, positions)):
            xs[n] = layer(kind, x.shape[0], precision, controls)(
                x, lw, jnp.int32(where[0] if len(where)
                                 else len(sequences[n])))
        del lw
    norm = outer_weights(cfg, key, "norm")
    head = _f32(jax.jit(lambda: outer_weights(cfg, key, "head"))())
    out = []
    for x, where in zip(xs, positions):
        rows = _rms(x[jnp.asarray(np.asarray(where, np.int32))], norm,
                    z["eps"])
        out.append(mm(rows, head, "td,dv->tv"))
    return out


def final_states(cfg, seed, sequence, decode_from, precision="f32",
                 held=None, **controls):
    """Each linear layer's ``(S [Hv, dk, dv], tail [conv - 1,
    channels])``, float32, as the token ``sequence`` leaves them
    (``decode_from``: ``make_layer``'s; ``controls``: keyword arguments
    of ``make_layer``, ``CONTROL_KEYS``).  The layers after the last
    linear one are not run."""
    key = seed_key(seed)
    n = len(sequence)
    toks = np.zeros((pad_length(n),), np.int32)
    toks[:n] = sequence
    x = _f32(jax.jit(lambda: outer_weights(cfg, key, "embed"))()[
        jnp.asarray(toks)])
    makers, layer = _programs(json.dumps(cfg, sort_keys=True),
                              None if held is None else tuple(held))
    controls = tuple(sorted(dict(controls, keep_state=True).items()))
    kinds = [layer_kind(cfg, i) for i in range(cfg["num_hidden_layers"])]
    last = max(i for i, kind in enumerate(kinds)
               if kind == "linear_attention")
    states = []
    for i in range(last + 1):
        lw = makers[kinds[i]](key, i)
        x, state = layer(kinds[i], x.shape[0], precision, controls)(
            x, lw, jnp.int32(decode_from), jnp.int32(n))
        if state is not None:
            states.append(state)
        del lw
    return states


def state_gaps(program, reference):
    """How far the program's states (a list of ``(S, tail)`` a linear
    layer, as :func:`final_states` gives them) lie from the reference's,
    a linear layer at a time: the widest relative Frobenius distance of
    one value head's S, or of the layer's conv tail, to the
    reference's.  ``[inf]`` where the lists differ in length."""
    if len(program) != len(reference) or not program:
        return [math.inf]
    gaps = []
    for (s_p, c_p), (s_r, c_r) in zip(program, reference):
        s_p, s_r = (np.asarray(a, np.float64) for a in (s_p, s_r))
        heads = np.sqrt(np.sum((s_p - s_r) ** 2, axis=(1, 2))
                        / np.maximum(np.sum(s_r ** 2, axis=(1, 2)), 1e-30))
        c_p, c_r = (np.asarray(a, np.float64) for a in (c_p, c_r))
        tail = np.sqrt(np.sum((c_p - c_r) ** 2)
                       / max(np.sum(c_r ** 2), 1e-30))
        gap = max(float(np.max(heads)), float(tail))
        gaps.append(gap if math.isfinite(gap) else math.inf)
    return gaps


def state_gap(program, reference):
    """:func:`state_gaps` of the FIRST linear layer: its input, the
    embedding, is the program's and the reference's bit for bit, so its
    gap is the state's own arithmetic (S kept in float32, the chunked
    form against the recurrence, the tail handed on) and not the drift
    of the residual stream through the experts before a deeper layer
    (a routing choice that flips between the two moves a token's whole
    output there); any lengths that differ read inf."""
    gaps = state_gaps(program, reference)
    return math.inf if math.inf in gaps else gaps[0]


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
