"""The plain reference of Brumby-14B-Base (``model_type`` ``brumby``,
Manifest AI: Qwen3-14B retrained with its attention replaced by POWER
RETENTION, arXiv:2507.04239): pre-norm blocks of RMSNorm, a degree-2
retention layer with grouped heads, QK-norm and rotary positions, and a
dense gated-SiLU MLP — in straightforward ``jax.numpy`` and float32,
and in the QUADRATIC form:

    h    = RMSNorm(x)                                  gain [5120], eps 1e-6
    q_t  = rope(RMSNorm_head(W_q h_t))   [40, 128]     k_t likewise [8, 128]
    v_t  = W_v h_t                       [8, 128]
    log g_t = logsigmoid(W_g h_t + b_g)  [8]           one gate a KV head
    for query head i of KV head j = i // 5 and every s <= t:
        a_ts = exp(sum_{r=s+1..t} log g_r[j]) * ((q_t[i] . k_s[j]) / sqrt(128))^2
        y_t[i] = sum_s a_ts v_s[j] / (sum_s a_ts + eps)
    x'  = x + W_o concat_i y_t[i]
    x'' = x' + W_down (silu(W_gate n) * (W_up n)),     n = RMSNorm(x')
    logits = RMSNorm(x_last) W_head                    the head untied

No state, no ``phi``, no chunks, no cache, no kernels, no batching: it
shares no formulation with the program, which serves the layer as a
recurrence over a fixed-size state (``veles_tpu/ops/retention.py``).
The sums over keys go through in blocks of queries, each against the
keys up to its own group's end, so that a 17k-token answer fits.

It imports nothing of the program and takes nothing the program made.
The weights come from :func:`layer_weights` / :func:`outer_weights`
(from the seed, on the device, ONE LAYER AT A TIME, in bfloat16 — the
configuration's parameter dtype — and upcast to float32 one matrix at a
time where a matmul reads it); the benchmark's build copies the same
values into the program.  Each layer is applied to every checked
sequence before the next is made.

Every matmul goes through one of ``reference.PRECISIONS`` (``f32``:
float32 at ``Precision.HIGHEST``; ``int8``: the control, both operands
on a per-tensor int8 grid).  Three more controls switch a mechanism
off: ``state_reset`` = n (nothing before the last multiple of n at or
before the query is remembered: what a program that dropped the state
between its passes of n tokens would compute), ``gate_off`` (g = 1: no
forgetting), ``softmax_attention`` (the parent family's ``exp(q . k /
sqrt d)`` in place of the squared product, the gates and the
normalisation kept).

What the published config does not give, and the convention taken
(also the configuration file's ``assumed``): degree 2; one
``logsigmoid`` gate a KV head from the block's normed input, with a
bias; the normalisation by the decayed sum of weights + ``eps`` 1e-6;
the scale 1/sqrt(128) inside the power; QK-norm (gain of 128,
``rms_norm_eps``) and rotary positions over all 128 dims kept from the
Qwen-3 parent; the rotation pairs features (2i, 2i+1), as the
program's ``rope`` does (a fixed permutation of the published
half-split, with seeded weights the same model)."""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import PRECISIONS, seed_key

#: queries a block of the retention holds (40 heads x 64 x 17k keys of
#: float32 weights are 178 MB), tokens a block of the MLP
QUERY_BLOCK = 64
TOKEN_BLOCK = 2048
#: groups of query blocks, each given only the keys up to its own end
GROUPS = 4
#: added to the decayed sum of weights before the division
EPS = 1e-6

#: the controls of ``logit_gap``: keyword arguments of ``make_layer``
CONTROL_KEYS = ("state_reset", "gate_off", "softmax_attention")


# ----------------------------------------------------------------- weights
def _sizes(cfg):
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        f=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]))


def _seeded(cfg):
    return cfg["assumed"]["seeded_weights"]


def half_lives(cfg):
    """Tokens after which a KV head's gate has halved a key's weight,
    at a zero gate input: log-spaced over the heads between
    ``gate_half_life``'s ends."""
    lo, hi = map(float, _seeded(cfg)["gate_half_life"])
    n = cfg["num_key_value_heads"]
    return [lo * (hi / lo) ** (j / max(1, n - 1)) for j in range(n)]


def layer_weights(cfg, key, i, dtype=jnp.bfloat16):
    """Layer ``i``'s leaves, arranged as the program's block tree.
    Matrices are normal draws of ``initializer_std`` cast to ``dtype``,
    the norm gains ones in float32, with the departures of
    ``assumed.seeded_weights``: the gate's bias puts KV head j's
    half-life at ``half_lives(cfg)[j]`` (g = 2^(-1 / half-life) at a
    zero input), and the rows of W_o that read KV head j's query heads
    are scaled by ``o_gain x sqrt(half-life_j / half-life_0)`` — a head
    that averages over a thousand keys hands on a thousandth of the
    variance of one that averages over one, and a model in which only
    the short heads are heard would not notice its state."""
    z = _sizes(cfg)
    sw = _seeded(cfg)
    std = float(sw["initializer_std"])
    key = jax.random.fold_in(key, 1 + i)

    def w(name, *shape):
        return (std * jax.random.normal(jax.random.fold_in(key, name), shape,
                                        jnp.float32)).astype(dtype)

    d, dq, dkv = z["d"], z["heads"] * z["hd"], z["kv"] * z["hd"]
    lives = jnp.asarray(half_lives(cfg), jnp.float32)
    gate = jnp.exp2(-1.0 / lives)
    heard = float(sw.get("o_gain", 1.0)) * jnp.sqrt(lives / lives[0])
    rows = jnp.repeat(heard, dq // z["kv"])              # [dq]
    return {
        "ln1": {"gamma": jnp.ones((d,), jnp.float32)},
        "ln2": {"gamma": jnp.ones((d,), jnp.float32)},
        "mha": {"wq": w(0, d, dq), "wk": w(1, d, dkv), "wv": w(2, d, dkv),
                "wo": (w(3, dq, d).astype(jnp.float32)
                       * rows[:, None]).astype(dtype),
                "q_norm": jnp.ones((z["hd"],), jnp.float32),
                "k_norm": jnp.ones((z["hd"],), jnp.float32),
                "wg": w(4, d, z["kv"]) * jnp.asarray(
                    sw.get("gate_gain", 1.0), dtype),
                "bg": jnp.log(gate / (1.0 - gate))},
        "ffn": {"w_gate": w(5, d, z["f"]), "w_up": w(6, d, z["f"]),
                "w_down": w(7, z["f"], d)},
    }


def outer_weights(cfg, key, which, dtype=jnp.bfloat16):
    """``which``: "embed" -> the [vocab, d] table; "head" -> the untied
    [d, vocab] head; "norm" -> the final RMSNorm's gain."""
    z = _sizes(cfg)
    std = float(_seeded(cfg)["initializer_std"])
    if which == "norm":
        return jnp.ones((z["d"],), jnp.float32)
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
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g


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


def make_layer(cfg, t, precision="f32", state_reset=0, gate_off=False,
               softmax_attention=False):
    """``layer(x [t, d], lw) -> x`` for one layer's leaves ``lw``;
    trailing padding is never attended (it lies after every real
    query)."""
    z = _sizes(cfg)
    mm = PRECISIONS[precision]
    g = z["heads"] // z["kv"]
    qb = min(QUERY_BLOCK, t)
    tb = min(TOKEN_BLOCK, t)
    if t % qb or t % tb:
        raise ValueError("sequence length %d must divide into blocks" % t)
    pos = jnp.arange(t)

    def retention(h, lw):
        a = lw["mha"]
        q = mm(h, _f32(a["wq"]), "td,de->te").reshape(t, z["heads"],
                                                      z["hd"])
        k = mm(h, _f32(a["wk"]), "td,de->te").reshape(t, z["kv"], z["hd"])
        v = mm(h, _f32(a["wv"]), "td,de->te").reshape(t, z["kv"], z["hd"])
        q = _rotate(_rms(q, a["q_norm"], z["eps"]), pos, z["theta"])
        k = _rotate(_rms(k, a["k_norm"], z["eps"]), pos, z["theta"])
        logg = jax.nn.log_sigmoid(
            mm(h, _f32(a["wg"]), "td,dk->tk") + a["bg"])   # [t, kv]
        if gate_off:
            logg = jnp.zeros_like(logg)
        # position s's entry: sum_{r<=s} log g_r; a_ts decays by the
        # difference of t's and s's
        cum = jnp.cumsum(logg, axis=0)

        def make_block(ext):
            # queries [at, at + qb) against keys [0, ext): a key past
            # every query of the group is masked anyway
            kx, vx, px, cx = k[:ext], v[:ext], pos[:ext], cum[:ext]

            def block(at):
                qq = jax.lax.dynamic_slice_in_dim(q, at, qb).reshape(
                    qb, z["kv"], g, z["hd"])
                cq = jax.lax.dynamic_slice_in_dim(cum, at, qb)
                qpos = at + jnp.arange(qb)
                keep = px[None, :] <= qpos[:, None]
                if state_reset:
                    keep = keep & (px[None, :] >= (
                        qpos[:, None] // state_reset) * state_reset)
                score = mm(qq, kx, "qkgd,skd->kgqs") / math.sqrt(z["hd"])
                decay = jnp.where(keep[None], (cq.T[:, :, None]
                                               - cx.T[:, None, :]),
                                  -jnp.inf)[:, None]          # [kv,1,q,s]
                if softmax_attention:
                    e = jnp.where(keep[None, None], score + decay,
                                  -jnp.inf)
                    w = jnp.exp(e - jnp.max(e, axis=-1, keepdims=True))
                else:
                    w = jnp.square(score) * jnp.exp(decay)
                num = mm(w, vx, "kgqs,skd->qkgd")
                den = jnp.sum(w, axis=-1).transpose(2, 0, 1)  # [q,kv,g]
                return (num / (den[..., None] + EPS)).reshape(
                    qb, z["heads"] * z["hd"])

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

    def mlp(n, lw):
        f = lw["ffn"]

        def tokens(at):
            nb = jax.lax.dynamic_slice_in_dim(n, at, tb)
            up = mm(nb, _f32(f["w_up"]), "td,df->tf")
            gate = mm(nb, _f32(f["w_gate"]), "td,df->tf")
            return mm(jax.nn.silu(gate) * up, _f32(f["w_down"]),
                      "tf,fd->td")

        return jax.lax.map(tokens, jnp.arange(0, t, tb)).reshape(t, -1)

    def layer(x, lw):
        x = x + retention(_rms(x, lw["ln1"]["gamma"], z["eps"]), lw)
        return x + mlp(_rms(x, lw["ln2"]["gamma"], z["eps"]), lw)

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
                   **controls):
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
    del table
    layer_maker = jax.jit(lambda k, i: layer_weights(cfg, k, i))
    layers = {}                 # one compiled layer a length
    for i in range(cfg["num_hidden_layers"]):
        lw = layer_maker(key, i)
        for n, x in enumerate(xs):
            at = x.shape[0]
            if at not in layers:
                layers[at] = jax.jit(make_layer(cfg, at, precision,
                                                **controls))
            xs[n] = layers[at](x, lw)
        del lw
    norm = outer_weights(cfg, key, "norm")
    head = _f32(jax.jit(lambda: outer_weights(cfg, key, "head"))())
    out = []
    for x, where in zip(xs, positions):
        rows = _rms(x[jnp.asarray(np.asarray(where, np.int32))], norm,
                    z["eps"])
        out.append(mm(rows, head, "td,dv->tv"))
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
