"""The plain reference of the Keye-VL-2.0 language model's block stack:
RMSNorm, GQA with per-head QK-norm and rotary positions, a
DeepSeek-Sparse-Attention indexer that picks the keys each query
attends, and a 128-expert top-8 gated-SiLU mixture in every layer — in
straightforward ``jax.numpy`` and float32.  No cache, no kernels, no
batching, no paging, no sorting of tokens by expert.

    h  = RMSNorm(x)
    I[t, s] = sum_j w[t, j] * ReLU(qI[t, j] . kI[s])        s <= t
    S_t = the topk largest I[t, s] (all while t < topk; ties towards
          the lower position)
    x += W_o softmax_{s in S_t}(q_t . k_s / sqrt(hd)) v_s   (GQA)
    h' = RMSNorm(x);  p = softmax(h' W_r);  the top_k largest
         renormalised to sum 1
    x += sum_e g_e W_down,e (SiLU(W_gate,e h') * W_up,e h')

It imports nothing of the program and takes nothing the program made.
The weights come from :func:`layer_weights` / :func:`outer_weights`
(from the seed, on the device, ONE LAYER AT A TIME: a layer in float32
is 2.5 GB, six at once would not fit beside anything); the benchmark's
build copies the same values into the program in bfloat16, the
configuration's parameter dtype, and the reference computes in float32
on those same bfloat16 values.  Each layer is applied to every checked
sequence before the next is made.  Index scores, the selection and the
attention go through in blocks of queries; the experts as one dense
pass over ALL experts a block of tokens (gate nought where a token did
not choose the expert).

Every matmul goes through one of ``reference.PRECISIONS`` (``f32``:
float32 at ``Precision.HIGHEST``; ``int8``: the control, both operands
on a per-tensor int8 grid).  ``select=False`` is the second control:
the indexer switched off, every key at or before the query attended.

What the published config does not give, and the convention taken
(also the configuration file's ``assumed``): per-head RMSNorm of q and k
before the rotation; the rotation on the indexer's q and k (over all 64
of their dims) and a LayerNorm on its key; head weights scaled by
``heads^-0.5 * head_dim^-0.5``; the indexer reads the block's normed
input; the rotation pairs features (2i, 2i+1), as the program's
``rope`` does (a fixed permutation of the published half-split, with
seeded weights the same model)."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import PRECISIONS, seed_key

#: queries a block of the attention holds, tokens a block of the experts
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048
#: groups of query blocks, each given only the keys up to its own end
#: (a long sequence's first quarter never sees the other three)
GROUPS = 4


# ----------------------------------------------------------------- weights
def _sizes(cfg):
    sa = cfg["sa_config"]
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        hi=sa["indexer_num_heads"], di=sa["indexer_head_dim"],
        topk=sa["topk"], e=cfg["num_experts"],
        k=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
        vocab=cfg["vocab_size"], eps=float(cfg["rms_norm_eps"]),
        theta=float(cfg["rope_theta"]))


def _seeded(cfg):
    return cfg["assumed"]["seeded_weights"]


def layer_weights(cfg, key, i, dtype=jnp.bfloat16):
    """Layer ``i``'s leaves, arranged as the program's block tree.
    Matrices are normal draws of ``initializer_std`` cast to ``dtype``;
    gains are float32: ones, but the q-norm's, which is
    ``q_norm_gain`` (it sets how peaked the attention is: with unit
    gains random keys make it nearly flat, and dropping 88% of them
    would move nothing — the configuration file says why)."""
    z = _sizes(cfg)
    std = float(_seeded(cfg)["initializer_std"])
    key = jax.random.fold_in(key, 1 + i)
    names = iter(range(64))

    def w(*shape):
        return (std * jax.random.normal(
            jax.random.fold_in(key, next(names)), shape, jnp.float32)
        ).astype(dtype)

    def ones(n, gain=1.0):
        return jnp.full((n,), gain, jnp.float32)

    d, dq, dkv = z["d"], z["heads"] * z["hd"], z["kv"] * z["hd"]
    return {
        "ln1": {"gamma": ones(d)}, "ln2": {"gamma": ones(d)},
        "mha": {
            "wq": w(d, dq), "wk": w(d, dkv), "wv": w(d, dkv),
            "wo": w(dq, d),
            "q_norm": ones(z["hd"], float(_seeded(cfg)["q_norm_gain"])),
            "k_norm": ones(z["hd"]),
            "indexer": {
                "wq": w(d, z["hi"] * z["di"]), "wk": w(d, z["di"]),
                "ww": w(d, z["hi"]),
                "k_ln": {"gamma": ones(z["di"]),
                         "beta": jnp.zeros((z["di"],), jnp.float32)}}},
        "moe": {"router": w(d, z["e"]) * jnp.asarray(
                    _seeded(cfg).get("router_gain", 1.0), dtype),
                "w_gate": w(z["e"], d, z["f"]),
                "w_up": w(z["e"], d, z["f"]),
                "w_down": w(z["e"], z["f"], d)},
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


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


# ----------------------------------------------------------------- forward
def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g


def _layer_norm(x, g, b, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


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


def select_keys(scores, valid, topk):
    """bool [Q, T]: per row the ``topk`` largest valid scores, ties
    towards the lower position (a full sort finds the k-th value)."""
    s = jnp.where(valid, jnp.where(scores == 0.0, 0.0, scores), -jnp.inf)
    n_valid = jnp.sum(valid, axis=-1)
    k = jnp.minimum(n_valid, topk)
    ranked = jnp.sort(s, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(ranked, jnp.maximum(k - 1, 0)[:, None],
                              axis=-1)
    above, equal = s > kth, s == kth
    need = k - jnp.sum(above, axis=-1)
    first = jnp.cumsum(equal, axis=-1) <= need[:, None]
    return valid & (above | (equal & first))


def make_layer(cfg, t, precision="f32", select=True, held=None,
               sets=False):
    """``layer(x [t, d], lw) -> (x, keys attended per query [t])`` for
    one layer's float32 leaves ``lw``; trailing padding is never
    attended (it lies after every real query).  ``held`` = (first,
    count): only those experts' part of the mixture is added (the
    chip's share; default all).  ``sets`` (tests): the second result is
    the selection itself, bool [t, t]."""
    z = _sizes(cfg)
    mm = PRECISIONS[precision]
    g = z["heads"] // z["kv"]
    qb = min(QUERY_BLOCK, t)
    tb = min(TOKEN_BLOCK, t)
    if t % qb or t % tb:
        raise ValueError("sequence length %d must divide into blocks" % t)
    first, count = held or (0, z["e"])
    pos = jnp.arange(t)

    def attention(h, lw):
        a = lw["mha"]
        q = mm(h, a["wq"], "td,de->te").reshape(t, z["heads"], z["hd"])
        k = mm(h, a["wk"], "td,de->te").reshape(t, z["kv"], z["hd"])
        v = mm(h, a["wv"], "td,de->te").reshape(t, z["kv"], z["hd"])
        q = _rotate(_rms(q, a["q_norm"], z["eps"]), pos, z["theta"])
        k = _rotate(_rms(k, a["k_norm"], z["eps"]), pos, z["theta"])
        ix = a["indexer"]
        qi = mm(h, ix["wq"], "td,de->te").reshape(t, z["hi"], z["di"])
        ki = _layer_norm(mm(h, ix["wk"], "td,de->te"),
                         ix["k_ln"]["gamma"], ix["k_ln"]["beta"])
        qi = _rotate(qi, pos, z["theta"])
        ki = _rotate(ki[:, None], pos, z["theta"])[:, 0]
        wi = mm(h, ix["ww"], "td,dj->tj") \
            * (z["hi"] ** -0.5 * z["di"] ** -0.5)

        def make_block(ext):
            # queries [at, at + qb) against keys [0, ext): a key past
            # every query of the group is masked anyway, so a group of
            # query blocks is given only the keys up to its own end
            kx, vx, kix, px = k[:ext], v[:ext], ki[:ext], pos[:ext]

            def block(at):
                sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                                       start_index=at, slice_size=qb)
                qpos = at + jnp.arange(qb)
                valid = px[None, :] <= qpos[:, None]
                if select:
                    s = jax.nn.relu(mm(sl(qi), kix, "qjd,sd->qjs"))
                    scores = jnp.einsum(
                        "qjs,qj->qs", s, sl(wi),
                        precision=jax.lax.Precision.HIGHEST)
                    keep = select_keys(scores, valid, z["topk"])
                else:
                    keep = valid
                qq = sl(q).reshape(qb, z["kv"], g, z["hd"])
                att = mm(qq, kx, "qkgd,skd->kgqs") / math.sqrt(z["hd"])
                att = jnp.where(keep[None, None], att, -jnp.inf)
                p = jax.nn.softmax(att, axis=-1)
                o = mm(p, vx, "kgqs,skd->qkgd")
                kept = jnp.pad(keep, ((0, 0), (0, t - ext))) if sets \
                    else jnp.sum(keep, axis=-1)
                return o.reshape(qb, z["heads"] * z["hd"]), kept

            return block

        n_blocks = t // qb
        groups = GROUPS if n_blocks >= 4 * GROUPS else 1
        outs, kepts = [], []
        for gi in range(groups):
            lo = gi * n_blocks // groups
            hi = (gi + 1) * n_blocks // groups
            o, kept = jax.lax.map(make_block(hi * qb),
                                  jnp.arange(lo, hi) * qb)
            outs.append(o)
            kepts.append(kept)
        o, n_kept = jnp.concatenate(outs), jnp.concatenate(kepts)
        return (mm(o.reshape(t, -1), a["wo"], "te,ed->td"),
                n_kept.reshape((t, t) if sets else (t,)))

    def experts(h, lw):
        m = lw["moe"]
        probs = jax.nn.softmax(jnp.einsum(
            "td,de->te", h, m["router"],
            precision=jax.lax.Precision.HIGHEST), axis=-1)
        # the k largest, ties towards the lower expert id (stable sort)
        order = jnp.argsort(-probs, axis=-1, stable=True)[:, :z["k"]]
        top = jnp.take_along_axis(probs, order, axis=-1)
        top = top / jnp.sum(top, axis=-1, keepdims=True)
        gates = jnp.zeros_like(probs).at[
            jnp.arange(t)[:, None], order].set(top)          # [t, E]

        def tokens(at):
            hb = jax.lax.dynamic_slice_in_dim(h, at, tb)
            gb = jax.lax.dynamic_slice_in_dim(gates, at, tb)

            def one(y, e):
                up = mm(hb, m["w_up"][e], "td,df->tf")
                gate = mm(hb, m["w_gate"][e], "td,df->tf")
                out = mm(jax.nn.silu(gate) * up, m["w_down"][e],
                         "tf,fd->td")
                return y + gb[:, e, None] * out, None

            return jax.lax.scan(one, jnp.zeros_like(hb),
                                first + jnp.arange(count))[0]

        return jax.lax.map(tokens, jnp.arange(0, t, tb)).reshape(t, -1)

    def layer(x, lw):
        a, n_kept = attention(_rms(x, lw["ln1"]["gamma"], z["eps"]), lw)
        x = x + a
        return x + experts(_rms(x, lw["ln2"]["gamma"], z["eps"]), lw), \
            n_kept

    return layer


def pad_length(n):
    """Sequence lengths the layers are compiled for: multiples of
    ``TOKEN_BLOCK`` (a handful of programs whatever the answers'
    lengths); short ones, as the tests use, multiples of 16."""
    if n <= TOKEN_BLOCK:
        return -(-n // 16) * 16 if n > QUERY_BLOCK else max(16, n)
    return -(-n // TOKEN_BLOCK) * TOKEN_BLOCK


def forward_logits(cfg, seed, sequences, positions, precision="f32",
                   select=True, held=None, sets=False):
    """Logits [len(positions[i]), vocab] of each token sequence at the
    given positions, and per sequence and layer how many keys each
    query attended (``sets``: which).  One layer's weights at a time,
    each applied to every sequence before the next is made."""
    z = _sizes(cfg)
    key = seed_key(seed)
    n_layers = cfg["num_hidden_layers"]

    def made(fn):
        return jax.jit(lambda: _f32(fn()))()

    table = made(lambda: outer_weights(cfg, key, "embed"))
    xs, lens = [], []
    for seq in sequences:
        t = pad_length(len(seq))
        toks = np.zeros((t,), np.int32)
        toks[:len(seq)] = seq
        xs.append(table[jnp.asarray(toks)])
        lens.append(len(seq))
    del table
    kept = [[] for _ in sequences]
    layer_maker = jax.jit(lambda k, i: _f32(layer_weights(cfg, k, i)))
    layers = {}                 # one compiled layer a padded length
    for i in range(n_layers):
        lw = layer_maker(key, i)
        for n, x in enumerate(xs):
            if x.shape[0] not in layers:
                layers[x.shape[0]] = jax.jit(make_layer(
                    cfg, x.shape[0], precision, select, held, sets))
            xs[n], n_kept = layers[x.shape[0]](x, lw)
            kept[n].append(np.asarray(n_kept)[:lens[n]] if not sets
                           else np.asarray(n_kept)[:lens[n], :lens[n]])
        del lw
    norm = outer_weights(cfg, key, "norm")
    head = made(lambda: outer_weights(cfg, key, "head"))
    mm = PRECISIONS[precision]
    out = []
    for x, where in zip(xs, positions):
        rows = _rms(x[jnp.asarray(np.asarray(where, np.int32))], norm,
                    z["eps"])
        out.append(mm(rows, head, "td,dv->tv"))
    return out, kept


def served(samples):
    """What a sample ``{"prompt", "result"}`` asks of the reference:
    the sequence fed (all but the last token) and the positions whose
    logits chose the served tokens."""
    return ([s["result"][:-1] for s in samples],
            [list(range(len(s["prompt"]) - 1, len(s["result"]) - 1))
             for s in samples])


def reference_logits(cfg, seed, samples):
    """The float32 reference's logits at every served position."""
    return forward_logits(cfg, seed, *served(samples))[0]


def logit_gaps(cfg, seed, samples, precision="f32", select=True,
               probe_precision=None, drop_expert=None, reference=None):
    """For each sample ``{"prompt", "result"}``: at every served
    position, how far the served token's reference logit lies below the
    reference's best.  ``probe_precision`` / ``select`` /
    ``drop_expert`` build a CONTROL: the tokens that a lesser reference
    (int8 operands; the selection switched off; expert ``drop_expert``
    of every layer left out, the rest held) puts first at those
    positions take the served tokens' place, and the float32 reference
    with the selection on and every expert judges them.  ``reference``:
    :func:`reference_logits` of the same samples, where the caller
    already has them.  Returns ``(widest gap, tokens compared)``."""
    logits = reference or reference_logits(cfg, seed, samples)
    if probe_precision is not None or not select \
            or drop_expert is not None:
        held = None
        if drop_expert is not None:
            if drop_expert != 0:
                raise ValueError("the experts held are one range: only "
                                 "the first can be left out")
            held = (1, cfg["num_experts"] - 1)
        lesser, _ = forward_logits(cfg, seed, *served(samples),
                                   precision=probe_precision or "f32",
                                   select=select, held=held)
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
