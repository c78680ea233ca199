"""The plain reference: the GPT-2 block stack in straightforward
``jax.numpy`` and float32 — forward pass, next-token loss, gradients,
global-norm clip and AdamW.  No kernels, no cache, no batching tricks.

It imports nothing of the program and takes nothing the program made:
the weights come from :func:`make_weights` (one jitted call from the
seed; ``benchmarks/build.py`` copies that same tree INTO the program),
the tokens from ``benchmarks/traffic.py``.

Every matmul goes through one of the ``PRECISIONS``:

``f32``   float32 operands at ``jax.lax.Precision.HIGHEST`` — the
          reference proper.
``int8``  the control: both operands of every matmul (projections, MLP,
          head, q.k and p.v, forward AND backward) rounded to a
          per-tensor symmetric int8 grid first — the nearest precision
          below the bfloat16 compute the configurations state, the step
          that would tempt a later PR.  It must come out *not correct*.

Block leaves are stacked over the layer axis and the stack is scanned;
rows go through in blocks (``rows_per_block``) so the float32 logits and
the T x T scores of the plain attention fit beside the weights.

Departures of the program from the published models, which the
reference follows because it is the program that is measured:
LayerNorm epsilon 1e-6 (published 1e-5) and, for Cerebras-GPT, the tanh
GELU (published erf ``gelu``); both are keys of the configuration files
and listed in their ``reduced``."""

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


# ----------------------------------------------------------------- weights
def weight_shapes(cfg):
    """name -> (shape, kind); kind: 'normal' | 'zeros' | 'ones'.  Block
    leaves carry the layer axis first."""
    d, f, n = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    shapes = {
        "wte": ((cfg["vocab_size"], d), "normal"),
        "wpe": ((cfg["n_positions"], d), "normal"),
        "lnf_g": ((d,), "ones"), "lnf_b": ((d,), "zeros"),
    }
    for name, shape in (("wq", (d, d)), ("wk", (d, d)), ("wv", (d, d)),
                        ("wo", (d, d)), ("w1", (d, f)), ("w2", (f, d))):
        shapes[name] = ((n,) + shape, "normal")
    for name, width in (("bq", d), ("bk", d), ("bv", d), ("bo", d),
                        ("b1", f), ("b2", d), ("ln1_b", d), ("ln2_b", d)):
        shapes[name] = ((n, width), "zeros")
    for name in ("ln1_g", "ln2_g"):
        shapes[name] = ((n, d), "ones")
    return shapes


def seed_key(seed):
    """A PRNG key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def weights_from_key(cfg, key):
    """The float32 weight tree from a PRNG key (pure, traceable): GPT-2's
    initializer (normal, ``initializer_range``) for matrices and tables,
    zeros for biases, ones for LayerNorm gains."""
    shapes = weight_shapes(cfg)
    std = float(cfg["initializer_range"])
    out = {}
    for i, name in enumerate(sorted(shapes)):
        shape, kind = shapes[name]
        if kind == "normal":
            out[name] = std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        else:
            out[name] = jnp.full(shape, float(kind == "ones"), jnp.float32)
    return out


def make_weights(cfg, seed):
    """The model's weights from ``seed``, on the device, in one jitted
    call."""
    return jax.jit(lambda key: weights_from_key(cfg, key))(seed_key(seed))


# --------------------------------------------------------------- precision
def _round_int8(x):
    """Per-tensor symmetric int8 grid, kept in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / scale) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _mm_int8(a, b, spec):
    return jnp.einsum(spec, _round_int8(a), _round_int8(b),
                      precision=HIGHEST)


def _mm_int8_fwd(a, b, spec):
    return _mm_int8(a, b, spec), (a, b)


def _mm_int8_bwd(spec, res, g):
    a, b = res
    ins, out = spec.split("->")
    sa, sb = ins.split(",")
    g, a, b = _round_int8(g), _round_int8(a), _round_int8(b)
    da = jnp.einsum("%s,%s->%s" % (out, sb, sa), g, b, precision=HIGHEST)
    db = jnp.einsum("%s,%s->%s" % (sa, out, sb), a, g, precision=HIGHEST)
    return da, db


_mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)


def _mm_f32(a, b, spec):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


PRECISIONS = {"f32": _mm_f32, "int8": _mm_int8}


# ----------------------------------------------------------------- forward
def _layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def _gelu(x, kind):
    if kind == "gelu_new":      # the tanh form
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    if kind == "gelu":          # the erf form
        return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))
    raise ValueError("activation_function %r" % (kind,))


def block_leaves(w):
    return {k: v for k, v in w.items()
            if k not in ("wte", "wpe", "lnf_g", "lnf_b")}


def make_block(cfg, b, t, precision="f32"):
    """``block(x [b, t, d], lw) -> x``: one pre-LN GPT-2 block, ``lw``
    one layer's leaves."""
    mm = PRECISIONS[precision]
    eps = float(cfg["layer_norm_epsilon"])
    act = cfg["activation_function"]
    heads = cfg["n_head"]
    hd = cfg["n_embd"] // heads
    causal = jnp.tril(jnp.ones((t, t), bool))

    def split(y):
        return y.reshape(b, t, heads, hd).transpose(0, 2, 1, 3)

    def block(x, lw):
        h = _layer_norm(x, lw["ln1_g"], lw["ln1_b"], eps)
        q = split(mm(h, lw["wq"], "btd,de->bte") + lw["bq"])
        k = split(mm(h, lw["wk"], "btd,de->bte") + lw["bk"])
        v = split(mm(h, lw["wv"], "btd,de->bte") + lw["bv"])
        s = mm(q, k, "bhqd,bhkd->bhqk") / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = mm(p, v, "bhqk,bhkd->bhqd")
        o = o.transpose(0, 2, 1, 3).reshape(b, t, heads * hd)
        x = x + mm(o, lw["wo"], "btd,de->bte") + lw["bo"]
        h = _layer_norm(x, lw["ln2_g"], lw["ln2_b"], eps)
        h = _gelu(mm(h, lw["w1"], "btd,df->btf") + lw["b1"], act)
        return x + mm(h, lw["w2"], "btf,fd->btd") + lw["b2"]

    return block


def embed(wte, wpe, tokens):
    return wte[tokens] + wpe[:tokens.shape[1]]


def head(x, wte, lnf_g, lnf_b, cfg, precision="f32"):
    """Final LayerNorm and the tied head: x [b, t, d] -> logits."""
    x = _layer_norm(x, lnf_g, lnf_b, float(cfg["layer_norm_epsilon"]))
    return PRECISIONS[precision](x, wte, "btd,vd->btv")


def nll_of_logits(logits, tokens):
    """Summed next-token negative log-likelihood."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.sum(jnp.take_along_axis(
        logp, tokens[:, 1:, None], axis=-1))


def forward(w, tokens, cfg, precision="f32"):
    """tokens [B, T] int -> logits [B, T, V] float32."""
    block = make_block(cfg, *tokens.shape, precision)
    x = embed(w["wte"], w["wpe"], tokens)
    x, _ = jax.lax.scan(lambda x, lw: (jax.checkpoint(block)(x, lw), None),
                        x, block_leaves(w))
    return head(x, w["wte"], w["lnf_g"], w["lnf_b"], cfg, precision)


def nll_sum(w, tokens, cfg, precision="f32"):
    """Summed next-token negative log-likelihood over rows [B, T]."""
    return nll_of_logits(forward(w, tokens, cfg, precision), tokens)


def add_grads(w, acc, tokens, cfg, precision="f32"):
    """``(nll_sum, acc + d nll_sum / d w)`` over rows [B, T], layer by
    layer: the forward pass keeps each layer's input, the backward pass
    takes one layer's vector-Jacobian product at a time and adds it into
    ``acc`` in place.  The same mathematics as ``jax.grad(nll_sum)``
    (benchmarks/tests checks it); written out so that no second copy of
    the gradient tree is ever alive, which is what lets weights,
    gradient and both Adam moments of a 700M-parameter stack share one
    chip with a row's activations."""
    layers = block_leaves(w)
    n_layer = layers["wq"].shape[0]
    block = make_block(cfg, *tokens.shape, precision)
    x0 = embed(w["wte"], w["wpe"], tokens)
    x_last, x_in = jax.lax.scan(lambda x, lw: (block(x, lw), x), x0, layers)
    loss, head_vjp = jax.vjp(
        lambda x, wte, g, b: nll_of_logits(
            head(x, wte, g, b, cfg, precision), tokens),
        x_last, w["wte"], w["lnf_g"], w["lnf_b"])
    dx, d_wte, d_g, d_b = head_vjp(jnp.ones((), loss.dtype))

    def back(i, carry):
        dx, acc_layers = carry
        at = n_layer - 1 - i
        _, vjp = jax.vjp(block, x_in[at],
                         {k: v[at] for k, v in layers.items()})
        dx, d_lw = vjp(dx)
        return dx, {k: acc_layers[k].at[at].add(d_lw[k])
                    for k in acc_layers}

    dx, acc_layers = jax.lax.fori_loop(0, n_layer, back,
                                       (dx, block_leaves(acc)))
    out = dict(acc_layers)
    out["lnf_g"], out["lnf_b"] = acc["lnf_g"] + d_g, acc["lnf_b"] + d_b
    out["wte"] = (acc["wte"] + d_wte).at[tokens].add(dx)
    out["wpe"] = acc["wpe"].at[:tokens.shape[1]].add(jnp.sum(dx, axis=0))
    return loss, out


# ---------------------------------------------------------------- training
def init_adam(w):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, w)
    return {"m": zeros, "v": jax.tree_util.tree_map(jnp.zeros_like, w),
            "t": jnp.zeros((), jnp.int32)}


def make_train_step(cfg, opt, rows_per_block, precision="f32"):
    """One optimizer step over rows [B, T]: the mean next-token loss,
    its gradient accumulated over blocks of rows, clipped to the global
    norm ``opt['clip_norm']``, then AdamW.  Returns
    ``(w, adam, loss_per_token)``; ``adam['m']`` after one step is
    (1 - beta1) x the gradient as the optimizer got it."""
    b1, b2 = float(opt["beta1"]), float(opt["beta2"])
    eps, lr = float(opt["epsilon"]), float(opt["learning_rate"])
    wd, clip = float(opt["weight_decay"]), float(opt["clip_norm"])
    no_decay = ("bq", "bk", "bv", "bo", "b1", "b2", "ln1_b", "ln2_b",
                "lnf_b")

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def add_block(w, acc, loss, rows):
        more, acc = add_grads(w, acc, rows, cfg, precision)
        return acc, loss + more

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def update(w, adam, grads, count):
        grads = jax.tree_util.tree_map(lambda g: g / count, grads)
        if clip:
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in
                                jax.tree_util.tree_leaves(grads)))
            scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        t_new = adam["t"] + 1
        tf = t_new.astype(jnp.float32)
        new_w, new_m, new_v = {}, {}, {}
        for name in w:
            g = grads[name]
            m = b1 * adam["m"][name] + (1.0 - b1) * g
            v = b2 * adam["v"][name] + (1.0 - b2) * g * g
            upd = (m / (1.0 - b1 ** tf)) / (
                jnp.sqrt(v / (1.0 - b2 ** tf)) + eps)
            decay = 0.0 if name in no_decay else wd
            new_w[name] = w[name] - lr * upd - lr * decay * w[name]
            new_m[name], new_v[name] = m, v
        return new_w, {"m": new_m, "v": new_v, "t": t_new}

    zeros = jax.jit(lambda w: jax.tree_util.tree_map(jnp.zeros_like, w))

    def step(w, adam, tokens):
        n_rows, t = tokens.shape
        acc, loss = zeros(w), jnp.zeros(())
        for at in range(0, n_rows, rows_per_block):
            acc, loss = add_block(w, acc, loss,
                                  tokens[at:at + rows_per_block])
        count = float(n_rows * (t - 1))
        w, adam = update(w, adam, acc, count)
        return w, adam, loss / count

    step.parts = (add_block, update)
    return step


# ------------------------------------------------------------------- norms
def leaf_norms(tree, cfg):
    """L2 norm of every leaf as the PROGRAM sees leaves: one number per
    layer for a stacked block leaf.  name -> float32 array (scalar or
    [n_layer])."""
    out = {}
    for name, x in tree.items():
        x = x.astype(jnp.float32)
        if name in ("wte", "wpe", "lnf_g", "lnf_b"):
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))
        else:
            out[name] = jnp.sqrt(jnp.sum(
                jnp.square(x.reshape(x.shape[0], -1)), axis=1))
    return out


def flat_norms(norms):
    """{'wq': [L]} -> {'h0.wq': x, ...} of python floats."""
    import numpy as np
    flat = {}
    for name, val in norms.items():
        val = np.asarray(val)
        if val.ndim == 0:
            flat[name] = float(val)
        else:
            for i, x in enumerate(val):
                flat["h%d.%s" % (i, name)] = float(x)
    return flat


# ----------------------------------------------------------------- serving
def make_logit_gaps(cfg, precision="f32"):
    """``gaps(w, tokens [1, T], probe [T]) -> (gap [T], argmax [T])``:
    at every position, how far the logit of ``probe``'s token lies below
    the best logit, and which token is best.  The whole prompt with its
    served tokens goes through the plain forward pass at once — no
    cache, no paging."""
    @jax.jit
    def gaps(w, tokens, probe):
        logits = forward(w, tokens, cfg, precision)[0]
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, probe[:, None], axis=-1)[:, 0]
        return best - got, jnp.argmax(logits, axis=-1)

    return gaps
