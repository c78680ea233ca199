"""The gated delta rule (Yang, Kautz and Hatamizadeh, "Gated Delta
Networks: Improving Mamba2 with Delta Rule", arXiv:2412.06464): linear
attention whose state is WRITTEN, not only added to — each token first
decays the state by its gate, reads what the state already says for its
key, and writes back the β-scaled difference to its value:

    S_t = e^{g_t} S_{t-1} + β_t k_t (v_t - e^{g_t} S_{t-1}^T k_t)^T
    o_t = S_t^T q_t                              S: [dk, dv] a value head

The layer around it (the ``linear_attention`` layer of Qwen3-Next's
configuration): one projection of the normed input to q, k, v and an
output gate z, a second to β's and the gate's logits (one of each a value
head); ``[q; k; v]`` through a depthwise CAUSAL conv of ``conv`` taps
(no bias) and SiLU; q and k L2-normalised a head (q scaled by dk^-1/2),
each key head serving ``v_heads / k_heads`` value heads; β = σ(b), g =
-exp(A_log) softplus(a + dt_bias); the output RMS-normalised a head
(a gain of dv), times silu(z), then projected back.

A slot's serve-time state is FIXED-SIZE (:class:`DeltaNetState`): S,
float32, and the conv's TAIL — the last ``conv - 1`` pre-conv inputs,
which the next token's conv reads.  Three forms of one function live
here: :func:`deltanet_step` (one token a row, the recurrence;
``ops.pallas.deltanet`` is its kernel), :func:`delta_chunk` (a pass of T
tokens against an incoming state: a scan over chunks of ``CHUNK``
tokens in the WY / UT form, handing S on) and, from a zero state, the
whole-sequence form ``TransformerBlock.apply`` runs (the same scan).
The per-token recurrence is the benchmark's reference
(``benchmarks/reference_qwen3next.py``) and the tests' ground truth.

Inside a chunk with cumulative gates ``c`` (``c_i = sum_{r<=i} g_r``),
``Γ_ij = exp(c_i - c_j)`` and ``A = strictly_lower(diag(β) K K^T ⊙ Γ)``:
``T = (I + A)^-1`` (A is nilpotent, so T is the product of ``I +
(-A)^(2^m)``: log2(CHUNK) matmul pairs, no solve), ``U = T (β V)``,
``W = T (β K ⊙ e^c)``, the chunk's corrected values ``V' = U - W S``,
its outputs ``(Q ⊙ e^c) S + tril(Q K^T ⊙ Γ) V'`` and the state it hands
on ``e^{c_C} S + (K ⊙ e^{c_C - c})^T V'``.  Everything that touches the
state or the inverse is float32 at ``Precision.HIGHEST``: an error in T
or S is carried to every later token."""

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from veles_tpu.ops import attention

#: tokens a chunk of :func:`delta_chunk` holds: T costs log2(CHUNK) pairs
#: of [CHUNK, CHUNK] matmuls a chunk and a value head, and a pass is
#: T / CHUNK steps of a scan.  64 keeps the inverse under a tenth of a
#: chunk's work at dk 128 while a 2,048-token pass is 32 steps
CHUNK = 64

#: added to a head's sum of squares before q and k are L2-normalised
L2_EPS = 1e-6

#: None: the decode step of a batch of rows runs in the Pallas kernel on
#: the TPU and in :func:`deltanet_step`'s XLA form off it; True forces
#: the kernel (interpret mode off the TPU: the tests'), False the XLA form
KERNEL = None

_HI = jax.lax.Precision.HIGHEST


class DeltaNetState(NamedTuple):
    """A gated-delta-rule layer's serve-time state, fixed-size a slot
    (float32 whatever the cache dtype): ``s`` [B, Hv, dk, dv] and
    ``conv`` [B, conv - 1, channels], the conv's tail: the last
    pre-conv inputs of ``[q; k; v]``, oldest first."""

    s: jnp.ndarray
    conv: jnp.ndarray


#: the state's container, by the name every state module gives it
State = DeltaNetState


def channels(shape):
    """Width of ``[q; k; v]``: what the conv runs over."""
    return 2 * shape["k_heads"] * shape["k_dim"] \
        + shape["v_heads"] * shape["v_dim"]


def state_shapes(shape):
    """Leaf name -> shape a slot, as ``TransformerBlock.state_leaves``
    declares them; ``shape`` = {"k_heads", "v_heads", "k_dim", "v_dim",
    "conv"} (the block's ``deltanet``)."""
    return {"s": (shape["v_heads"], shape["k_dim"], shape["v_dim"]),
            "conv": (shape["conv"] - 1, channels(shape))}


def init_state(batch, shape):
    return DeltaNetState(**{name: jnp.zeros((batch,) + dims, jnp.float32)
                            for name, dims in state_shapes(shape).items()})


# --------------------------------------------------------- the recurrence
def deltanet_step(q, k, v, beta, g, s):
    """One token a row in XLA: q / k [B, Hv, dk] (a key head's already
    repeated over its value heads), v [B, Hv, dv], ``beta`` / ``g``
    [B, Hv] float32 -> ``(o [B, Hv, dv], s)``.  Float32 throughout."""
    f32 = jnp.float32
    s = s * jnp.exp(g.astype(f32))[..., None, None]
    k = k.astype(f32)
    read = jnp.einsum("bhk,bhkv->bhv", k, s, precision=_HI)
    delta = (v.astype(f32) - read) * beta.astype(f32)[..., None]
    s = s + k[..., :, None] * delta[..., None, :]
    return jnp.einsum("bhk,bhkv->bhv", q.astype(f32), s, precision=_HI), s


def rows_skipped():
    """Whether :func:`deltanet_step_rows` runs the kernel here, which
    skips inactive rows (the XLA form moves every row's state)."""
    from veles_tpu.ops import pallas
    return KERNEL if KERNEL is not None else \
        not pallas.autodetect_interpret(None)


def deltanet_step_rows(q, k, v, beta, g, s, active=None, interpret=None):
    """:func:`deltanet_step` for the paged batcher's tick: where the
    kernel runs (``KERNEL``), S is read and written in ONE trip through
    HBM, in place under the tick's donation, and a row whose ``active``
    flag is down is skipped (its state stays, its output is nought);
    the XLA form keeps an inactive row's state by a select."""
    b = q.shape[0]
    if active is None:
        active = jnp.ones((b,), jnp.bool_)
    if not rows_skipped():
        o, new = deltanet_step(q, k, v, beta, g, s)
        return (jnp.where(active[:, None, None], o, 0.0),
                jnp.where(active[:, None, None, None], new, s))
    from veles_tpu.ops.pallas import deltanet as kernel
    return kernel.deltanet_decode(s, q, k, v, beta, g, active,
                                  interpret=interpret)


# -------------------------------------------------------- the chunked pass
def _inverse_unit_lower(a):
    """``(I + a)^-1`` of strictly lower ``a`` [..., C, C]: a is
    nilpotent (a^C = 0), so the inverse is the product of ``I +
    (-a)^(2^m)`` for 2^m < C — squarings and products, float32 at the
    highest precision."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    out = eye - a
    power = a
    for _ in range(1, max(1, (c - 1).bit_length())):
        power = jnp.matmul(power, power, precision=_HI)
        out = jnp.matmul(out, eye + power, precision=_HI)
    return out


def delta_chunk(q, k, v, beta, g, s=None, chunk=None):
    """A pass of T tokens against an incoming state: q / k [B, H, T,
    dk] (repeated to the value heads), v [B, H, T, dv], ``beta`` / ``g``
    [B, H, T] float32 -> ``(o [B, H, T, dv] float32, s)``.  ``s`` None:
    from zeros.  A token with β = 0 and g = 0 leaves the state as it
    found it (how a pass's padding is kept out of it: :func:`mixer_chunk`
    zeroes both past ``valid``).  T is padded to whole chunks of
    ``chunk`` (``CHUNK``; T where shorter) the same way."""
    f32 = jnp.float32
    b, h, t, dk = k.shape
    dv = v.shape[-1]
    c = min(int(chunk or CHUNK), t)
    pad = -t % c
    q, k, v = (a.astype(f32) for a in (q, k, v))
    beta, g = beta.astype(f32), g.astype(f32)
    if pad:
        widen = ((0, 0), (0, 0), (0, pad))
        q, k, v = (jnp.pad(a, widen + ((0, 0),)) for a in (q, k, v))
        beta, g = jnp.pad(beta, widen), jnp.pad(g, widen)
    n = (t + pad) // c
    if s is None:
        s = jnp.zeros((b, h, dk, dv), f32)

    def chunks(a):
        """[B, H, T, ...] -> [n, B, H, c, ...]."""
        return jnp.moveaxis(a.reshape(a.shape[:2] + (n, c) + a.shape[3:]),
                            2, 0)

    lower = jnp.tril(jnp.ones((c, c), jnp.bool_))
    strict = jnp.tril(jnp.ones((c, c), jnp.bool_), -1)

    def one(s, xs):
        qc, kc, vc, bc, gc = xs
        cum = jnp.cumsum(gc, axis=-1)                       # [B, H, c]
        # masked BEFORE the exponent: a later key's difference is
        # positive and would overflow
        gam = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :],
                                -jnp.inf))
        kb = kc * bc[..., None]
        a = jnp.where(strict, jnp.einsum(
            "bhik,bhjk->bhij", kb, kc, precision=_HI) * gam, 0.0)
        tri = _inverse_unit_lower(a)
        u = jnp.einsum("bhij,bhjv->bhiv", tri, vc * bc[..., None],
                       precision=_HI)
        w = jnp.einsum("bhij,bhjk->bhik", tri,
                       kb * jnp.exp(cum)[..., None], precision=_HI)
        fresh = u - jnp.einsum("bhik,bhkv->bhiv", w, s, precision=_HI)
        att = jnp.where(lower, jnp.einsum(
            "bhik,bhjk->bhij", qc, kc, precision=_HI) * gam, 0.0)
        o = jnp.einsum("bhik,bhkv->bhiv", qc * jnp.exp(cum)[..., None], s,
                       precision=_HI) \
            + jnp.einsum("bhij,bhjv->bhiv", att, fresh, precision=_HI)
        last = cum[..., -1:]
        s = jnp.exp(last)[..., None] * s + jnp.einsum(
            "bhik,bhiv->bhkv", kc * jnp.exp(last - cum)[..., None], fresh,
            precision=_HI)
        return s, o

    s, os_ = jax.lax.scan(one, s, tuple(chunks(a)
                                         for a in (q, k, v, beta, g)))
    o = jnp.moveaxis(os_, 0, 2).reshape(b, h, n * c, dv)
    return o[:, :, :t], s


# --------------------------------------------------------------- the mixer
def mixer_init(rng, d_model, shape, dtype=jnp.float32):
    """The layer's leaves: ``w_qkvz`` [d_model, 2 Hk dk + 2 Hv dv] (q,
    k, v and z side by side, in that order), ``w_ba`` [d_model, 2 Hv]
    (β's logits, then the gate's), the conv's taps ``conv_w`` [conv,
    channels] (tap ``conv - 1`` reads the current token), ``a_log`` and
    ``dt_bias`` [Hv] float32, the output norm's gain ``norm`` [dv]
    float32, ``wo`` [Hv dv, d_model]."""
    hk, hv = shape["k_heads"], shape["v_heads"]
    dk, dv = shape["k_dim"], shape["v_dim"]
    if hv % hk:
        raise ValueError("v_heads %d %% k_heads %d != 0" % (hv, hk))
    std = 1.0 / math.sqrt(d_model)

    def w(dims, s):
        return jnp.asarray(rng.normal(0.0, s, dims), dtype)

    return {
        "w_qkvz": w((d_model, 2 * hk * dk + 2 * hv * dv), std),
        "w_ba": w((d_model, 2 * hv), std),
        "conv_w": w((shape["conv"], channels(shape)),
                    1.0 / math.sqrt(shape["conv"])),
        "a_log": jnp.zeros((hv,), jnp.float32),
        "dt_bias": jnp.ones((hv,), jnp.float32),
        "norm": jnp.ones((dv,), jnp.float32),
        "wo": w((hv * dv, d_model), 1.0 / math.sqrt(hv * dv)),
    }


def _project(params, x, shape, policy):
    """x [B, T, d] -> the pre-conv ``[q; k; v]`` [B, T, channels], z
    [B, T, Hv, dv], β and the gate [B, T, Hv], all float32."""
    f32 = jnp.float32
    hv, dv = shape["v_heads"], shape["v_dim"]
    wide = channels(shape)
    qkvz = attention._proj(x, params["w_qkvz"], None, policy).astype(f32)
    ba = attention._proj(x, params["w_ba"], None, policy).astype(f32)
    z = qkvz[..., wide:].reshape(x.shape[:2] + (hv, dv))
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(params["a_log"].astype(f32)) * jax.nn.softplus(
        ba[..., hv:] + params["dt_bias"].astype(f32))
    return qkvz[..., :wide], z, beta, g


def _conv(params, pre, tail, valid=None):
    """The causal depthwise conv over ``[tail; pre]`` and SiLU: pre
    [B, T, C], tail [B, conv - 1, C] -> ``(out [B, T, C], tail)``, the
    new tail the ``conv - 1`` inputs before position ``valid`` (default
    T) of the joined row."""
    w = params["conv_w"].astype(jnp.float32)
    taps, t = w.shape[0], pre.shape[1]
    row = jnp.concatenate([tail, pre], axis=1)
    out = sum(row[:, i:i + t] * w[i] for i in range(taps))
    cut = t if valid is None else valid
    tail = jax.lax.dynamic_slice_in_dim(row, cut, taps - 1, axis=1)
    return jax.nn.silu(out), tail


def _heads(conv_out, shape):
    """``[q; k; v]`` after the conv -> q, k [B, Hv, T, dk] (L2-normed,
    q scaled, a key head repeated over its value heads), v [B, Hv, T,
    dv]."""
    b, t, _ = conv_out.shape
    hk, hv = shape["k_heads"], shape["v_heads"]
    dk, dv = shape["k_dim"], shape["v_dim"]
    q, k, v = jnp.split(conv_out, [hk * dk, 2 * hk * dk], axis=-1)

    def norm(a):
        a = a.reshape(b, t, hk, dk)
        a = a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True)
                              + L2_EPS)
        return jnp.repeat(jnp.swapaxes(a, 1, 2), hv // hk, axis=1)

    return (norm(q) * dk ** -0.5, norm(k),
            jnp.swapaxes(v.reshape(b, t, hv, dv), 1, 2))


def _out(params, o, z, policy, eps):
    """o [B, Hv, T, dv] -> [B, T, d]: RMS-normed a head (the gain of
    dv), times silu(z), projected back."""
    o = jnp.swapaxes(o, 1, 2)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * params["norm"].astype(jnp.float32) * jax.nn.silu(z)
    return attention._proj(o.reshape(o.shape[:2] + (-1,)), params["wo"],
                           None, policy)


def mixer_chunk(params, x, state, start, n_heads, shape, policy=None,
                eps=1e-6, valid=None):
    """x [B, T, d] against ``state`` (None: a prompt's first pass, from
    zeros) -> ``(y [B, T, d], state)``.  ``valid``: the tokens before it
    alone enter the state and the conv's tail (a pass's padding, and the
    last prompt token that the decode step takes, must not); the outputs
    past it are not meant to be read.  ``start`` and ``n_heads`` are
    taken as ``ops.retention``'s mixer takes them (the block calls both
    alike) and not read: the rule has no positions, ``shape`` its
    heads."""
    b, t, _ = x.shape
    if state is None:
        state = init_state(b, shape)
    pre, z, beta, g = _project(params, x, shape, policy)
    conv_out, tail = _conv(params, pre, state.conv, valid)
    q, k, v = _heads(conv_out, shape)
    beta, g = jnp.swapaxes(beta, 1, 2), jnp.swapaxes(g, 1, 2)
    if valid is not None:
        keep = jnp.arange(t) < valid
        beta, g = jnp.where(keep, beta, 0.0), jnp.where(keep, g, 0.0)
    o, s = delta_chunk(q, k, v, beta, g, state.s)
    return _out(params, o, z, policy, eps), DeltaNetState(s, tail)


def mixer_forward(params, x, n_heads, shape, policy=None, eps=1e-6):
    """x [B, T, d] -> [B, T, d]: the whole-sequence form, from a zero
    state."""
    return mixer_chunk(params, x, None, 0, n_heads, shape, policy, eps)[0]


def mixer_step(params, x, state, pos, n_heads, shape, policy=None,
               eps=1e-6, active=None, rows=False):
    """x [B, 1, d] -> ``(y [B, 1, d], state)``: the dense generator's
    step, or with ``rows`` the paged tick's, whose rows go through
    :func:`deltanet_step_rows` (``active``: the rows it advances; the
    others' S and tail stay as they are).  ``pos`` and ``n_heads``:
    :func:`mixer_chunk`'s ``start`` and ``n_heads``."""
    pre, z, beta, g = _project(params, x, shape, policy)
    conv_out, tail = _conv(params, pre, state.conv)
    q, k, v = (a[:, :, 0] for a in _heads(conv_out, shape))
    if rows:
        o, s = deltanet_step_rows(q, k, v, beta[:, 0], g[:, 0], state.s,
                                  active)
        if active is not None:
            tail = jnp.where(active[:, None, None], tail, state.conv)
    else:
        o, s = deltanet_step(q, k, v, beta[:, 0], g[:, 0], state.s)
    return _out(params, o[:, :, None], z, policy, eps), \
        DeltaNetState(s, tail)
