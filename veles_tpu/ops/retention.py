"""Power retention of degree 2 (Manifest AI, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239): causal attention whose weight
for key ``s`` at query ``t`` is the SQUARE of the scaled dot product,
decayed by a learned per-KV-head gate and normalised by the sum of the
weights — no softmax:

    a_ts = exp(sum_{r=s+1..t} log g_r) * ((q_t . k_s) / sqrt(hd))^2
    y_t  = sum_s a_ts v_s / (sum_s a_ts + eps)

With ``phi(u)`` the symmetric second power of ``u`` (``phi(q) . phi(k)
= (q . k)^2`` exactly) the same function is a RECURRENCE over a
fixed-size float32 state a KV head — what makes the layer servable
without a per-token cache:

    S_t = g_t S_{t-1} + v_t phi(k_t)^T      [hd, Dp]
    z_t = g_t z_{t-1} + phi(k_t)            [Dp]
    y_t = S_t phi(q_t) / (z_t . phi(q_t) + eps)

Three forms of one function live here: :func:`retention_step` (one
token a row, the recurrence; ``ops.pallas.retention`` is its kernel),
:func:`retention_chunk` (a pass of T tokens against an incoming state:
a scan over chunks of ``CHUNK`` tokens, each the quadratic form inside
the chunk plus the state's term, handing the state on) and, from a zero
state, the whole-sequence form ``TransformerBlock.apply`` trains with
(the same chunked scan, differentiable by autodiff).  The plain
quadratic form is the benchmark's reference (``benchmarks/
reference_brumby.py``) and the tests' ground truth.

THE LAYOUT OF ``phi``, chosen once (:func:`phi`): with ``u = [u1; u2]``
split in halves of ``h = hd / 2``, the features are ``sqrt2 u1 (x) u2``
(h x h), one h x h block holding ``u1 (x) u1``'s upper triangle (its
off-diagonal entries times sqrt2) and, below the diagonal, ``u2 (x)
u2``'s strictly lower one times sqrt2, then ``u2``'s squares: ``hd (hd
+ 1) / 2`` features — 8,256 at hd 128 — padded with zeros to a multiple
of 128 lanes (``Dp`` = 8,320).  Feature r is ``w_r u[i_r] u[j_r]``
(:func:`phi_index`), and the two selections are ONE-HOT MATMULS: the
MXU is the gather (exact: a one-hot row picks one value), no gather of
single elements and no relayout of a [.., h, h] tile into lanes — the
broadcast-and-reshape form of the same layout cost a 2,048-token pass
150 ms of copies where these cost 15 (my chip run, PR 37).
The state is kept TRANSPOSED, ``s [hd, Dp]``:
the features lie along the lanes, so the decode kernel updates a tile
with two broadcasts and reads it with one ``q k^T``-shaped matmul."""

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from veles_tpu.ops import attention

#: added to the decayed sum of weights before the division
EPS = 1e-6

#: tokens a chunk of :func:`retention_chunk` holds: the product inside a
#: chunk is quadratic in it, ``phi(q)`` (float32 [Hq, CHUNK, Dp]: 340 MB
#: at 40 heads of 128) linear; a pass's length is a multiple of it or
#: shorter
CHUNK = 256

#: None: the decode step of a batch of rows runs in the Pallas kernel on
#: the TPU and in :func:`retention_step`'s XLA form off it; True forces
#: the kernel (interpret mode off the TPU: the tests'), False the XLA form
KERNEL = None

_LANES = 128


class RetentionState(NamedTuple):
    """A retention layer's serve-time state, fixed-size a slot (float32
    whatever the cache dtype): ``s`` [B, Hkv, hd, Dp], the decayed sum
    of ``v phi(k)^T``, and ``z`` [B, Hkv, Dp], of ``phi(k)``."""

    s: jnp.ndarray
    z: jnp.ndarray


#: the state's container, by the name every state module gives it
State = RetentionState


def phi_width(hd):
    """``Dp``: ``hd (hd + 1) / 2`` features, padded to whole lanes."""
    return -(-(hd * (hd + 1) // 2) // _LANES) * _LANES


def state_shapes(n_kv_heads, hd):
    """Leaf name -> shape a slot, as ``TransformerBlock.state_leaves``
    declares them."""
    return {"s": (n_kv_heads, hd, phi_width(hd)),
            "z": (n_kv_heads, phi_width(hd))}


@functools.lru_cache(maxsize=None)
def phi_index(hd):
    """``(i, j, w)``, each [Dp]: feature r is ``w[r] u[i[r]] u[j[r]]``
    — the module's layout written once (the padding's weight is 0)."""
    if hd % 2:
        raise ValueError("power retention needs an even head_dim")
    h = hd // 2
    r2 = math.sqrt(2.0)
    a, b = (x.reshape(-1) for x in np.meshgrid(np.arange(h), np.arange(h),
                                               indexing="ij"))
    upper = a <= b
    i = np.concatenate([a, np.where(upper, a, h + a), h + np.arange(h)])
    j = np.concatenate([h + b, np.where(upper, b, h + b),
                        h + np.arange(h)])
    w = np.concatenate([np.full(h * h, r2), np.where(a == b, 1.0, r2),
                        np.ones(h)])
    pad = phi_width(hd) - i.size
    return (np.pad(i, (0, pad)), np.pad(j, (0, pad)),
            np.pad(w, (0, pad)).astype(np.float32))


@functools.lru_cache(maxsize=None)
def _selectors(hd):
    """The two one-hot matrices [hd, Dp] that pick ``u[i]`` and ``u[j]``
    (float32; built once a head width), and the weights."""
    i, j, w = phi_index(hd)
    eye = np.eye(hd, dtype=np.float32)
    return eye[:, i], eye[:, j], w


def phi(u, scale=1.0):
    """The symmetric second power of ``u`` [..., hd], in the module's
    layout, [..., Dp] float32: ``phi(a) . phi(b) == (a . b)^2``;
    ``scale`` multiplies every feature (``phi(c u) = c^2 phi(u)``
    without rounding ``u``).  The selections keep ``u``'s dtype: a
    bfloat16 ``u`` is picked in one exact MXU pass, a float32 one at
    the highest precision."""
    pick_i, pick_j, w = _selectors(u.shape[-1])
    exact = None if u.dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST
    first, second = (jnp.matmul(
        u, jnp.asarray(pick, u.dtype), precision=exact,
        preferred_element_type=u.dtype) for pick in (pick_i, pick_j))
    return first.astype(jnp.float32) * second.astype(jnp.float32) \
        * jnp.asarray(w * scale)


def init_state(batch, n_kv_heads, hd):
    return RetentionState(**{name: jnp.zeros((batch,) + shape, jnp.float32)
                             for name, shape in
                             state_shapes(n_kv_heads, hd).items()})


def _features(q, k, rows=None):
    """``phi`` of one token's queries and key, ONE array [B, Hkv, rows,
    Dp] a KV head: rows [0, G) the group's queries (the scale 1/hd
    folded in), row G the key, the rest (``rows`` None: none) zeros —
    what the decode kernel is handed."""
    b, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    rows = rows or g + 1
    u = jnp.concatenate(
        [q.reshape(b, hkv, g, hd), k[:, :, None].astype(q.dtype),
         jnp.zeros((b, hkv, rows - g - 1, hd), q.dtype)], axis=2)
    each = np.zeros((rows, 1), np.float32)
    each[:g], each[g] = 1.0 / hd, 1.0
    return phi(u) * each


def retention_step(q, k, v, logg, state, eps=EPS):
    """One token a row, the recurrence in XLA: q [B, Hq, hd], k / v
    [B, Hkv, hd], ``logg`` [B, Hkv] float32 (log of the gate) ->
    ``(y [B, Hq, hd] float32, state)``.  Float32 throughout."""
    b, hq, hd = q.shape
    g = hq // k.shape[1]
    f = _features(q, k)
    fq, fk = f[:, :, :g], f[:, :, g]
    decay = jnp.exp(logg.astype(jnp.float32))
    s = state.s * decay[..., None, None] \
        + v.astype(jnp.float32)[..., :, None] * fk[..., None, :]
    z = state.z * decay[..., None] + fk
    hi = jax.lax.Precision.HIGHEST
    num = jnp.einsum("bkgr,bker->bkge", fq, s, precision=hi)
    den = jnp.einsum("bkgr,bkr->bkg", fq, z, precision=hi)
    return (num / (den[..., None] + eps)).reshape(b, hq, hd), \
        RetentionState(s, z)


def rows_skipped():
    """Whether :func:`retention_step_rows` runs the kernel here, which
    skips inactive rows (the XLA form moves every row's state)."""
    from veles_tpu.ops import pallas
    return KERNEL if KERNEL is not None else \
        not pallas.autodetect_interpret(None)


def retention_step_rows(q, k, v, logg, state, active=None, eps=EPS,
                        interpret=None):
    """:func:`retention_step` for the paged batcher's tick: where the
    kernel runs (``KERNEL``), ``s`` is updated and read in ONE trip
    through HBM, in place under the tick's donation, and a row whose
    ``active`` flag is down is skipped (its state stays, its ``y`` is
    nought); ``z`` — a hundredth of the state — is XLA's."""
    if not rows_skipped():
        return retention_step(q, k, v, logg, state, eps)
    from veles_tpu.ops.pallas import retention as kernel
    b, hq, hd = q.shape
    g = hq // k.shape[1]
    if active is None:
        active = jnp.ones((b,), jnp.bool_)
    f = _features(q, k, kernel.feature_rows(g))
    fq, fk = f[:, :, :g], f[:, :, g]
    decay = jnp.exp(logg.astype(jnp.float32))
    z = jnp.where(active[:, None, None],
                  state.z * decay[..., None] + fk, state.z)
    den = jnp.einsum("bkgr,bkr->bkg", fq, z,
                     precision=jax.lax.Precision.HIGHEST)
    num, s = kernel.retention_decode(state.s, f, g, v, decay, active,
                                     interpret=interpret)
    y = jnp.where(active[:, None, None, None],
                  num / (den[..., None] + eps), 0.0)
    return y.reshape(b, hq, hd), RetentionState(s, z)


def retention_chunk(q, k, v, logg, state=None, valid=None, chunk=None,
                    policy=None, eps=EPS):
    """A pass of T tokens against an incoming state: q [B, Hq, T, hd],
    k / v [B, Hkv, T, hd], ``logg`` [B, Hkv, T] float32 -> ``(y
    [B, Hq, T, hd] float32, state)``.  ``state`` None: from zeros (the
    whole-sequence form).  ``valid``: the tokens before it alone enter
    the state (a pass's padding, and the last prompt token that the
    decode step takes, must not: a state cannot be overwritten as a
    cache row can); the outputs past it are not meant to be read.

    A scan over chunks of ``chunk`` (``CHUNK``) tokens.  Inside a chunk
    that enters with ``(S, z)`` and cumulative gates ``b_t``: the
    numerator is ``exp(b_t) S phi(q_t) + sum_{s<=t} exp(b_t - b_s)
    (q_t . k_s)^2 v_s``, the denominator likewise with ``z`` and 1, and
    the chunk leaves ``exp(b_C) S + sum_s exp(b_C - b_s) v_s
    phi(k_s)^T``.  Matmul operands in the policy's compute dtype, gates,
    exponents, accumulation and the state float32."""
    b, hq, t, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    c = min(int(chunk or CHUNK), t)
    if t % c:
        raise ValueError("a pass of %d tokens does not divide into "
                         "chunks of %d" % (t, c))
    n = t // c
    cast = (lambda a: a) if policy is None else policy.cast_in
    f32 = jnp.float32
    logg = logg.astype(f32)
    keep = jnp.ones((t,), jnp.bool_) if valid is None \
        else jnp.arange(t) < valid
    logg = jnp.where(keep, logg, 0.0)
    fresh = state is None
    if fresh:
        state = init_state(b, hkv, hd)

    def chunks(a, axis):
        """[..., T, ...] -> [n, ..., c, ...], the chunk axis first."""
        a = a.reshape(a.shape[:axis] + (n, c) + a.shape[axis + 1:])
        return jnp.moveaxis(a, axis, 0)

    causal = jnp.tril(jnp.ones((c, c), jnp.bool_))

    def one(carry, xs):
        s, z = carry
        qc, kc, vc, lg, ok = xs
        cum = jnp.cumsum(lg, axis=-1)                    # [B, Hkv, c]
        score = jnp.einsum("bkgtd,bksd->bkgts", cast(qc), cast(kc),
                           preferred_element_type=f32) * hd ** -0.5
        # masked BEFORE the exponent: a later key's difference is
        # positive and would overflow
        decay = jnp.exp(jnp.where(
            causal, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        a = jnp.square(score) * decay[:, :, None]
        num = jnp.einsum("bkgts,bkse->bkgte", cast(a), cast(vc),
                         preferred_element_type=f32)
        den = jnp.sum(a, axis=-1)
        if not (fresh and n == 1):
            fq = cast(phi(cast(qc), 1.0 / hd))
            into = jnp.exp(cum)[:, :, None]              # [B, Hkv, 1, c]
            num = num + into[..., None] * jnp.einsum(
                "bkgtr,bker->bkgte", fq, cast(s),
                preferred_element_type=f32)
            den = den + into * jnp.einsum(
                "bkgtr,bkr->bkgt", fq, cast(z),
                preferred_element_type=f32)
        y = num / (den[..., None] + eps)
        # what the chunk leaves: every valid key decayed to its end
        tail = jnp.where(ok, jnp.exp(cum[..., -1:] - cum), 0.0)
        out = jnp.exp(cum[..., -1])
        fk = cast(phi(cast(kc)))
        s = out[..., None, None] * s + jnp.einsum(
            "bkse,bksr->bker", cast(vc.astype(f32) * tail[..., None]), fk,
            preferred_element_type=f32)
        z = out[..., None] * z + jnp.sum(
            tail[..., None] * fk.astype(f32), axis=-2)
        return (s, z), y

    xs = (chunks(q.reshape(b, hkv, g, t, hd), 3), chunks(k, 2),
          chunks(v, 2), chunks(logg, 2), chunks(keep, 0))
    (s, z), ys = jax.lax.scan(one, tuple(state), xs)
    y = jnp.moveaxis(ys, 0, 3).reshape(b, hkv, g, t, hd)
    return y.reshape(b, hq, t, hd), RetentionState(s, z)


# --------------------------------------------------------------- the mixer
def mixer_init(rng, d_model, n_heads, dtype=jnp.float32, n_kv_heads=None,
               bias=True, head_dim=None, qk_norm=False):
    """The attention's q / k / v / o projections (``attention.mha_init``
    draws them, first and in its order) and the gate's: ``wg``
    [d_model, Hkv] and its bias ``bg`` — one ``logsigmoid`` gate a KV
    head, from the block's normed input."""
    n_kv_heads = n_kv_heads or n_heads
    params = attention.mha_init(rng, d_model, n_heads, dtype,
                                n_kv_heads=n_kv_heads, bias=bias,
                                head_dim=head_dim, qk_norm=qk_norm)
    params["wg"] = jnp.asarray(
        rng.normal(0.0, 1.0 / math.sqrt(d_model), (d_model, n_kv_heads)),
        dtype)
    params["bg"] = jnp.zeros((n_kv_heads,), jnp.float32)
    return params


def _project(params, x, positions, n_heads, n_kv_heads, policy, use_rope,
             rope_base, per_row=False):
    """x [B, T, d] at ``positions`` ([T]; with ``per_row`` [B]: T is 1
    and every row sits at its own) -> q [B, Hq, T, hd], k, v
    [B, Hkv, T, hd], log gates [B, Hkv, T] float32."""
    q, k, v = attention._qkv_proj(params, x, n_heads, n_kv_heads, policy)
    if use_rope:
        turn = attention._rope_rows if per_row else attention.rope
        q = turn(q, positions, rope_base)
        k = turn(k, positions, rope_base)
    gate = attention._proj(x, params["wg"], None, policy).astype(
        jnp.float32) + params["bg"].astype(jnp.float32)
    return q, k, v, jnp.swapaxes(jax.nn.log_sigmoid(gate), 1, 2)


def _out(params, y, policy):
    return attention._proj(attention.merge_heads(y), params["wo"],
                           params.get("bo"), policy)


def mixer_forward(params, x, n_heads, n_kv_heads=None, policy=None,
                  use_rope=False, rope_base=10000.0):
    """x [B, T, d] -> [B, T, d]: the whole-sequence form, from a zero
    state (training; padded to whole chunks, whose outputs go)."""
    t = x.shape[1]
    pad = -t % min(CHUNK, t)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    q, k, v, logg = _project(params, x, jnp.arange(t + pad), n_heads,
                             n_kv_heads or n_heads, policy, use_rope,
                             rope_base)
    y, _ = retention_chunk(q, k, v, logg, policy=policy)
    return _out(params, y[:, :, :t], policy)


def mixer_chunk(params, x, state, start, n_heads, n_kv_heads=None,
                policy=None, use_rope=False, rope_base=10000.0,
                valid=None):
    """Positions [start, start + K) of x [B, K, d] against ``state``
    (None: a prompt's first pass, from zeros) -> ``(y [B, K, d],
    state)``; ``valid``: :func:`retention_chunk`'s."""
    q, k, v, logg = _project(
        params, x, start + jnp.arange(x.shape[1]), n_heads,
        n_kv_heads or n_heads, policy, use_rope, rope_base)
    y, state = retention_chunk(q, k, v, logg, state, valid=valid,
                               policy=policy)
    return _out(params, y, policy), state


def mixer_step(params, x, state, pos, n_heads, n_kv_heads=None,
               policy=None, use_rope=False, rope_base=10000.0,
               active=None, rows=False):
    """x [B, 1, d] -> ``(y [B, 1, d], state)``: ``pos`` a scalar (the
    dense generator's step) or, with ``rows``, [B] — the paged tick,
    whose rows go through :func:`retention_step_rows`."""
    pos = jnp.asarray(pos, jnp.int32)
    q, k, v, logg = _project(
        params, x, pos if rows else pos[None], n_heads,
        n_kv_heads or n_heads, policy, use_rope, rope_base, per_row=rows)
    step = functools.partial(retention_step_rows, active=active) \
        if rows else retention_step
    y, state = step(q[:, :, 0], k[:, :, 0], v[:, :, 0], logg[..., 0], state)
    return _out(params, y[:, :, None], policy), state
