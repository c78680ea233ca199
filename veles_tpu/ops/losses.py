"""Evaluators (ref Znicz EvaluatorSoftmax / EvaluatorMSE, SURVEY.md §2.9).

The reference's evaluators produce ``err_output`` consumed by hand-written
GD backward units; here the loss scalar feeds ``jax.grad`` and the metric
outputs (n_errors, confusion matrix, max-err) feed the Decision unit."""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


def softmax_cross_entropy(logits, labels, n_classes=None):
    """Mean NLL over the batch + error count + confusion matrix.

    :param labels: int class ids [N]
    :returns: dict(loss, n_errors, confusion, predictions)
    """
    n_classes = n_classes or logits.shape[-1]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    pred = jnp.argmax(logits, axis=-1)
    errors = (pred != labels).astype(jnp.int32)
    confusion = jnp.zeros((n_classes, n_classes), jnp.int32).at[
        labels, pred].add(1)
    return {"loss": nll.mean(), "n_errors": errors.sum(),
            "confusion": confusion, "predictions": pred}


def mse(output, target):
    """Mean squared error + per-batch RMSE metrics (EvaluatorMSE)."""
    diff = (output - target).astype(jnp.float32).reshape(output.shape[0], -1)
    se = jnp.sum(diff * diff, axis=1)
    return {"loss": se.mean(),
            "rmse": jnp.sqrt(jnp.mean(diff * diff)),
            "max_err": jnp.max(jnp.abs(diff))}


def masked_softmax_xent(logits, labels, valid):
    """Masked-batch softmax cross-entropy sums, for fixed-shape minibatches
    with padded tails (``valid`` is the loader's 0/1 mask).

    :returns: (nll_sum, err_sum, n_valid) — all float32 scalars.
    """
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    pred = jnp.argmax(logits, axis=-1)
    err_sum = jnp.sum((pred != labels).astype(jnp.float32) * valid)
    return jnp.sum(nll * valid), err_sum, jnp.sum(valid)


class HeadLogits(NamedTuple):
    """An LM head's logits not yet computed: the head's input ``h``
    [B, T, d] and its weight ``w`` [V, d] (``transposed``: [d, V], a
    ``timestep_dense`` head's layout), with what :func:`lm_head_xent`
    takes besides.  The logits of ``h``'s first T - 1 positions, as
    :func:`masked_seq_xent` reads them; the last position is computed
    too, and masked (T rows tile evenly)."""
    h: jax.Array
    w: jax.Array
    compute: object = jnp.bfloat16
    transposed: bool = False
    shard: object = None


def masked_seq_xent(logits, labels, valid):
    """Per-timestep softmax cross-entropy for language modeling:
    logits [B, T, V], labels [B, T] int, valid [B] 0/1 sample mask.
    ``logits`` may be a :class:`HeadLogits`, whose logits the head and
    this loss then compute as one op (:func:`lm_head_xent`).

    :returns: (nll_sum, err_sum, n_valid_tokens) — float32 scalars.
    """
    if isinstance(logits, HeadLogits):
        return lm_head_xent(logits.h, logits.w, labels, valid,
                            logits.compute, logits.transposed, logits.shard)
    b, t, v = logits.shape
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    pred = jnp.argmax(logits, axis=-1)
    tok_valid = jnp.broadcast_to(valid[:, None], (b, t)).astype(jnp.float32)
    err_sum = jnp.sum((pred != labels).astype(jnp.float32) * tok_valid)
    return (jnp.sum(nll * tok_valid), err_sum, jnp.sum(tok_valid))


def lm_head_xent(h, w, labels, valid, compute=jnp.bfloat16,
                 transposed=False, shard=None):
    """The LM head and its next-token loss as ONE op: ``h`` [B, T, d]
    (the head's input), ``w`` the head's weight [V, d] (``transposed``:
    [d, V], a ``timestep_dense`` head's layout), ``labels`` [B, T - 1]
    the next token of each position but the last, ``valid`` [B] 0/1.
    Returns what ``masked_seq_xent((h @ Wᵀ)[:, :-1], labels, valid)``
    returns — ``(nll_sum, err_sum, n_valid_tokens)`` — from the logits
    of all T positions, the last masked (it has no next token).

    The matmul's operands are ``compute`` (the head's policy), its
    accumulation, the softmax and the loss float32.  On a TPU the
    forward is the Pallas kernel ``veles_head_xent``
    (``ops.pallas.head_xent``: statistics taken while the logits tile
    is in VMEM, the logits written once as the backward's residual);
    elsewhere the same statistics in jnp.  ``shard=(mesh, axis)`` runs
    the kernel per device under ``shard_map`` (a Mosaic kernel cannot
    be partitioned), rows split over ``axis``, or all rows on each
    device where ``axis`` is None.  The backward is
    ``dZ = (softmax - onehot) · valid`` in ``compute`` from the
    residual, one elementwise pass that XLA fuses
    into the operand of each of the two matmuls ``dZ W`` and ``dZᵀ h``,
    scaled by the loss's cotangent; evaluation runs the primal alone,
    which keeps no logits."""
    return _head_xent_op(jnp.dtype(compute), bool(transposed), shard)(
        h, w, labels, valid)


def _kernel_here():
    """The Pallas forward runs where the kernel compiles: on a TPU."""
    return jax.default_backend() == "tpu"


def _head_stats(hc, wc, nxt, transposed, keep_logits, shard):
    """``(lse, z[nxt], argmax)`` per row of ``z = hc @ wcᵀ`` [N, V]
    (float32 accumulation), and ``z`` itself with ``keep_logits``."""
    if _kernel_here():
        from veles_tpu.ops.pallas import head_xent
        fn = functools.partial(head_xent.head_xent, transposed=transposed,
                               keep_logits=keep_logits)
        if shard is None:
            return fn(hc, wc, nxt)
        from jax.sharding import PartitionSpec as P
        mesh, axis = shard
        rows = P(axis)
        return jax.shard_map(
            fn, mesh=mesh, in_specs=(rows, P(), rows),
            out_specs=(rows,) * (4 if keep_logits else 3),
            check_vma=False)(hc, wc, nxt)
    z = jnp.dot(hc, wc if transposed else wc.T,
                preferred_element_type=jnp.float32)
    m = jnp.max(z, axis=-1)
    stats = (m + jnp.log(jnp.sum(jnp.exp(z - m[:, None]), axis=-1)),
             jnp.take_along_axis(z, nxt[:, None], axis=-1)[:, 0],
             jnp.argmax(z, axis=-1).astype(jnp.int32))
    return stats + ((z,) if keep_logits else ())


@functools.lru_cache(maxsize=None)
def _head_xent_op(compute, transposed, shard):
    """The ``custom_vjp`` of :func:`lm_head_xent` for one static
    configuration (built once, so a trace finds the same function)."""
    f32 = jnp.float32

    def rows(h, w, labels, valid):
        b, t, d = h.shape
        nxt = jnp.concatenate([labels, jnp.zeros_like(labels[:, :1])],
                              axis=1)
        tok = (valid.astype(f32)[:, None]
               * (jnp.arange(t) < t - 1).astype(f32)[None, :])
        return (h.reshape(b * t, d).astype(compute), w.astype(compute),
                nxt.reshape(-1).astype(jnp.int32), tok.reshape(-1))

    def sums(lse, zl, am, nxt, tok):
        return (jnp.sum((lse - zl) * tok),
                jnp.sum((am != nxt).astype(f32) * tok), jnp.sum(tok))

    @jax.custom_vjp
    def op(h, w, labels, valid):
        hc, wc, nxt, tok = rows(h, w, labels, valid)
        lse, zl, am = _head_stats(hc, wc, nxt, transposed, False, shard)
        return sums(lse, zl, am, nxt, tok)

    def fwd(h, w, labels, valid):
        hc, wc, nxt, tok = rows(h, w, labels, valid)
        lse, zl, am, z = _head_stats(hc, wc, nxt, transposed, True, shard)
        return sums(lse, zl, am, nxt, tok), (h, w, nxt, tok, lse, z)

    def bwd(res, ct):
        h, w, nxt, tok, lse, z = res
        g = ct[0]
        hc, wc = h.reshape(-1, h.shape[-1]).astype(compute), \
            w.astype(compute)
        # z <= lse already; the min changes no value, but the two
        # matmuls that take dZ into their operand run 1.9% faster with
        # it on a v5e (gpt2s.train +0.22% end to end, PERF.md section 6)
        p = jnp.exp(jnp.minimum(z - lse[:, None], 0.0))
        onehot = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) \
            == nxt[:, None]
        dz = ((p - onehot.astype(f32)) * tok[:, None]).astype(compute)
        dh = jnp.dot(dz, wc.T if transposed else wc,
                     preferred_element_type=f32) * g
        dw = (jnp.dot(hc.T, dz, preferred_element_type=f32) if transposed
              else jnp.dot(dz.T, hc, preferred_element_type=f32)) * g
        # both products before the backward goes on: left free, the
        # scheduler puts dW's matmul off (a tied table's gradient is only
        # summed at the end) and the logits stay live through the layers'
        # backward, which the compiler then pays for in recomputed
        # activations (PERF.md, section 6)
        dh, dw = jax.lax.optimization_barrier((dh, dw))
        return (dh.reshape(h.shape).astype(h.dtype), dw.astype(w.dtype),
                None, None)

    op.defvjp(fwd, bwd)
    return op


def masked_mse(output, target, valid):
    """Masked-batch summed squared error.

    :returns: (se_sum, n_valid, n_features).
    """
    diff = (output - target).astype(jnp.float32).reshape(output.shape[0], -1)
    se = jnp.sum(diff * diff, axis=1)
    return jnp.sum(se * valid), jnp.sum(valid), diff.shape[1]


# ---------------------------------------------------------------------------
# Pluggable evaluator registry (the extension seam the reference had via
# its evaluator unit registry — new losses register by name and the
# trainer/StandardWorkflow pick them up without modification).
#
# An entry is ``(fn, kind)``:
#   fn(out, labels, targets, valid) ->
#       (loss_sum, err_sum, n_valid, n_features)  — float32 scalars/ints
#   kind: "class" (Decision watches the error count) or
#         "regression" (Decision watches the loss)
# ---------------------------------------------------------------------------

_LOSSES = {}


def register_loss(name, kind="class", numerics_suppress=()):
    """Decorator: ``@register_loss("focal")`` adds an evaluator usable as
    ``StandardWorkflow(loss="focal")``.

    ``numerics_suppress`` is the explicit "checked" escape hatch for the
    VN4xx/VR5xx numerics audit (docs/static_analysis.md): a loss that
    DELIBERATELY trips a rule — say an int8 evaluation metric whose
    narrowing cast is range-checked by construction — names the rule ids
    here, and ``StagedTrainer.lint_numerics_spec`` carries them into the
    audit as suppressions.  Every loss in this file is written to pass
    clean instead (f32 accumulation, ``maximum(n, 1)`` guards), so the
    built-ins suppress nothing."""
    def deco(fn):
        fn.numerics_suppress = tuple(numerics_suppress)
        _LOSSES[name] = (fn, kind)
        return fn
    return deco


def get_loss(name):
    try:
        return _LOSSES[name]
    except KeyError:
        raise KeyError("unknown loss %r — registered: %s"
                       % (name, sorted(_LOSSES))) from None


@register_loss("softmax", kind="class")
def _softmax_loss(out, labels, targets, valid):
    loss_sum, err_sum, n_valid = masked_softmax_xent(out, labels, valid)
    return loss_sum, err_sum, n_valid, 1


@register_loss("lm", kind="class")
def _lm_loss(out, labels, targets, valid):
    # next-token objective: predict token t+1 from logits at t (a
    # HeadLogits computes the last position's too, and masks it)
    loss_sum, err_sum, n_valid = masked_seq_xent(
        out if isinstance(out, HeadLogits) else out[:, :-1],
        labels[:, 1:], valid)
    return loss_sum, err_sum, n_valid, 1


@register_loss("mse", kind="regression")
def _mse_loss(out, labels, targets, valid):
    loss_sum, n_valid, n_features = masked_mse(out, targets, valid)
    return loss_sum, jnp.asarray(0.0), n_valid, n_features
