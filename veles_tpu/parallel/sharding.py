"""Sharding rules: which mesh axes each tensor rides
(the "pick a mesh, annotate shardings, let XLA insert collectives" recipe).

Parameter rule (tensor parallelism): dense weights (in, out) shard their
*output* dimension over the model axis (Megatron column-parallel); conv
kernels (kh, kw, cin, cout) shard output channels; biases follow their
weights.  Activations are left to GSPMD propagation.  Anything whose dim
doesn't divide the axis stays replicated — correctness never depends on
divisibility.

Data rule (data parallelism): the minibatch index/valid vectors shard over
the data axis; the HBM-resident dataset and labels are replicated (each
shard gathers its own rows).  With params replicated on the data axis and
batch sharded, XLA inserts the gradient ``psum`` over ICI — the TPU-native
equivalent of the reference's master-apply of slave gradient deltas
(veles/workflow.py:529 apply_data_from_slave).

FSDP rule (``MeshConfig(fsdp=True)`` / ``--fsdp``): parameters (and
their optimizer state) additionally shard their FIRST dim over the data
axis where it divides — ZeRO-3: 1/D of the model per worker, GSPMD
inserts the all-gather before use and a reduce-scatter (not psum) on
the gradients."""

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


#: optimizer-state slot names (models/optimizer.init_state): a fallback
#: on ``slot1/l03_dense/weights`` is the SAME fallback as on
#: ``l03_dense/weights`` — strip the slot so the record (and VS201)
#: reports the layer once
_SLOT_KEYS = ("slot1", "slot2", "gacc", "ema")


def _layer_param(path):
    parts = list(path)
    if parts and parts[0] in _SLOT_KEYS:
        parts = parts[1:]
    layer = parts[0] if parts else None
    param = ".".join(parts[1:]) if len(parts) > 1 else None
    return layer, param


def _record(mesh_cfg, path, dim, axis, reason, shape, replicated=True):
    """Log a sharding fallback on the mesh config (VS201 feed);
    tolerant of bare MeshConfig-likes without the recorder.
    ``replicated=False``: the tensor kept a sharding on another axis
    and only missed this one (informational, not a silent replica)."""
    rec = getattr(mesh_cfg, "record_fallback", None)
    if rec is not None:
        layer, param = _layer_param(path or ())
        rec(layer, param, dim, axis, reason, shape,
            replicated=replicated)


def param_spec(shape, mesh_cfg, path=()):
    """PartitionSpec for one parameter tensor: model axis on the output
    (last) dim — Megatron column parallelism — and, when the mesh config
    asks for ``fsdp``, the data axis on the first dim (ZeRO-3-style fully
    sharded params: each data-parallel worker stores 1/D of every weight
    and its optimizer state; GSPMD inserts the all-gather before use and
    the reduce-scatter on the gradient).  Dims that don't divide stay
    replicated — correctness never depends on divisibility — but every
    such fallback is RECORDED on ``mesh_cfg.sharding_fallbacks`` (keyed
    by ``path``, the layer/param names) so the VS201 lint rule can report
    which layer silently lost its sharding and why."""
    if not shape:
        return P()
    spec = [None] * len(shape)
    m_size = mesh_cfg.model_size
    if m_size > 1:
        if shape[-1] % m_size == 0:
            spec[-1] = mesh_cfg.model_axis
        else:
            _record(mesh_cfg, path, len(shape) - 1, mesh_cfg.model_axis,
                    "output dim %d not divisible by %s=%d — tensor "
                    "stays replicated over the model axis"
                    % (shape[-1], mesh_cfg.model_axis, m_size), shape)
    d_size = mesh_cfg.data_size
    if getattr(mesh_cfg, "fsdp", False) and d_size > 1:
        if spec[0] is not None:
            # still model-axis sharded — an informational miss of the
            # EXTRA fsdp axis, not a silent replication (every 1-D bias
            # hits this on every fsdp mesh)
            _record(mesh_cfg, path, 0, mesh_cfg.data_axis,
                    "fsdp skip: dim 0 already carries the model axis — "
                    "parameter is NOT additionally sharded over %s=%d"
                    % (mesh_cfg.data_axis, d_size), shape,
                    replicated=False)
        elif shape[0] % d_size == 0:
            spec[0] = mesh_cfg.data_axis
        else:
            _record(mesh_cfg, path, 0, mesh_cfg.data_axis,
                    "fsdp: dim %d not divisible by %s=%d — parameter "
                    "(and its optimizer state) stays replicated over "
                    "the data axis" % (shape[0], mesh_cfg.data_axis,
                                       d_size), shape)
    while spec and spec[-1] is None:    # canonical: no trailing Nones
        spec.pop()
    return P(*spec)


def _safe_spec(shape, spec, mesh_cfg, path=()):
    """Keep an override spec only where the named dims divide evenly;
    otherwise replicate (correctness never depends on divisibility) and
    record the fallback for VS201."""
    if spec is None:
        return param_spec(shape, mesh_cfg, path)
    entries = tuple(spec)
    if len(entries) > len(shape):
        _record(mesh_cfg, path, None, None,
                "override spec %s names %d dims but the tensor has "
                "only %d — whole tensor replicated"
                % (spec, len(entries), len(shape)), shape)
        return P()
    for dim, axis in enumerate(entries):
        if axis is None:
            continue
        size = mesh_cfg.mesh.shape.get(axis, 1)
        if size > 1 and shape[dim] % size:
            _record(mesh_cfg, path, dim, axis,
                    "override dim %d (size %d) not divisible by "
                    "%s=%d — whole tensor replicated"
                    % (dim, shape[dim], axis, size), shape)
            return P()
    return spec


def _walk_leaves(tree, fn, path=()):
    """tree_map with the dict-key path handed to ``fn(leaf, path)`` —
    param trees are nested dicts, so manual recursion suffices."""
    if isinstance(tree, dict):
        return {k: _walk_leaves(v, fn, path + (k,))
                for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_walk_leaves(v, fn, path + (str(i),))
                          for i, v in enumerate(tree))
    return fn(tree, path)


def _specs_tree(tree, overrides, mesh_cfg, path=()):
    """Spec pytree for ``tree``.  ``overrides`` maps a dict key (layer
    name, at any nesting level — the velocity tree nests layers under
    slot names) to either a PartitionSpec applied to every leaf below it,
    or a partial dict mirroring the subtree (missing keys fall back to
    the default model-axis rule)."""
    def apply_override(sub, ov, p):
        if isinstance(ov, dict):
            if not isinstance(sub, dict):
                raise TypeError("override dict against non-dict params")
            return {k: (apply_override(v, ov[k], p + (k,)) if k in ov
                        and ov[k] is not None
                        else _specs_tree(v, overrides, mesh_cfg, p + (k,)))
                    for k, v in sub.items()}
        return _walk_leaves(
            sub, lambda x, lp: _safe_spec(x.shape, ov, mesh_cfg, lp), p)

    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            ov = (overrides or {}).get(k)
            out[k] = (apply_override(v, ov, path + (k,))
                      if ov is not None
                      else _specs_tree(v, overrides, mesh_cfg,
                                       path + (k,)))
        return out
    return param_spec(tree.shape, mesh_cfg, path)


def shard_params(params, mesh_cfg, overrides=None):
    """device_put a {layer: {name: array}} pytree.  Default rule:
    model-axis tensor parallelism; ``overrides`` (from
    Layer.param_partition_specs) shard e.g. expert banks over 'expert'
    and pipeline stages over 'pipe'."""
    mesh = mesh_cfg.mesh
    specs = _specs_tree(params, overrides, mesh_cfg)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)


def param_shardings(params, mesh_cfg, overrides=None):
    mesh = mesh_cfg.mesh
    specs = _specs_tree(params, overrides, mesh_cfg)
    return jax.tree_util.tree_map(
        lambda x, s: NamedSharding(mesh, s), params, specs)


def replicate(x, mesh_cfg):
    return jax.device_put(x, NamedSharding(mesh_cfg.mesh, P()))


def shard_dataset(x, mesh_cfg):
    """Place a whole dataset with its sample dim sharded over the data
    axis — each device holds 1/data_size of the rows instead of a full
    replica (lifts the r1 replication that made ImageNet-scale fullbatch
    impossible; ref OOM concern veles/loader/fullbatch.py:164-242).
    Rows are zero-padded up to a multiple of the axis size; padding rows
    are never referenced (indices < true length)."""
    import numpy as np
    d = mesh_cfg.data_size
    n = x.shape[0]
    pad = (-n) % d
    if pad:
        x = np.concatenate(
            [np.asarray(x),
             np.zeros((pad,) + tuple(x.shape[1:]), np.asarray(x).dtype)])
    return jax.device_put(
        x, NamedSharding(mesh_cfg.mesh, P(mesh_cfg.data_axis)))


def make_sharded_gather(mesh_cfg):
    """Minibatch gather against a row-sharded dataset, for use INSIDE the
    jitted step.  Each device: all_gathers the (tiny, int32) index vector,
    gathers the rows it owns locally (others masked to 0), then a
    ``psum_scatter`` over the data axis both completes every row and hands
    each device exactly its own 1/D slice of the minibatch — total ICI
    traffic is one minibatch, never the dataset.  (TPU-native equivalent
    of the reference's fill_minibatch_data_labels gather,
    ocl/fullbatch_loader.cl, against a dataset no single device holds.)"""
    from jax import shard_map

    axis = mesh_cfg.data_axis
    mesh = mesh_cfg.mesh

    def local(data_local, idx_local):
        rows_per = data_local.shape[0]
        idx_all = jax.lax.all_gather(idx_local, axis, tiled=True)   # [B]
        loc = jnp.maximum(idx_all, 0) - jax.lax.axis_index(axis) * rows_per
        ok = (loc >= 0) & (loc < rows_per)
        part = jnp.take(data_local, jnp.clip(loc, 0, rows_per - 1), axis=0)
        mask = ok.reshape((ok.shape[0],) + (1,) * (part.ndim - 1))
        part = jnp.where(mask, part, jnp.zeros((), part.dtype))
        return jax.lax.psum_scatter(part, axis, scatter_dimension=0,
                                    tiled=True)

    return shard_map(local, mesh=mesh, in_specs=(P(axis), P(axis)),
                     out_specs=P(axis))


def shard_batch(x, mesh_cfg):
    """Shard the leading (minibatch) dim over the data axis (replicated
    when the mesh has no data axis — e.g. a pure tensor-parallel mesh)."""
    if mesh_cfg.data_axis not in mesh_cfg.mesh.shape:
        return replicate(x, mesh_cfg)
    return jax.device_put(
        x, NamedSharding(mesh_cfg.mesh, P(mesh_cfg.data_axis)))


def shard_batch_stack(x, mesh_cfg):
    """Shard dim 1 (minibatch) of a [k, B, ...] stack over the data axis —
    the fused k-step sweep's index/valid matrices, one transfer per k
    steps."""
    if mesh_cfg.data_axis not in mesh_cfg.mesh.shape:
        return replicate(x, mesh_cfg)
    return jax.device_put(
        x, NamedSharding(mesh_cfg.mesh,
                         P(None, mesh_cfg.data_axis)))


def replicated_sharding(mesh_cfg):
    return NamedSharding(mesh_cfg.mesh, P())
