"""Decode-path auditor: static lint of the serving engine's decode tick
and segmented-prefill pass (VD7xx).

The serving hot loop is the continuous batcher's tick — ONE jitted,
state-donated dispatch (``ContinuousBatcher._jit_ticks``) that every
in-flight request's decode shares.  Anything wrong inside it is paid on
every generated token of every request: a stray dense dequant streams
float weights again (the exact bug class PR 14's quantized decode
erased), a lost donation doubles the KV pool in HBM, a host callback
serializes the XLA stream per token, a weak-typed scalar retraces the
tick per distinct value, and a mis-sized paged-pool block retiles every
VMEM copy of the fused kernel.  All of it is statically decidable: the
auditor abstractly traces the batcher's OWN tick body
(``_tick_body()`` — the same function serving jits, so the lint can
never audit a different tick than serving runs) over
``jax.ShapeDtypeStruct`` mirrors of the live state, and never
dispatches a single decode step.

Rule catalog (docs/static_analysis.md):

========  =======  ======================================================
VD700     error    quantized payload dequantized outside a dot: an
                   int8→float convert of payload size in the traced tick
                   whose result does not feed a ``dot_general``
                   (``ops.quant.stray_dequant_sites`` — the PR 14 jaxpr
                   test generalized into a rule)
VD701     error    donation miss on decode carry state: a state leaf
                   (KV pool / block tables / active flags / sample
                   state) is not aliased in the lowered tick — it is
                   re-allocated on every dispatch
VD702     error    host callback or host transfer inside the tick
                   (``debug_callback`` / ``pure_callback`` /
                   ``io_callback`` / infeed / outfeed), or a tick that
                   fails to trace abstractly at all (host state in the
                   trace)
VD703     warning  retrace hazard: a weak-typed python scalar in the
                   tick signature — each distinct value recompiles the
                   tick (the PR 3 compile counters,
                   ``veles_compile_events_total``, count the damage at
                   runtime; this rule catches it before)
VD704     warning  TP collective volume per tick exceeds the tick's
                   KV-read bytes — the decode is ICI-bound, not
                   HBM-bound (bytes priced with ``ops.flops``)
VD705     mirror   paged-pool launch geometry fails the VP6xx audit at
                   the block the engine actually resolved (config >
                   tuner winner > default — the same chain the launch
                   would use); severity mirrors the underlying VP rule
========  =======  ======================================================
"""

import jax
import jax.numpy as jnp

from veles_tpu.analysis.findings import (ERROR, WARNING, Finding,
                                         sort_findings)
from veles_tpu.analysis.staging import _aval_str, iter_primitives

#: the full VD7xx family, in catalog order
RULES = ("VD700", "VD701", "VD702", "VD703", "VD704", "VD705")

#: primitive names that round-trip device -> host mid-tick
_HOST_SYNC_PRIMS = ("outfeed", "infeed")

#: collective kinds priced by VD704 (the sharding auditor's grammar)
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
                "all-to-all", "collective-permute")


def _abstract(tree, with_shardings=False):
    """ShapeDtypeStruct mirror of a pytree of arrays.  With
    ``with_shardings`` each leaf that carries a mesh (NamedSharding)
    keeps it, so a lowering sees the same post-SPMD module serving
    would compile — still nothing concrete."""
    def leaf(a):
        if not hasattr(a, "shape"):
            # a python scalar in the tree stays concrete — exactly the
            # weak-type retrace hazard VD703 exists to flag
            return a
        sh = getattr(a, "sharding", None) if with_shardings else None
        if sh is not None and hasattr(sh, "spec"):     # NamedSharding
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
        return jax.ShapeDtypeStruct(a.shape, a.dtype)
    return jax.tree_util.tree_map(leaf, tree,
                                  is_leaf=lambda x: hasattr(x, "shape"))


def _tick_name(cb):
    gen = cb.gen
    tags = [getattr(gen, "weight_dtype", None) or "bf16"]
    if getattr(cb, "block", None):
        tags.append("paged%s" % ("-q8" if getattr(gen, "cache_dtype",
                                                  None) == "int8"
                                 else ""))
    if getattr(cb, "speculative_k", 0):
        tags.append("spec%d" % cb.speculative_k)
    return "decode[%s]" % ",".join(tags)


def _scan_jaxpr(closed, name, params=None, scheme=None):
    """The three jaxpr-level rules over one traced pass: VD700 (when a
    quantized param tree is given), VD702, VD703."""
    findings = []

    if scheme and params is not None:
        from veles_tpu.ops import quant
        try:
            thr = quant.min_payload_elems(params)
        except ValueError:        # no quantized leaves after all
            thr = None
        if thr:
            for site in quant.stray_dequant_sites(closed, thr):
                findings.append(Finding(
                    "VD700", ERROR, name,
                    "quantized payload dequantized outside a dot: %s "
                    "— XLA hoists the dense float copy out of the "
                    "decode scan and the loop streams floats again"
                    % site,
                    hint="keep the int8/int4 payload narrow into the "
                         "dot (ops.quant int8_matmul / w4a8_matmul "
                         "funnels); dequantize per-row only for "
                         "gathers"))

    seen = set()
    for prim_name, _eqn in iter_primitives(closed.jaxpr):
        if "callback" not in prim_name \
                and prim_name not in _HOST_SYNC_PRIMS:
            continue
        if prim_name in seen:
            continue
        seen.add(prim_name)
        what = ("jax.debug.print/debug.callback"
                if prim_name == "debug_callback" else prim_name)
        findings.append(Finding(
            "VD702", ERROR, name,
            "host callback/transfer inside the decode tick (%s): "
            "every generated token round-trips device -> host and "
            "serializes the XLA stream for the whole pool" % what,
            hint="move host work (logging, metrics, numpy) to the "
                 "engine thread outside the tick; fetch stats from "
                 "the tick's outputs instead"))

    for i, aval in enumerate(closed.in_avals):
        if getattr(aval, "weak_type", False):
            findings.append(Finding(
                "VD703", WARNING, name,
                "tick input leaf %d is weak-typed (%s): a python "
                "scalar leaked into the tick signature — each "
                "distinct value retraces and recompiles the tick "
                "(veles_compile_events_total counts these at "
                "runtime)" % (i, _aval_str(aval)),
                hint="wrap host scalars at admission, e.g. "
                     "jnp.int32(x) / jnp.asarray(x, dtype) — the "
                     "admit bodies already do this for the state "
                     "tuple"))
    return findings


def _kv_leaves(state):
    """The KV-carrying leaves of a batcher state tuple: cache/pool
    tensors are >= 3-D, the token matrix and per-slot vectors are
    not."""
    return [l for l in jax.tree_util.tree_leaves(state)
            if getattr(l, "ndim", 0) >= 3]


def audit_decode_tick(cb, vmem_kib=None, name=None):
    """All VD7xx rules over one batcher's decode tick.

    ``cb`` is a constructed ``ContinuousBatcher`` /
    ``PagedContinuousBatcher`` (construction allocates its zero-filled
    state, exactly like ``--numerics`` allocates parameters); the audit
    itself traces and lowers abstractly — no tick is ever
    dispatched."""
    gen = cb.gen
    name = name or _tick_name(cb)
    findings = []

    state = cb._state()
    abstract = _abstract((gen.params, state, cb._aids))
    try:
        body = cb._tick_body()
        closed = jax.make_jaxpr(body)(*abstract)
    except Exception as e:  # noqa: BLE001 — the failure IS the finding
        findings.append(Finding(
            "VD702", ERROR, name,
            "decode tick failed to trace abstractly: %s: %s — host "
            "state or data-dependent python control flow is inside "
            "the tick" % (type(e).__name__, e),
            hint="the tick must be traceable over ShapeDtypeStructs; "
                 "hoist host decisions to admission"))
        return sort_findings(findings + audit_pool_geometry(
            cb, vmem_kib=vmem_kib, name=name))

    findings.extend(_scan_jaxpr(closed, name, params=gen.params,
                                scheme=getattr(gen, "weight_dtype",
                                               None)))

    # ---- VD701: state donation in the ACTUAL dispatch wrapper.  The
    # engine jits through _jit_ticks (donate_argnums=(1,)); donation
    # materializes as per-arg aliasing markers in the lowered module,
    # one per donated state leaf — count them against the state tree.
    # The tick's report beside the state is a fresh output of a few
    # hundred bytes: no argument is donated into it and it has no
    # marker, so the count is the state's alone.  (It is what lets the
    # engine enqueue the NEXT dispatch on the returned state before it
    # reads this one's report: no leaf of the state is ever read.)
    try:
        lowered = cb._jit_ticks(body).lower(*abstract)
        text = lowered.as_text()
    except Exception as e:  # noqa: BLE001 — lowering failed: report, don't crash
        findings.append(Finding(
            "VD702", ERROR, name,
            "decode tick failed to lower: %s: %s"
            % (type(e).__name__, e)))
        text = None
    n_state = len(jax.tree_util.tree_leaves(state))
    if text is not None:
        aliased = text.count("tf.aliasing_output")
        if aliased < n_state:
            findings.append(Finding(
                "VD701", ERROR, name,
                "decode carry state not donated: %d of %d state "
                "leaves alias their outputs in the lowered tick — "
                "the rest (KV pool / caches, active flags, sample "
                "state) are re-allocated on EVERY dispatch, doubling "
                "their HBM while the tick runs"
                % (aliased, n_state),
                hint="dispatch through ContinuousBatcher._jit_ticks "
                     "(donate_argnums=(1,)) and keep state outputs "
                     "aval-identical to their inputs"))

    # ---- VD704: TP collective volume per tick vs KV-read bytes.
    # Only meaningful under a model-axis mesh; the collectives GSPMD
    # actually inserts live in the post-SPMD compiled module
    # (sharding_audit's technique) — compiled, never dispatched.
    mc = getattr(gen, "mesh_cfg", None)
    if mc is not None and getattr(mc, "model_size", 1) > 1 \
            and text is not None:
        from veles_tpu.analysis.sharding_audit import collective_stats
        from veles_tpu.ops.flops import shape_nbytes
        sharded = _abstract((gen.params, state, cb._aids),
                            with_shardings=True)
        try:
            compiled = cb._jit_ticks(body).lower(*sharded).compile()
            stats = collective_stats(compiled.as_text())
        except Exception:  # noqa: BLE001 — collective pricing degrades gracefully
            stats = {}
        coll = sum(stats.get(k, {}).get("bytes", 0)
                   for k in _COLLECTIVES)
        coll //= max(1, cb.ticks_per_dispatch)
        kv = sum(shape_nbytes(l.shape, l.dtype)
                 for l in _kv_leaves(state))
        kv //= max(1, getattr(mc, "model_size", 1))
        if coll and coll > kv:
            counts = {k: stats[k]["count"] for k in stats
                      if k in _COLLECTIVES and stats[k]["count"]}
            findings.append(Finding(
                "VD704", WARNING, name,
                "TP collectives move %.2f MiB/device per tick but the "
                "tick reads at most %.2f MiB/device of KV (%s) — the "
                "decode is ICI-bound, the model axis costs more than "
                "the memory traffic it saves"
                % (coll / 2 ** 20, kv / 2 ** 20,
                   ", ".join("%s x%d" % kv_ for kv_ in
                             sorted(counts.items()))),
                hint="shrink the model axis for serving, shard the KV "
                     "heads on it (gen._cache_constraint), or serve "
                     "replicated and route requests instead"))

    findings.extend(audit_pool_geometry(cb, vmem_kib=vmem_kib,
                                        name=name))
    return sort_findings(findings)


def audit_pool_geometry(cb, vmem_kib=None, name=None):
    """VD705: re-audit the paged-pool launch geometry the engine
    RESOLVED (``PagedContinuousBatcher.block`` — config > tuner winner
    > default, the exact chain ``ops.pallas.paged.preferred_pool_block``
    walks at admission) through the VP6xx kernel rules, at the pool's
    own KV heads: the launch holds ``paged.page_schedule``'s page
    buffers (a chunk of whole pages, K and V, two slots each), so a
    misaligned block is a misaligned page tile and a block too fat for
    the buffers is over the VMEM budget.  Dense batchers launch no
    kernel — nothing to audit."""
    if getattr(cb, "block", None) is None:
        return []
    name = name or _tick_name(cb)
    from veles_tpu.analysis.numerics_audit import audit_kernel_launch
    from veles_tpu.ops.pallas import mosaic_sublane_min
    from veles_tpu.ops.pallas import paged as _paged

    # the PAGED layers' leaves only: a state layer's slot-major leaf is
    # 4-D too, and no page of it exists
    pool_leaves = [l for l in jax.tree_util.tree_leaves(
                       [cb._pool[i] for i in cb._paged_layers])
                   if getattr(l, "ndim", 0) == 4]
    if not pool_leaves:
        return []
    leaf = pool_leaves[0]
    # below the sublane minimum the batcher refuses to construct on
    # real hardware — interpret mode on CPU CI takes any block, but
    # no Mosaic kernel would ever launch with this one, so there is
    # no geometry to audit
    if cb.block < mosaic_sublane_min(leaf.dtype):
        return []
    hkv, hd = int(leaf.shape[1]), int(leaf.shape[-1])
    g = max(1, int(cb.gen._blocks[cb._paged_layers[0]].n_heads) // hkv)
    dtype = leaf.dtype
    launches = _paged.audit_launch(
        hd, cb.block, g=_paged._resolve_block_g(g, hd, dtype),
        dtype=dtype, nbm=cb.max_blocks,
        q_dtype=cb.gen._model_dtype(), hkv=hkv)

    findings = []
    per_rule = {}
    for launch in launches:
        for f in audit_kernel_launch(launch, vmem_kib=vmem_kib):
            per_rule.setdefault(f.rule, f)
    for rule, f in sorted(per_rule.items()):
        findings.append(Finding(
            "VD705", f.severity, name,
            "paged-pool launch geometry (block=%d, resolved through "
            "config > tuner > default) fails %s: %s"
            % (cb.block, rule, f.message),
            hint=f.hint or "pin root.common.serve.paged_block to an "
                 "audited size, or re-bake the tuner winner"))
    return findings


def audit_prefill_pass(gen, segment=0, name=None):
    """VD700/VD702/VD703 over the segmented-prefill chunk pass — the
    OTHER jaxpr serving dispatches per admission
    (``LMGenerator._prefill_resume_fn``: the resume-from-cursor math
    both segmented admission and the prefix-cache compute skip run).
    ``segment`` sizes the chunk bucket (0 = one full-prompt pass)."""
    name = name or "prefill[%s]" % (getattr(gen, "weight_dtype", None)
                                    or "bf16")
    kb = gen._bucket(int(segment) or gen.max_len, gen.max_len)
    caches = jax.eval_shape(
        lambda: gen._init_caches(1, gen._model_dtype()))
    args = (_abstract(gen.params), caches,
            jax.ShapeDtypeStruct((1, kb), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32)) + tuple(
                jax.ShapeDtypeStruct((), jnp.int32)
                for _ in gen._valid(0))
    try:
        closed = jax.make_jaxpr(gen._prefill_resume_fn(kb))(*args)
    except Exception as e:  # noqa: BLE001 — the failure IS the finding
        return [Finding(
            "VD702", ERROR, name,
            "segmented-prefill pass failed to trace abstractly: "
            "%s: %s" % (type(e).__name__, e),
            hint="the chunk pass must be traceable over "
                 "ShapeDtypeStructs")]
    return _scan_jaxpr(closed, name, params=gen.params,
                       scheme=getattr(gen, "weight_dtype", None))


#: the standard serving matrix ``lint_serving`` sweeps: weight scheme x
#: pool layout x speculative ticks — the same variants the chaos gates
#: exercise dynamically (tools/serve_loadtest.py legs).  Unsupported
#: combos on a given model (w4a8 under a model-axis mesh, quantized
#: MoE) are skipped, not findings — serving refuses them too.
DEFAULT_VARIANTS = (
    ("bf16/dense", {}),
    ("bf16/dense/spec", {"speculative_k": 4}),
    ("bf16/paged", {"paged": True}),
    ("int8/dense", {"weights": "int8"}),
    ("int8/paged-q8", {"weights": "int8", "cache_dtype": "int8",
                       "paged": True}),
    ("w4a8/dense", {"weights": "w4a8"}),
)


def lint_serving(trainer, max_len, variants=None, slots=2,
                 pool_tokens=None, prefill_segment=8, vmem_kib=None):
    """VD7xx audit of the real serving surface: build each variant's
    generator + batcher exactly as serving would (quantized weight
    copies ARE made — the same host-side construction work the engine
    does; no tick or prefill ever dispatches) and audit its tick, plus
    one segmented-prefill pass per weight scheme.  Returns sorted
    Findings."""
    from veles_tpu.models.generate import (ContinuousBatcher,
                                           LMGenerator,
                                           PagedContinuousBatcher)
    findings = []
    prefilled = set()
    for tag, spec in (variants or DEFAULT_VARIANTS):
        kwargs = dict(spec)
        paged = kwargs.pop("paged", False)
        spec_k = kwargs.pop("speculative_k", 0)
        try:
            gen = LMGenerator(trainer, max_len, **kwargs)
            if paged:
                cb = PagedContinuousBatcher(
                    gen, slots=slots,
                    pool_tokens=pool_tokens or slots * gen.max_len,
                    prefill_segment=prefill_segment)
            else:
                cb = ContinuousBatcher(
                    gen, slots=slots, speculative_k=spec_k,
                    prefill_segment=prefill_segment)
        except (TypeError, ValueError):
            continue      # variant unsupported on this model
        findings.extend(audit_decode_tick(cb, vmem_kib=vmem_kib,
                                          name="decode[%s]" % tag))
        scheme = kwargs.get("weights")
        if scheme not in prefilled:
            prefilled.add(scheme)
            findings.extend(audit_prefill_pass(
                gen, segment=prefill_segment,
                name="prefill[%s]" % (scheme or "bf16")))
    return sort_findings(findings)
