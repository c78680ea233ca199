"""Jit-staging auditor: abstract tracing of a staged step function.

A workflow's hot loop is staged into one jitted step (workflow.py design
note), so anything host-side that leaks into that step is a silent 100×
perf bug: a callback forces a device→host sync every iteration, a
weak-typed python scalar in the signature recompiles on promotion, and a
carry whose dtype/shape drifts between iterations recompiles every single
step.  All three are visible in the jaxpr *without running anything* —
``jax.make_jaxpr`` over ``jax.ShapeDtypeStruct`` inputs traces abstractly
(the pattern of parallel/pipeline.py's ``jax.eval_shape`` probe and
nn_units' abstract optimizer-slot spec).

Rule catalog (docs/static_analysis.md):

========  ========  =====================================================
VJ100     error     the step failed to trace abstractly at all
VJ101     error     host callback primitive in the hot path
                    (``debug_print`` / ``pure_callback`` / ``io_callback``)
VJ102     warning   weak-typed input: a python scalar leaked into the
                    step signature (promotion → recompile hazard)
VJ103     error     carry aval drift: an output that feeds the next
                    iteration differs in shape/dtype/weak-type from the
                    input it replaces (recompile every iteration)
========  ========  =====================================================
"""

import jax

from veles_tpu.analysis.findings import ERROR, WARNING, Finding

#: primitive names that force a device→host round trip mid-step
_HOST_SYNC_PRIMS = ("outfeed", "infeed")


def _sub_jaxprs(value):
    """Nested jaxprs hiding in an eqn's params (pjit/scan/while carry a
    ClosedJaxpr under 'jaxpr', cond a list under 'branches', custom
    primitives stash them in dicts — e.g. keyed branch/function tables),
    so a container-valued param never hides a VJ101 host callback."""
    if hasattr(value, "jaxpr"):          # ClosedJaxpr
        return [value.jaxpr]
    if hasattr(value, "eqns"):           # bare Jaxpr
        return [value]
    if isinstance(value, dict):
        out = []
        for v in value.values():
            out.extend(_sub_jaxprs(v))
        return out
    if isinstance(value, (list, tuple)):
        out = []
        for v in value:
            out.extend(_sub_jaxprs(v))
        return out
    return []


#: primitive names as the installed JAX (0.9.0) stages them -> the
#: name the jaxpr-matching auditors and their rule texts know them by.
#: ``jax.debug.print``/``jax.debug.callback`` are a host callback
#: whichever primitive carries them (``debug_print`` now,
#: ``debug_callback`` before), and the primitive of a nested ``jax.jit``
#: is ``jit`` now, ``pjit`` before.  THE one table: every auditor reads
#: names through :func:`primitive_name`.
_PRIMITIVE_ALIASES = {"debug_print": "debug_callback", "jit": "pjit"}


def primitive_name(eqn):
    """Canonical primitive name of a jaxpr equation."""
    name = eqn.primitive.name
    return _PRIMITIVE_ALIASES.get(name, name)


def iter_primitives(jaxpr):
    """Yield every (primitive_name, eqn) in ``jaxpr``, recursing into
    sub-jaxprs of higher-order primitives.  Names are canonical
    (:func:`primitive_name`), so a rule matches the operation and not
    this JAX's spelling of it."""
    for eqn in jaxpr.eqns:
        yield primitive_name(eqn), eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_primitives(sub)


def _aval_str(aval):
    weak = ", weak" if getattr(aval, "weak_type", False) else ""
    return "%s[%s]%s" % (getattr(aval, "dtype", "?"),
                         ",".join(map(str, getattr(aval, "shape", ()))),
                         weak)


def _avals_equal(a, b):
    return (getattr(a, "shape", None) == getattr(b, "shape", None)
            and getattr(a, "dtype", None) == getattr(b, "dtype", None)
            and bool(getattr(a, "weak_type", False))
            == bool(getattr(b, "weak_type", False)))


def audit_step(fn, args=(), *, carry_argnums=(), name="step"):
    """Abstractly trace ``fn(*args)`` and return staging Findings.

    ``args`` may be concrete arrays, pytrees, or ``jax.ShapeDtypeStruct``
    specs — tracing never touches a device.  ``carry_argnums`` names the
    positional args that the step's outputs replace on the next iteration
    (e.g. ``(0, 1, 2)`` for ``(params, velocity, acc) -> (params,
    velocity, acc)``); their avals are compared against the outputs for
    the VJ103 recompile-every-iteration hazard."""
    findings = []
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as e:  # noqa: BLE001 — any trace failure is the finding
        return [Finding(
            "VJ100", ERROR, name,
            "staged step failed to trace abstractly: %s: %s"
            % (type(e).__name__, e),
            hint="the step must be traceable with abstract inputs — "
                 "no data-dependent python control flow or host state")]

    # ---- VJ101: host callbacks / host syncs in the hot path
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
            "VJ101", ERROR, name,
            "host callback in the hot path (%s): every iteration "
            "round-trips device -> host, serializing the XLA stream"
            % what,
            hint="move host work (printing, logging, numpy) outside the "
                 "staged step; fetch stats from the step's outputs "
                 "instead"))

    # ---- VJ102: weak-typed inputs (python scalars in the signature)
    for i, aval in enumerate(closed.in_avals):
        if getattr(aval, "weak_type", False):
            findings.append(Finding(
                "VJ102", WARNING, name,
                "input leaf %d is weak-typed (%s): a python scalar "
                "leaked into the step signature — promotion rules "
                "change downstream dtypes and a later strongly-typed "
                "call recompiles" % (i, _aval_str(aval)),
                hint="wrap host scalars before the call, e.g. "
                     "jnp.float32(x) / jnp.asarray(x, dtype)"))

    # ---- VJ103: carry aval drift across iterations
    if carry_argnums:
        flat_args = [jax.tree_util.tree_leaves(a) for a in args]
        offsets = []
        pos = 0
        for leaves in flat_args:
            offsets.append(pos)
            pos += len(leaves)
        expected = []
        for argnum in carry_argnums:
            n = len(flat_args[argnum])
            expected.extend(
                closed.in_avals[offsets[argnum]:offsets[argnum] + n])
        outs = closed.out_avals
        if len(outs) != len(expected):
            findings.append(Finding(
                "VJ103", ERROR, name,
                "carry structure mismatch: the step returns %d output "
                "leaves but the carry args hold %d — the next "
                "iteration cannot reuse the compiled step"
                % (len(outs), len(expected)),
                hint="return exactly the updated carry args (same "
                     "pytree structure) from the step"))
        else:
            for i, (inp, out) in enumerate(zip(expected, outs)):
                if _avals_equal(inp, out):
                    continue
                findings.append(Finding(
                    "VJ103", ERROR, name,
                    "carry leaf %d drifts across iterations: fed in as "
                    "%s, comes out as %s — every iteration recompiles "
                    "the step" % (i, _aval_str(inp), _aval_str(out)),
                    hint="pin the carry dtype (e.g. x.astype(...) "
                         "before returning, or make the initial carry "
                         "match the steady-state dtype)"))
    return findings
