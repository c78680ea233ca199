"""Numerics, determinism & Pallas-kernel auditor for the staged step.

The platform's thesis is "catch model-definition mistakes before chip
time" (docs/static_analysis.md): PR 1-2 covered graph/staging (VG/VJ)
and sharding/HBM (VS/VM).  The remaining class of silent, statically
decidable failures is NUMERICAL: a ``log`` fed a value that can reach
zero NaNs the loss on step 40k, a bf16 sum over a long axis quietly
loses 40 dB of signal, two draws from one PRNG key correlate every
dropout mask with the data order, and a hand-tiled Pallas kernel with
a 100-row block pays a 28% retile tax on every copy.  All of them are
visible ahead of time — the jaxpr of the staged step traces over
abstract ``ShapeDtypeStruct`` inputs (no device arrays, the same
discipline as ``sharding_audit``), and the kernels' launch geometry is
plain arithmetic over block shapes.

Three rule families (catalog: docs/static_analysis.md):

========  ========  =====================================================
VN400     warning   unguarded ``log``/``div``/``rsqrt``: the operand's
                    dataflow cone reaches a step input with no
                    positivity guard (eps add, ``maximum`` with a
                    positive constant, ``exp``, squaring...) on the way
VN401     warning   unguarded ``exp``: the operand is not bounded above
                    (no ``minimum``/``clamp``/``x - max(x)`` guard) —
                    overflows to inf for inputs past ~88 (f32)
VN402     warning   ``log(softmax(x))`` instead of ``log_softmax``:
                    the exp->normalize->log round trip underflows to
                    ``log(0) = -inf`` exactly where the model is most
                    confident
VN403     warning   sum/mean accumulation in a <=16-bit dtype over a
                    large reduced axis — bf16 has 8 mantissa bits, the
                    tail of a long sum is rounded away
VN404     warning   integer-narrowing cast whose operand is not
                    provably in range (no clamp) — silent wraparound
VR500     warning   ``jax.random`` key reuse: one key (or two
                    ``fold_in`` derivations with the same counter)
                    consumed by two random draws — the draws correlate
VR501     warning   named prng streams with colliding seeds in the
                    global registry (veles_tpu.prng) — two "independent"
                    streams replay each other
VR502     error     host ``numpy.random`` call in staged code: it runs
                    ONCE at trace time and bakes constants — every step
                    reuses the same "random" numbers
VR503     warning   scatter-add on float outputs with possibly-duplicate
                    indices — accumulation order is unspecified, results
                    differ run to run on parallel backends
VP600     warning   Pallas block shape not aligned to the dtype's native
                    TPU tile (8/16/32 sublanes x 128 lanes) — Mosaic
                    retiles every VMEM copy
VP601     warning   grid axis does not divide its array length and the
                    kernel neither pads nor masks the tail — the last
                    block reads/writes out of bounds or garbage
VP602     error     static per-kernel VMEM footprint (refs double-
                    buffered + accumulators) exceeds the per-core VMEM
                    budget — the kernel will not fit
========  ========  =====================================================

Everything here is static: ``jax.make_jaxpr`` over abstract values for
the VN/VR rules (asserted dispatch-free in tests), registry inspection
for VR501, an AST scan of the step's own source for VR502, and pure
block-geometry arithmetic for VP6xx.
"""

import ast
import inspect
import textwrap

import jax
import numpy as np

from veles_tpu.analysis.findings import ERROR, WARNING, Finding
from veles_tpu.analysis.staging import _sub_jaxprs, primitive_name

#: per-core VMEM budget the VP602 estimate is judged against, KiB
#: (~16 MiB on current TPU generations — pallas guide "Memory Spaces")
DEFAULT_VMEM_KIB = 16 * 1024

#: reduced-element count above which a <=16-bit sum is VN403 (an
#: 8-mantissa-bit bf16 sum starts dropping ulps well before this; 1024
#: keeps small per-tile reductions out of the findings)
LOW_PRECISION_REDUCE_ELEMS = 1024

# ---------------------------------------------------------------------------
# VN4xx: value-range dataflow over the jaxpr
# ---------------------------------------------------------------------------
# Each var carries a small flag set:
#   POS      provably > 0 everywhere
#   NONNEG   provably >= 0
#   UB       bounded above by a finite static value (exp-safe)
#   SOFTMAX  the output of an exp/sum-exp normalization (feeds VN402)
POS, NONNEG, UB, SOFTMAX = "pos", "nonneg", "ub", "softmax"
#: strictly below 1 (and >= 0): ``pow(b, t)`` with literal 0 < b < 1 and
#: t > 0 — so ``1 - b**t`` is provably positive (adam bias correction)
LT1 = "lt1"


def _float_dtype(dt):
    """jnp.issubdtype, not np: bf16/f8 are ml_dtypes extension types
    (numpy kind 'V') that np.issubdtype refuses to call floating."""
    import jax.numpy as jnp
    return jnp.issubdtype(np.dtype(dt), jnp.floating)

#: jax's OWN numerically-stable kernels, recognized by the pjit name
#: their jax.nn/jnp implementations stage under.  Their internals are
#: deliberately stable (softplus' jvp is exp(x - softplus(x)) <= 1,
#: provable only with function-level bounds no flag lattice carries) —
#: the auditor's job is the MODEL's numerics, not re-verifying jax's,
#: so VN400/VN401 skip findings whose innermost named scope is one of
#: these.
_STABLE_IMPL_CTX = frozenset((
    "softplus", "logaddexp", "logaddexp2", "logsumexp", "log_sigmoid",
    "sigmoid", "expit", "log1p", "xlogy", "xlog1py", "entr",
    "log_softmax", "_softmax", "softmax", "erf_inv", "ndtri",
))

#: ops that forward their operand's value range unchanged (the identity
#: chain both the flag propagation and the origin walk see through)
_IDENTITY_PRIMS = frozenset((
    "broadcast_in_dim", "reshape", "transpose", "squeeze", "expand_dims",
    "copy", "stop_gradient", "slice", "rev", "gather", "dynamic_slice",
    "optimization_barrier", "reduce_precision", "sharding_constraint",
))


def _lit_val(v):
    """Scalar value of a Literal / unit-sized constant, else None."""
    val = getattr(v, "val", None)
    if val is None:
        return None
    try:
        arr = np.asarray(val)
    except Exception:  # noqa: BLE001 — opaque const (e.g. a prng key)
        return None
    if arr.size != 1 or not np.issubdtype(arr.dtype, np.number):
        return None
    return float(arr.reshape(()))


def _lit_flags(v):
    x = _lit_val(v)
    if x is None:
        val = getattr(v, "val", None)
        if val is None:
            return frozenset()
        try:
            arr = np.asarray(val)
        except Exception:  # noqa: BLE001
            return frozenset()
        if arr.size == 0 or not np.issubdtype(arr.dtype, np.number):
            return frozenset()
        flags = set()
        if np.isfinite(arr).all():
            flags.add(UB)
            if (arr > 0).all():
                flags.update((POS, NONNEG))
            elif (arr >= 0).all():
                flags.add(NONNEG)
        return frozenset(flags)
    flags = set()
    if np.isfinite(x):
        flags.add(UB)
    if x > 0:
        flags.update((POS, NONNEG))
    elif x == 0:
        flags.add(NONNEG)
    return frozenset(flags)


class _NumericsScan(object):
    """One recursive walk of a closed jaxpr that runs every VN/VR jaxpr
    rule.  Sub-jaxprs under pjit/custom-vjp/remat inherit their caller's
    flags and key classes; scan/while/cond bodies are walked with
    unknown inputs (conservative: their guards are still seen locally,
    their findings still surface)."""

    def __init__(self, name, reduce_elems=LOW_PRECISION_REDUCE_ELEMS):
        self.name = name
        self.reduce_elems = reduce_elems
        self.findings = []
        self._fired = set()          # (rule, detail-key) dedup
        # VR500: key-equivalence classes -> number of consuming draws
        self._key_uses = {}
        self._key_sources = {}       # class -> human description
        self._fold_memo = {}         # (class, counter-token) -> class
        self._next_class = [0]
        # scalar constant folding: var -> float value, for values that
        # are pure literal arithmetic (jnp.var's ``n - ddof``, adam's
        # hyper scalars) — lets the div guard see through them
        self._consts = {}

    # -- bookkeeping --------------------------------------------------------
    def _emit(self, rule, severity, message, hint="", key=None):
        if (rule, key) in self._fired:
            return
        self._fired.add((rule, key))
        self.findings.append(Finding(rule, severity, self.name, message,
                                     hint=hint))

    @staticmethod
    def _is_float(aval):
        dt = getattr(aval, "dtype", None)
        return dt is not None and _float_dtype(dt)

    @staticmethod
    def _is_key(aval):
        dt = getattr(aval, "dtype", None)
        if dt is None:
            return False
        try:
            return jax.dtypes.issubdtype(dt, jax.dtypes.prng_key)
        except Exception:  # noqa: BLE001 — older dtype objects
            return "key" in str(dt)

    def _new_key_class(self, desc):
        self._next_class[0] += 1
        c = self._next_class[0]
        self._key_sources[c] = desc
        return c

    # -- entry point --------------------------------------------------------
    def run(self, closed, input_flags=None):
        flags = {}
        keys = {}
        for v in closed.jaxpr.constvars:
            if self._is_key(v.aval):
                keys[v] = self._new_key_class("a captured key constant")
        for i, v in enumerate(closed.jaxpr.invars):
            if self._is_key(v.aval):
                keys[v] = self._new_key_class("input leaf %d" % i)
            if input_flags and i in input_flags:
                flags[v] = frozenset(input_flags[i])
        self._walk(closed.jaxpr, flags, keys)
        for cls, n in sorted(self._key_uses.items()):
            if n < 2:
                continue
            self._emit(
                "VR500", WARNING,
                "PRNG key reuse: %s feeds %d independent random draws — "
                "the draws are identical/correlated, not independent"
                % (self._key_sources.get(cls, "a key"), n),
                hint="split or fold_in a fresh key per draw "
                     "(jax.random.split / fold_in with distinct "
                     "counters); veles_tpu.prng streams advance a "
                     "counter per draw for exactly this reason",
                key=cls)
        return self.findings

    # -- flag/key lookup helpers -------------------------------------------
    def _get(self, table, v, default=frozenset()):
        if hasattr(v, "val"):        # Literal
            return _lit_flags(v) if table is not None else None
        return table.get(v, default)

    def _kget(self, keys, v):
        if hasattr(v, "val"):
            return None
        return keys.get(v)

    def _cval(self, v):
        """Known scalar value of ``v``: a Literal, or a var the
        constant-folding pass resolved."""
        if hasattr(v, "val"):
            return _lit_val(v)
        return self._consts.get(v)

    #: scalar arithmetic the const-folding pass evaluates (comparisons
    #: fold to 1.0/0.0 so a constant `where` predicate — jnp.var's
    #: ddof-count guard — resolves to its live branch)
    _CONST_OPS = {
        "add": lambda a, b: a + b, "sub": lambda a, b: a - b,
        "mul": lambda a, b: a * b, "max": max, "min": min,
        "div": lambda a, b: (a / b) if b else None,
        "neg": lambda a: -a, "abs": abs,
        "pow": lambda a, b: a ** b if a > 0 else None,
        "gt": lambda a, b: float(a > b), "lt": lambda a, b: float(a < b),
        "ge": lambda a, b: float(a >= b),
        "le": lambda a, b: float(a <= b),
        "eq": lambda a, b: float(a == b),
        "ne": lambda a, b: float(a != b),
    }

    def _fold_const(self, eqn):
        """Record (and return) the outvar's value when every operand is
        a known scalar — pure literal arithmetic only."""
        prim = primitive_name(eqn)
        if prim in ("convert_element_type", "broadcast_in_dim",
                    "reshape", "squeeze", "copy", "stop_gradient"):
            cv = self._cval(eqn.invars[0])
        elif prim in self._CONST_OPS:
            vals = [self._cval(v) for v in eqn.invars]
            if any(x is None for x in vals):
                return None
            try:
                cv = self._CONST_OPS[prim](*vals)
            except Exception:  # noqa: BLE001 — overflow etc.
                return None
        else:
            return None
        if cv is not None:
            for ov in eqn.outvars:
                self._consts[ov] = cv
        return cv

    @staticmethod
    def _val_flags(x):
        flags = set()
        if np.isfinite(x):
            flags.add(UB)
        if x > 0:
            flags.update((POS, NONNEG))
        elif x == 0:
            flags.add(NONNEG)
        return frozenset(flags)

    # -- the walk -----------------------------------------------------------
    def _walk(self, jaxpr, flags, keys, ctx=""):
        defs = {}
        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                defs[ov] = eqn
        for eqn in jaxpr.eqns:
            self._visit(eqn, flags, keys, defs, ctx)

    def _origin(self, v, defs):
        """Walk back through value-preserving ops (and ``max`` with a
        literal) to the semantic source var — lets the ``exp(x - max(x))``
        pattern match through broadcast/stop_gradient glue."""
        seen = 0
        while seen < 64:
            seen += 1
            if hasattr(v, "val"):    # Literal: its own origin
                return v
            eqn = defs.get(v)
            if eqn is None:
                return v
            prim = primitive_name(eqn)
            if prim in _IDENTITY_PRIMS or prim == "convert_element_type":
                v = eqn.invars[0]
                continue
            if prim == "max":
                non_lit = [iv for iv in eqn.invars
                           if not hasattr(iv, "val")]
                if len(non_lit) == 1:
                    v = non_lit[0]
                    continue
            return v
        return v

    def _chain_prim(self, v, defs, prim_names, depth=8):
        """The defining eqn of ``v``, looking through identity glue, if
        its primitive is in ``prim_names``."""
        for _ in range(depth):
            if hasattr(v, "val"):
                return None
            eqn = defs.get(v)
            if eqn is None:
                return None
            prim = primitive_name(eqn)
            if prim in prim_names:
                return eqn
            if prim in _IDENTITY_PRIMS or prim == "convert_element_type":
                v = eqn.invars[0]
                continue
            if prim == "max":
                # ``max(-inf, reduce_max(x))`` — the empty-reduction
                # guard every jax softmax lowering inserts
                non_lit = [iv for iv in eqn.invars
                           if not hasattr(iv, "val")]
                if len(non_lit) == 1:
                    v = non_lit[0]
                    continue
            return None
        return None

    def _visit(self, eqn, flags, keys, defs, ctx=""):
        prim = primitive_name(eqn)
        get = lambda v: self._get(flags, v)  # noqa: E731

        # ---- recurse into sub-jaxprs -----------------------------------
        if prim in ("pjit", "closed_call", "core_call", "remat",
                    "remat2", "checkpoint", "custom_jvp_call",
                    "custom_vjp_call", "custom_vjp_call_jaxpr",
                    "custom_jvp_call_jaxpr"):
            sub = None
            for pname in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                cj = eqn.params.get(pname)
                if cj is not None:
                    sub = getattr(cj, "jaxpr", cj)
                    break
            if sub is not None and hasattr(sub, "eqns"):
                in_flags, in_keys = {}, {}
                n = min(len(sub.invars), len(eqn.invars))
                for iv, ov in zip(eqn.invars[-n:] if len(eqn.invars) > n
                                  else eqn.invars, sub.invars):
                    in_flags[ov] = get(iv)
                    cv = self._cval(iv)
                    if cv is not None:
                        self._consts[ov] = cv
                    kc = self._kget(keys, iv)
                    if kc is not None:
                        in_keys[ov] = kc
                    elif self._is_key(ov.aval):
                        in_keys[ov] = self._new_key_class(
                            "a key entering %s" % prim)
                # unnamed call wrappers (custom_jvp_call, remat) keep
                # the enclosing scope's name — softplus's jvp body must
                # still read as softplus
                self._walk(sub, in_flags, in_keys,
                           ctx=str(eqn.params.get("name") or ctx))
                for ov, sv in zip(eqn.outvars, sub.outvars):
                    flags[ov] = self._get(in_flags, sv)
                    cv = self._cval(sv)
                    if cv is not None:
                        self._consts[ov] = cv
                    if self._is_key(ov.aval):
                        kc = self._kget(in_keys, sv)
                        keys[ov] = (kc if kc is not None
                                    else self._new_key_class(
                                        "a key from %s" % prim))
                return
            # unknown call structure: fall through to generic handling

        if prim == "scan":
            # consts and per-iteration xs slices keep their caller
            # flags; the CARRY enters unknown (a sound fixpoint skip:
            # body-derived flags then hold for any carry).  Body outvar
            # flags map back out — stacked ys flags hold elementwise,
            # so a `maximum(l, eps)` residual stays provably positive
            # into the backward scan (the online-softmax guard).
            cj = eqn.params.get("jaxpr")
            sub = getattr(cj, "jaxpr", cj)
            if sub is not None and hasattr(sub, "eqns"):
                nc = int(eqn.params.get("num_consts", 0))
                ncar = int(eqn.params.get("num_carry", 0))
                in_flags, in_keys = {}, {}
                for i, (iv, ov) in enumerate(zip(eqn.invars,
                                                 sub.invars)):
                    carry = nc <= i < nc + ncar
                    in_flags[ov] = frozenset() if carry else get(iv)
                    if not carry:
                        cv = self._cval(iv)
                        if cv is not None:
                            self._consts[ov] = cv
                    if self._is_key(ov.aval):
                        kc = None if carry else self._kget(keys, iv)
                        in_keys[ov] = (kc if kc is not None else
                                       self._new_key_class(
                                           "a key entering scan"))
                self._walk(sub, in_flags, in_keys, ctx=ctx)
                for ov, sv in zip(eqn.outvars, sub.outvars):
                    flags[ov] = self._get(in_flags, sv)
            else:
                for value in eqn.params.values():
                    for s in _sub_jaxprs(value):
                        self._walk(s, {}, {}, ctx=ctx)
            for ov in eqn.outvars:
                if self._is_key(ov.aval):
                    keys[ov] = self._new_key_class("a key from scan")
            return
        if prim == "cond":
            # each branch binds the operands (eqn.invars[1:]) directly
            # — caller flags hold inside; outputs take the intersection
            # over branches (grad-accum wraps the whole optimizer
            # update in a cond, and adam's step-counter vouching must
            # survive it)
            branches = eqn.params.get("branches", ())
            out_sets = None
            for br in branches:
                sub = getattr(br, "jaxpr", br)
                if not hasattr(sub, "eqns"):
                    continue
                in_flags, in_keys = {}, {}
                for iv, ov in zip(eqn.invars[1:], sub.invars):
                    in_flags[ov] = get(iv)
                    cv = self._cval(iv)
                    if cv is not None:
                        self._consts[ov] = cv
                    kc = self._kget(keys, iv)
                    if kc is not None:
                        in_keys[ov] = kc
                    elif self._is_key(ov.aval):
                        in_keys[ov] = self._new_key_class(
                            "a key entering cond")
                self._walk(sub, in_flags, in_keys, ctx=ctx)
                brf = [set(self._get(in_flags, sv))
                       for sv in sub.outvars]
                out_sets = (brf if out_sets is None else
                            [a & b for a, b in zip(out_sets, brf)])
            for i, ov in enumerate(eqn.outvars):
                if out_sets is not None and i < len(out_sets):
                    flags[ov] = frozenset(out_sets[i] - {SOFTMAX})
                if self._is_key(ov.aval):
                    keys[ov] = self._new_key_class("a key from cond")
            return
        if prim == "while":
            # the carry loops — bodies run with unknown inputs (guards
            # inside them are still local, hazards still surface)
            for value in eqn.params.values():
                for sub in _sub_jaxprs(value):
                    self._walk(sub, {}, {}, ctx=ctx)
            for ov in eqn.outvars:
                if self._is_key(ov.aval):
                    keys[ov] = self._new_key_class("a key from while")
            return

        # ---- scalar constant folding (jnp.var's n - ddof, adam betas)
        if self._fold_const(eqn) is not None:
            vf = self._val_flags(self._cval(eqn.outvars[0]))
            for ov in eqn.outvars:
                flags[ov] = vf
            return

        # ---- VR5xx: key derivation and consumption ---------------------
        if prim == "random_fold_in":
            src = self._kget(keys, eqn.invars[0])
            if src is None:
                src = self._new_key_class("an untracked key")
                if not hasattr(eqn.invars[0], "val"):
                    keys[eqn.invars[0]] = src
            counter = eqn.invars[1]
            tok = (_lit_val(counter) if hasattr(counter, "val")
                   else id(counter))
            cls = self._fold_memo.get((src, tok))
            if cls is None:
                cls = self._new_key_class(
                    "fold_in(%s, %s)" % (self._key_sources.get(src, "?"),
                                         tok if hasattr(counter, "val")
                                         else "<traced>"))
                self._fold_memo[(src, tok)] = cls
            keys[eqn.outvars[0]] = cls
            return
        if prim in ("random_seed", "random_split"):
            for ov in eqn.outvars:
                keys[ov] = self._new_key_class(prim)
            return
        if prim == "random_wrap":
            kc = self._kget(keys, eqn.invars[0])
            keys[eqn.outvars[0]] = (kc if kc is not None
                                    else self._new_key_class("random_wrap"))
            return
        if prim in ("random_bits", "threefry2x32"):
            kc = self._kget(keys, eqn.invars[0])
            if kc is None and not hasattr(eqn.invars[0], "val"):
                kc = keys.setdefault(eqn.invars[0],
                                     self._new_key_class("a raw key"))
            if kc is not None:
                self._key_uses[kc] = self._key_uses.get(kc, 0) + 1
            return
        if self._is_key(getattr(eqn.outvars[0], "aval", None)) \
                and prim in _IDENTITY_PRIMS:
            # slice/squeeze of a split-key array: each distinct slice is
            # a distinct subkey — key by the slice geometry
            src = self._kget(keys, eqn.invars[0])
            if src is not None:
                geo = (prim,
                       str(eqn.params.get("start_indices", "")),
                       str(eqn.params.get("limit_indices", "")))
                cls = self._fold_memo.get((src, geo))
                if cls is None:
                    cls = (src if prim not in ("slice", "dynamic_slice")
                           else self._new_key_class("a split subkey"))
                    self._fold_memo[(src, geo)] = cls
                keys[eqn.outvars[0]] = cls
            return

        # ---- VR503: scatter-add on floats ------------------------------
        if prim in ("scatter-add", "scatter_add"):
            out_aval = eqn.outvars[0].aval
            dn = eqn.params.get("dimension_numbers")
            unique = bool(eqn.params.get("unique_indices", False))
            batched = bool(getattr(dn, "operand_batching_dims", ()))
            # the transpose of jnp.take (ctx "_take") is the embedding-
            # table gradient: XLA-generated, sequential (deterministic)
            # on TPU, and unavoidable — only handwritten accumulating
            # scatters are actionable
            take_bwd = ctx in ("_take", "take", "take_along_axis")
            if self._is_float(out_aval) and not unique and not batched \
                    and not take_bwd:
                self._emit(
                    "VR503", WARNING,
                    "scatter-add accumulates %s values at "
                    "possibly-duplicate indices — float addition is not "
                    "associative, so the result depends on reduction "
                    "order (nondeterministic on parallel backends)"
                    % out_aval.dtype,
                    hint="sort/segment the indices (jax.ops.segment_sum "
                         "with sorted ids), accumulate in a wider dtype, "
                         "or mark .at[].add(..., unique_indices=True) "
                         "when duplicates are impossible",
                    key=("scatter", str(out_aval.dtype)))
            return

        # ---- VN400/401/402: guarded-transcendental checks --------------
        if prim == "log":
            x = eqn.invars[0]
            fx = get(x)
            softmax_src = SOFTMAX in fx or self._is_softmax_chain(x, defs)
            if softmax_src:
                self._emit(
                    "VN402", WARNING,
                    "log(softmax(x)): the exp-normalize-log round trip "
                    "underflows to log(0) = -inf exactly where the model "
                    "is most confident",
                    hint="use jax.nn.log_softmax (computes x - "
                         "logsumexp(x) directly)",
                    key="log_softmax")
            elif POS not in fx and ctx not in _STABLE_IMPL_CTX:
                self._emit(
                    "VN400", WARNING,
                    "log of a value not provably positive "
                    "(operand %s) — log(0) = -inf, log(<0) = nan"
                    % _short_aval(x),
                    hint="clamp first (jnp.log(jnp.maximum(x, eps))) or "
                         "restructure so positivity is guaranteed "
                         "(exp, squaring, eps add)",
                    key=("log", id(eqn)))
            flags[eqn.outvars[0]] = frozenset(
                ({UB} if UB in fx else set())
                | ({NONNEG} if self._sum_exp_under_max(x, defs) else set()))
            return
        if prim == "rsqrt":
            fx = get(eqn.invars[0])
            if POS not in fx and ctx not in _STABLE_IMPL_CTX:
                self._emit(
                    "VN400", WARNING,
                    "rsqrt of a value not provably positive "
                    "(operand %s) — rsqrt(0) = inf, rsqrt(<0) = nan"
                    % _short_aval(eqn.invars[0]),
                    hint="add an eps before the rsqrt "
                         "(jax.lax.rsqrt(x + 1e-6)), the layer-norm "
                         "idiom",
                    key=("rsqrt", id(eqn)))
            flags[eqn.outvars[0]] = frozenset((POS, NONNEG)) \
                if POS in fx else frozenset((NONNEG,))
            return
        if prim == "div":
            num, den = eqn.invars
            fden = get(den)
            cv = self._cval(den)
            if self._is_float(eqn.outvars[0].aval) and POS not in fden \
                    and not (cv is not None and cv != 0.0) \
                    and ctx not in _STABLE_IMPL_CTX:
                self._emit(
                    "VN400", WARNING,
                    "division by a value not provably nonzero "
                    "(denominator %s) — x/0 = inf/nan propagates "
                    "through the whole step" % _short_aval(den),
                    hint="guard the denominator "
                         "(jnp.maximum(d, 1) for counts, + eps for "
                         "norms) — the loss already divides by "
                         "maximum(n_valid, 1)",
                    key=("div", id(eqn)))
            fnum = get(num)
            out = set()
            if POS in fnum and POS in fden:
                out.update((POS, NONNEG))
            elif NONNEG in fnum and POS in fden:
                out.add(NONNEG)
            # exp(x)/sum(exp(x)) — the softmax shape: in (0, 1], so
            # also bounded above (a softmax OUTPUT layer feeding the
            # loss keeps downstream exps guarded)
            if self._softmax_div(num, den, defs):
                out.update((SOFTMAX, UB, POS, NONNEG))
            flags[eqn.outvars[0]] = frozenset(out)
            return
        if prim == "exp":
            x = eqn.invars[0]
            fx = get(x)
            if UB not in fx and not self._sub_max_guard(x, defs) \
                    and ctx not in _STABLE_IMPL_CTX:
                self._emit(
                    "VN401", WARNING,
                    "exp of a value not bounded above "
                    "(operand %s) — overflows to inf past ~88 (f32) / "
                    "~11 (bf16 range is wide but the sum that usually "
                    "follows is not)" % _short_aval(x),
                    hint="subtract the running max first (the "
                         "online-softmax identity exp(x - max(x))), or "
                         "clamp the exponent",
                    key=("exp", id(eqn)))
            flags[eqn.outvars[0]] = frozenset(
                {POS, NONNEG} | ({UB} if UB in fx else set()))
            return

        # ---- VN403: low-precision accumulation -------------------------
        if prim == "dot_general":
            out_aval = eqn.outvars[0].aval
            dt = getattr(out_aval, "dtype", None)
            if dt is not None and _float_dtype(dt) \
                    and np.dtype(dt).itemsize <= 2:
                dn = eqn.params.get("dimension_numbers")
                ((lhs_c, _rhs_c), _batch) = dn
                shape = getattr(eqn.invars[0].aval, "shape", ())
                k = 1
                for a in lhs_c:
                    k *= shape[a] if a < len(shape) else 1
                if k >= self.reduce_elems:
                    self._emit(
                        "VN403", WARNING,
                        "dot_general contracts %d elements with a %s "
                        "accumulator — the MXU accumulates f32 only "
                        "when preferred_element_type says so; a <=16-"
                        "bit output dtype rounds the running sum"
                        % (k, dt),
                        hint="pass preferred_element_type=jnp.float32 "
                             "(ops/linear.py pins policy.accum) and "
                             "cast down after the reduction",
                        key=("dot", str(dt), k))
            flags[eqn.outvars[0]] = frozenset()
            return
        if prim == "reduce_sum":
            x = eqn.invars[0]
            aval = getattr(x, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None and _float_dtype(dt) \
                    and np.dtype(dt).itemsize <= 2:
                shape = getattr(aval, "shape", ())
                axes = eqn.params.get("axes", ())
                n = 1
                for a in axes:
                    n *= shape[a] if a < len(shape) else 1
                if n >= self.reduce_elems:
                    self._emit(
                        "VN403", WARNING,
                        "sum over %d elements accumulates in %s — with "
                        "<= 11 mantissa bits the tail of a long sum is "
                        "rounded away (loss/metric drift)" % (n, dt),
                        hint="accumulate in f32: x.astype(jnp.float32)"
                             ".sum() (every loss in ops/losses.py "
                             "does), or keep dot accumulation in f32 "
                             "via preferred_element_type",
                        key=("reduce", str(dt), n))
            f = set(get(x) & {POS, NONNEG})
            # the max-gradient tie count — sum(x == max(x)) — is >= 1
            # by construction (the max is attained); its div shows up
            # in the VJP of every jnp.max / reduce-max
            eq = self._chain_prim(x, defs, ("eq",))
            if eq is not None:
                a, b = eq.invars[:2]
                if self._reduce_max_of(b, a, defs) \
                        or self._reduce_max_of(a, b, defs):
                    f.update((POS, NONNEG))
            flags[eqn.outvars[0]] = frozenset(f)
            return

        # ---- VN404: integer-narrowing casts ----------------------------
        if prim == "convert_element_type":
            x = eqn.invars[0]
            src_dt = np.dtype(getattr(getattr(x, "aval", None), "dtype",
                                      np.float32))
            dst_dt = np.dtype(eqn.params.get("new_dtype", np.float32))
            fx = get(x)
            if np.issubdtype(src_dt, np.integer) \
                    and np.issubdtype(dst_dt, np.integer) \
                    and dst_dt.itemsize < src_dt.itemsize \
                    and not (UB in fx and NONNEG in fx) \
                    and not self._clamped_to_range(x, dst_dt, defs):
                self._emit(
                    "VN404", WARNING,
                    "narrowing integer cast %s -> %s of an unbounded "
                    "value — out-of-range values wrap around silently"
                    % (src_dt, dst_dt),
                    hint="jnp.clip to the target range before the cast",
                    key=("cast", str(src_dt), str(dst_dt)))
            # float->float and widening casts preserve range flags
            flags[eqn.outvars[0]] = fx
            return

        # ---- generic flag propagation ----------------------------------
        flags_out = self._propagate(prim, eqn, get, defs)
        for ov in eqn.outvars:
            flags[ov] = flags_out

    # -- propagation / pattern helpers --------------------------------------
    def _propagate(self, prim, eqn, get, defs):
        ins = [get(v) for v in eqn.invars]
        if prim in _IDENTITY_PRIMS:
            return ins[0] if ins else frozenset()
        if prim == "add" or prim == "add_any":
            out = set()
            if len(ins) == 2:
                a, b = ins
                if (POS in a and NONNEG in b) or (NONNEG in a
                                                  and POS in b):
                    out.update((POS, NONNEG))
                elif NONNEG in a and NONNEG in b:
                    out.add(NONNEG)
                if UB in a and UB in b:
                    out.add(UB)
            return frozenset(out)
        if prim == "sub":
            out = set()
            a, b = ins
            # a - b is bounded above only when a is AND b is bounded
            # below (c - x overflows exp for very negative x)
            if UB in a and (NONNEG in b or POS in b):
                out.add(UB)
            elif self._reduce_max_of(eqn.invars[1], eqn.invars[0], defs):
                out.add(UB)          # x - max(x) <= 0
            elif self._logsumexp_of(eqn.invars[1], eqn.invars[0], defs,
                                    get):
                out.add(UB)          # x - logsumexp(x) <= 0
            # 1 - b**t (adam bias correction): literal >= 1 minus a
            # value provably in [0, 1) is positive
            lit = _lit_val(eqn.invars[0])
            if lit is not None and lit >= 1.0 and LT1 in b:
                out.update((POS, NONNEG, UB))
            return frozenset(out)
        if prim == "mul":
            out = set()
            a, b = ins
            same = (self._origin(eqn.invars[0], defs)
                    is self._origin(eqn.invars[1], defs))
            if same:
                out.add(NONNEG)      # x * x
                if POS in a:
                    out.add(POS)
            elif POS in a and POS in b:
                out.update((POS, NONNEG))
            elif NONNEG in a and NONNEG in b:
                out.add(NONNEG)
            if UB in a and UB in b and NONNEG in a and NONNEG in b:
                out.add(UB)
            return frozenset(out)
        if prim == "max":
            a, b = ins
            out = set()
            if POS in a or POS in b:
                out.update((POS, NONNEG))
            elif NONNEG in a or NONNEG in b:
                out.add(NONNEG)
            if UB in a and UB in b:
                out.add(UB)
            return frozenset(out)
        if prim == "min":
            a, b = ins
            out = set()
            if POS in a and POS in b:
                out.update((POS, NONNEG))
            elif NONNEG in a and NONNEG in b:
                out.add(NONNEG)
            if UB in a or UB in b:
                out.add(UB)
            return frozenset(out)
        if prim == "clamp":
            lo, _x, hi = ins
            out = set()
            if POS in lo:
                out.update((POS, NONNEG))
            elif NONNEG in lo:
                out.add(NONNEG)
            if UB in hi:
                out.add(UB)
            return frozenset(out)
        if prim in ("abs", "square"):
            return frozenset((NONNEG,))
        if prim == "neg":
            a = ins[0]
            return frozenset({UB} if NONNEG in a else set())
        if prim == "sqrt":
            a = ins[0]
            out = {NONNEG}
            if POS in a:
                out.add(POS)
            if UB in a:
                out.add(UB)
            return frozenset(out)
        if prim == "integer_pow":
            y = eqn.params.get("y", 1)
            if isinstance(y, int) and y % 2 == 0 and y > 0:
                return frozenset((NONNEG,))
            return ins[0] if y == 1 else frozenset()
        if prim == "pow":
            a = ins[0]
            base = _lit_val(eqn.invars[0])
            if base is not None and 0.0 < base < 1.0 \
                    and POS in ins[1]:
                return frozenset((POS, NONNEG, UB, LT1))
            if POS in a:
                return frozenset((POS, NONNEG))
            return frozenset()
        if prim == "logistic":
            return frozenset((NONNEG, UB))
        if prim == "erf":
            return frozenset((UB,))      # erf ranges over [-1, 1]
        if prim in ("tanh", "sin", "cos", "erf_inv"):
            return frozenset({UB} if prim in ("tanh", "sin", "cos")
                             else set())
        if prim == "log1p":
            return frozenset(set(ins[0]) & {POS, NONNEG, UB})
        if prim == "exp2":
            return frozenset(
                {POS, NONNEG} | ({UB} if UB in ins[0] else set()))
        if prim == "reduce_window_sum":
            f = set(ins[0]) & {POS, NONNEG}
            # avg-pool count normalization: the window sum of (padded)
            # ones — every pooling window overlaps >= 1 real element by
            # construction, so the count is >= 1
            if POS not in f and self._ones_window(eqn.invars[0], defs):
                f.update((POS, NONNEG))
            return frozenset(f)
        if prim == "reduce_max":
            f = ins[0]
            return frozenset(f & {POS, NONNEG, UB})
        if prim == "reduce_min":
            f = ins[0]
            return frozenset(f & {POS, NONNEG, UB})
        if prim == "reduce_prod":
            f = ins[0]
            return frozenset(f & {POS, NONNEG})
        if prim == "select_n":
            cases = ins[1:]
            if not cases:
                return frozenset()
            pred = self._cval(eqn.invars[0])
            if pred is not None:      # constant predicate: live branch
                i = min(int(pred), len(cases) - 1)
                return frozenset(set(cases[i]) - {SOFTMAX})
            out = set(cases[0])
            for c in cases[1:]:
                out &= set(c)
            out.discard(SOFTMAX)
            # jnp.where(mask, softmax_p, 0) keeps the softmax shape
            if all(SOFTMAX in c or self._zero_literal(v)
                   for c, v in zip(cases, eqn.invars[1:])) \
                    and any(SOFTMAX in c for c in cases):
                out.add(SOFTMAX)
            return frozenset(out)
        if prim == "iota":
            return frozenset((NONNEG, UB))
        if prim == "concatenate":
            out = set(ins[0]) if ins else set()
            for f in ins[1:]:
                out &= set(f)
            return frozenset(out)
        if prim == "dot_general":
            return frozenset()
        if prim == "pad":
            a = ins[0]
            pv = ins[1] if len(ins) > 1 else frozenset()
            return frozenset(set(a) & set(pv) & {POS, NONNEG, UB})
        return frozenset()

    @staticmethod
    def _zero_literal(v):
        return _lit_val(v) == 0.0

    def _clamped_to_range(self, v, dst_dt, defs):
        """``v`` is (glue around) a ``clamp``/``max``+``min`` whose
        literal bounds fit the target integer dtype — the documented
        VN404 fix ``jnp.clip(x, -128, 127).astype(jnp.int8)`` must
        pass for SIGNED ranges too (the flag lattice has no
        bounded-below fact)."""
        lo = hi = None
        eqn = self._chain_prim(v, defs, ("clamp", "pjit"))
        if eqn is None:
            return False
        if primitive_name(eqn) == "clamp":
            lo = self._cval(eqn.invars[0])
            hi = self._cval(eqn.invars[2])
        elif eqn.params.get("name") == "clip" \
                and len(eqn.invars) >= 3:
            # jnp.clip stages as pjit[name=clip](x, lo, hi)
            lo = self._cval(eqn.invars[1])
            hi = self._cval(eqn.invars[2])
        if lo is None or hi is None:
            return False
        info = np.iinfo(dst_dt)
        return info.min <= lo and hi <= info.max

    def _ones_window(self, v, defs, depth=8):
        """``v`` is (identity/zero-pad glue around) a broadcast of a
        positive literal — the avg-pool per-position window count."""
        for _ in range(depth):
            if hasattr(v, "val"):
                x = _lit_val(v)
                return x is not None and x > 0
            eqn = defs.get(v)
            if eqn is None:
                return False
            prim = primitive_name(eqn)
            if prim in _IDENTITY_PRIMS or prim == "convert_element_type":
                v = eqn.invars[0]
                continue
            if prim == "pad":
                v = eqn.invars[0]
                continue
            return False
        return False

    def _reduce_max_of(self, b, a, defs, depth=10):
        """True when ``b`` provably dominates ``a`` elementwise-or-
        broadcast — i.e. ``a - b <= 0``, the online-softmax bound.
        Two shapes, searched through identity glue and through BOTH
        operands of ``max`` (max only raises a bound):

        * ``b`` reaches ``reduce_max`` of ``a``'s origin
          (``exp(x - max(x))``, jax's log_softmax lowering);
        * ``b`` reaches ``a``'s origin itself
          (``exp(m_prev - max(m_prev, ...))``, the running-max
          correction in every online-softmax / flash kernel body)."""
        target = self._origin(a, defs)
        stack, seen = [(b, depth)], set()
        while stack:
            v, d = stack.pop()
            if d <= 0 or hasattr(v, "val"):
                continue
            if v in seen:
                continue
            seen.add(v)
            if self._origin(v, defs) is target:
                return True
            eqn = defs.get(v)
            if eqn is None:
                continue
            prim = primitive_name(eqn)
            if prim == "reduce_max":
                if self._origin(eqn.invars[0], defs) is target:
                    return True
                continue
            if prim in _IDENTITY_PRIMS or prim == "convert_element_type":
                stack.append((eqn.invars[0], d - 1))
            elif prim == "max":
                for iv in eqn.invars:
                    stack.append((iv, d - 1))
        return False

    def _sum_exp_under_max(self, v, defs):
        """``v`` is ``sum(exp(x - max(x)))`` over the max's own axes:
        the maximum's term is 1, so ``v >= 1`` and its log ``>= 0``."""
        s = self._chain_prim(v, defs, ("reduce_sum",))
        e = s and self._chain_prim(s.invars[0], defs, ("exp",))
        d = e and self._chain_prim(e.invars[0], defs, ("sub",))
        m = d and self._chain_prim(d.invars[1], defs, ("reduce_max",))
        return bool(m) \
            and self._origin(m.invars[0], defs) \
            is self._origin(d.invars[0], defs) \
            and tuple(m.params["axes"]) == tuple(s.params["axes"])

    def _logsumexp_of(self, b, a, defs, get):
        """``b`` is ``m + y`` with ``m`` dominating ``a``
        (:meth:`_reduce_max_of`) and ``y >= 0`` — ``a``'s log-sum-exp
        ``max(a) + log(sum(exp(a - max(a))))``, so ``a - b <= 0``: the
        softmax ``exp(z - lse)`` that a fused loss's backward takes from
        its forward's residual."""
        eqn = self._chain_prim(b, defs, ("add",))
        return eqn is not None and any(
            self._reduce_max_of(m, a, defs) and NONNEG in get(y)
            for m, y in (eqn.invars, eqn.invars[::-1]))

    def _sub_max_guard(self, x, defs):
        eqn = self._chain_prim(x, defs, ("sub",))
        if eqn is None:
            return False
        return self._reduce_max_of(eqn.invars[1], eqn.invars[0], defs)

    def _softmax_div(self, num, den, defs):
        """exp(u) / [broadcast of] reduce_sum(exp(u)) — raw softmax."""
        num_exp = self._chain_prim(num, defs, ("exp",))
        if num_exp is None:
            return False
        den_sum = self._chain_prim(den, defs, ("reduce_sum",))
        if den_sum is None:
            return False
        den_exp = self._chain_prim(den_sum.invars[0], defs, ("exp",))
        return den_exp is not None

    def _is_softmax_chain(self, v, defs):
        eqn = self._chain_prim(v, defs, ("div",))
        if eqn is None:
            return False
        return self._softmax_div(eqn.invars[0], eqn.invars[1], defs)


def _short_aval(v):
    aval = getattr(v, "aval", None)
    return "%s[%s]" % (getattr(aval, "dtype", "?"),
                       ",".join(map(str, getattr(aval, "shape", ()))))


# ---------------------------------------------------------------------------
# VR502: host numpy.random in staged source
# ---------------------------------------------------------------------------
def _np_random_calls(fn):
    """Attribute chains ``np.random...`` / ``numpy.random...`` in the
    source of ``fn`` (and any lambdas/inner defs it contains).  Host
    randomness inside a staged step runs once at trace time and bakes
    the SAME values into every iteration."""
    fn = inspect.unwrap(getattr(fn, "__wrapped__", fn))
    try:
        src = textwrap.dedent(inspect.getsource(fn))
        tree = ast.parse(src)
    except (OSError, TypeError, SyntaxError, IndentationError):
        return []
    hits = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Attribute):
            continue
        base = node.value
        if isinstance(base, ast.Name) and base.id in ("np", "numpy") \
                and node.attr == "random":
            hits.append("%s.random (line %d)" % (base.id,
                                                 node.lineno))
    return hits


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------
def audit_numerics_step(spec):
    """VN4xx/VR5xx audit of one staged step.

    ``spec`` (the shape ``StagedTrainer.lint_numerics_spec()`` returns):

    ``fn``        the step — jitted object or plain callable
    ``args``      positional args: concrete arrays and/or
                  ``jax.ShapeDtypeStruct`` specs (never executed)
    ``name``      display name for findings
    ``suppress``  optional iterable of rule ids to drop (the explicit
                  "checked" escape hatch — e.g. a loss registered with
                  ``register_loss(..., numerics_suppress=("VN403",))``)
    ``reduce_elems``  optional VN403 threshold override
    ``input_flags``   optional {flat-input-leaf-index: flag names} the
                  caller can VOUCH for — e.g. the trainer pins its step
                  counter positive (it increments before dispatch), so
                  adam's ``1 - beta**t`` bias correction proves out
    ``host_scan``     optional extra callables whose SOURCE joins the
                  VR502 host-randomness scan — the trainer passes its
                  loss evaluator and any user-defined (non-veles_tpu)
                  layers, since the staged step fn itself is framework
                  code and the user's host calls live in its callees

    Tracing is abstract (``jax.make_jaxpr``): no device arrays, no
    dispatch — asserted in tests/test_numerics_audit.py."""
    name = spec.get("name", "step")
    fn = spec["fn"]
    suppress = frozenset(spec.get("suppress", ()))

    findings = []
    seen_hits = set()
    for scanned in (fn,) + tuple(spec.get("host_scan", ())):
        for hit in _np_random_calls(scanned):
            where = getattr(scanned, "__name__", "step")
            if (where, hit) in seen_hits:
                continue
            seen_hits.add((where, hit))
            findings.append(Finding(
                "VR502", ERROR, name,
                "host numpy.random call in staged code (%s, in %s): "
                "it runs ONCE at trace time — every step replays the "
                "same \"random\" values" % (hit, where),
                hint="use jax.random with a per-step key (fold_in on "
                     "the step counter), or draw on the host OUTSIDE "
                     "the step via veles_tpu.prng streams"))

    try:
        closed = jax.make_jaxpr(fn)(*spec.get("args", ()))
    except Exception as e:  # noqa: BLE001 — trace failure is VJ100's job
        findings.append(Finding(
            "VJ100", ERROR, name,
            "staged step failed to trace abstractly for the numerics "
            "audit: %s: %s" % (type(e).__name__, e),
            hint="the step must trace over abstract inputs — no "
                 "data-dependent python control flow"))
        return findings

    scan = _NumericsScan(
        name, reduce_elems=int(spec.get("reduce_elems",
                                        LOW_PRECISION_REDUCE_ELEMS)))
    findings.extend(scan.run(closed,
                             input_flags=spec.get("input_flags")))
    if suppress:
        findings = [f for f in findings if f.rule not in suppress]
    return findings


def audit_prng_registry(name="<prng>"):
    """VR501: named streams in the global ``veles_tpu.prng`` registry
    whose effective seeds collide — their entire futures replay each
    other.  Derived (hash-offset) seeds are rehashed away at creation
    (prng.py); what remains is explicit seeding."""
    from veles_tpu import prng
    findings = []
    for names, seed in prng.seed_collisions():
        findings.append(Finding(
            "VR501", WARNING, name,
            "prng streams %s share seed %d — every draw in one replays "
            "the other (fold_in counters advance in lockstep)"
            % (", ".join(sorted(names)), seed),
            hint="seed streams differently (prng.get(name).seed(s)), or "
                 "let the per-name sha1 offset derive them from "
                 "root.common.random_seed"))
    return findings


# ---------------------------------------------------------------------------
# VP6xx: Pallas kernel launch geometry
# ---------------------------------------------------------------------------
def _sublane_tile(dtype):
    """Native TPU sublane tile for a dtype: (8, 128) f32, (16, 128)
    bf16/f16, (32, 128) int8/fp8 — single source of truth shared with
    the paged-serving fallback (ops.pallas.mosaic_sublane_min)."""
    from veles_tpu.ops import pallas as _pallas
    return _pallas.mosaic_sublane_min(dtype)


def audit_kernel_launch(launch, vmem_kib=None):
    """VP6xx findings for one kernel-launch description.

    ``launch`` is the dict shape ``ops.pallas`` audit hooks return:

    ``kernel``    display name, e.g. ``"flash.forward"``
    ``blocks``    [(ref_name, block_shape, dtype), ...] — every VMEM
                  ref the kernel sees (in/out block tiles)
    ``scratch``   [(name, shape, dtype), ...] — VMEM scratch allocations
                  (one that carries an options dict is a buffer the
                  kernel DMAs tiles into by hand, and is tile-checked
                  like a block)
    ``grid_axes`` [(axis_name, length, block), ...] — launch axes whose
                  length/block divisibility matters
    ``masked``    True when the kernel masks/pads ragged tails (the
                  VP601 escape hatch — our kernels do, docstrings say
                  so, and the tests pin it)
    ``checked``   optional iterable of rule ids deliberately accepted
                  for this launch (escape hatch, mirrors ``suppress``)
    """
    name = launch.get("kernel", "<kernel>")
    checked = frozenset(launch.get("checked", ()))
    budget = int((vmem_kib or launch.get("vmem_kib")
                  or DEFAULT_VMEM_KIB) * 1024)
    findings = []

    for entry in list(launch.get("blocks", ())) + [
            e for e in launch.get("scratch", ()) if len(e) > 3]:
        ref_name, shape, dtype = entry[:3]
        opts = entry[3] if len(entry) > 3 else {}
        shape = tuple(int(s) for s in shape if int(s) != 1)
        if len(shape) < 2:
            continue
        sub, lane = shape[-2], shape[-1]
        want_sub = _sublane_tile(dtype)
        bad = []
        # a block dim that spans the WHOLE array in that axis is the
        # model's geometry, not a tunable tile choice — e.g. flash's
        # lane dim IS the head dim, and d=64 models exist (the kernel
        # handles the half-tile; only chosen block sizes are lintable)
        if lane % 128 and not opts.get("full_lane"):
            bad.append("lane dim %d %% 128 != 0" % lane)
        if sub % want_sub and not opts.get("full_sublane"):
            bad.append("sublane dim %d %% %d != 0 (%s tile)"
                       % (sub, want_sub, np.dtype(dtype).name))
        if bad and "VP600" not in checked:
            findings.append(Finding(
                "VP600", WARNING, name,
                "block %r %r is not aligned to the %s native tile "
                "(%d, 128): %s — Mosaic retiles every HBM<->VMEM copy"
                % (ref_name, shape, np.dtype(dtype).name, want_sub,
                   "; ".join(bad)),
                hint="round the block dims to multiples of (%d, 128) "
                     "and mask the tail inside the kernel" % want_sub))

    if not launch.get("masked", False) and "VP601" not in checked:
        for axis, length, block in launch.get("grid_axes", ()):
            block = int(block)
            if block and int(length) % block:
                findings.append(Finding(
                    "VP601", WARNING, name,
                    "grid axis %r: length %d is not divisible by block "
                    "%d and the kernel does not mask the ragged tail — "
                    "the last block reads/writes out of range"
                    % (axis, length, block),
                    hint="pad the operand to a block multiple and mask "
                         "inside the kernel (ops/pallas/flash.py's "
                         "_pad_to + validity-mask pattern)"))

    def _bytes(entries):
        total = 0
        for entry in entries:
            _n, shape, dtype = entry[:3]
            n = 1
            for s in shape:
                n *= int(s)
            total += n * np.dtype(dtype).itemsize
        return total

    ref_bytes = _bytes(launch.get("blocks", ()))
    scratch_bytes = _bytes(launch.get("scratch", ()))
    # Mosaic double-buffers the in/out refs so the next grid step's DMA
    # overlaps compute; scratch persists single-buffered
    total = 2 * ref_bytes + scratch_bytes
    if total > budget and "VP602" not in checked:
        findings.append(Finding(
            "VP602", ERROR, name,
            "estimated VMEM footprint %.1f KiB (refs %.1f x2 double-"
            "buffered + scratch %.1f) exceeds the %.0f KiB budget — "
            "the kernel will not fit on a core"
            % (total / 1024.0, ref_bytes / 1024.0,
               scratch_bytes / 1024.0, budget / 1024.0),
            hint="shrink block_q/block_k (halving one halves its "
                 "tiles), or drop --vmem-kib if targeting a larger "
                 "part"))
    return findings


def audit_pallas_kernels(launches=None, vmem_kib=None):
    """VP6xx audit over kernel-launch descriptions — ``launches`` or,
    by default, every launch the registered kernels report for their
    CONFIGURED geometry (``ops.pallas.kernel_audit_launches()``: flash
    fwd/bwd at the site-config block sizes, paged decode at the serving
    defaults).  Pure block-shape arithmetic — nothing is compiled or
    dispatched."""
    if launches is None:
        from veles_tpu.ops import pallas
        launches = pallas.kernel_audit_launches()
    findings = []
    for launch in launches:
        findings.extend(audit_kernel_launch(launch, vmem_kib=vmem_kib))
    return findings


def audit_numerics(spec=None, launches=None, vmem_kib=None,
                   prng_registry=True):
    """The full numerics pass: VN4xx/VR500/502/503 over ``spec``'s
    staged step (when given), VR501 over the prng registry, VP6xx over
    the Pallas launches.  This is what ``lint_workflow`` and the CLI
    ``--numerics`` flag run."""
    findings = []
    if spec:
        findings.extend(audit_numerics_step(spec))
    if prng_registry:
        findings.extend(audit_prng_registry())
    findings.extend(audit_pallas_kernels(launches=launches,
                                         vmem_kib=vmem_kib))
    return findings
