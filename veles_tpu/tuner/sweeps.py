"""Candidate enumeration + measurement harnesses for the autotuner.

One function per tuned kernel family: the flash forward, the two split
backward kernels (dq, dkv — independent grids since the block-size
decoupling), and the fused paged decode kernel.  Each returns the
``{"config", "launches"}`` candidate dicts :meth:`KernelTuner.sweep`
consumes, where ``launches`` are the same VP6xx launch descriptions the
``register_kernel_audit`` hooks emit — the audit gate and the kernels
can never disagree about geometry.

Measurement uses bench.py's chained in-jit harness: a kernel that runs
for microseconds cannot be timed per dispatch (the dispatch floor
swamps it), so ``iters`` kernel calls are chained inside ONE jit
dispatch — each call feeds the previous output back as q — and the
dispatch cost amortizes away.  Off-accelerator the same harness runs in interpret mode (CI's
``tune-smoke`` proves the machinery; the numbers only mean something
on silicon).
"""

import functools
import time

#: ranked block-size grid per head-dim regime: the d=128 grid the
#: flashtune phase swept, widened with 1024-blocks for the d<=64 VMEM
#: regime (half-size slabs — 1024 fits and measured fastest there)
_SIZES_D128 = (512, 256, 128)
_SIZES_D64 = (1024, 512, 256, 128)


def _rank_pairs(pairs, d):
    """tools/cost_model.predict_flashtune_order's ranking, inlined so
    library code never imports the repo-root ``tools`` package: larger
    blocks amortize the softmax/rescale bookkeeping between inner
    matmuls, square blocks win ties (cleaner causal diagonals)."""
    def overhead(pair):
        bq, bk = pair
        return ((bq * 4 + 200) / (2.0 * bq * bk * d)
                + (0 if bq == bk else 1e-9))
    return sorted(pairs, key=overhead)


def flash_candidates(kind, t, d, dtype="bfloat16", causal=True,
                     window=None):
    """Ranked candidates for one flash kernel.  ``kind`` is one of
    ``fwd``/``bwd_dq``/``bwd_dkv``; configs use plain block_q/block_k
    names (the kernel key carries which grid they bind to).  Blocks are
    capped at the padded sequence length — oversized candidates would
    all clamp to the same real geometry and measure as duplicates."""
    from veles_tpu.ops.pallas import flash

    sizes = _SIZES_D64 if d <= 64 else _SIZES_D128
    cap = max(128, -(-int(t) // 128) * 128)
    sizes = sorted({min(s, cap) for s in sizes}, reverse=True)
    kernel = {"fwd": "forward", "bwd_dq": "bwd_dq",
              "bwd_dkv": "bwd_dkv"}[kind]
    out = []
    for bq, bk in _rank_pairs([(bq, bk) for bq in sizes for bk in sizes],
                              d):
        blocks = ({"block_q": bq, "block_k": bk} if kind == "fwd" else
                  {"block_q_%s" % kind[4:]: bq,
                   "block_k_%s" % kind[4:]: bk})
        out.append({
            "config": {"block_q": bq, "block_k": bk},
            "launches": flash.audit_launch(
                t, t, d, dtype=dtype, causal=causal, window=window,
                kernels=(kernel,), **blocks),
        })
    return out


def paged_candidates(hd, g=1, dtype="bfloat16", nbm=32):
    """Candidates for the fused paged decode kernel: the KV pool block
    size (the page — the vLLM block: the kernel copies whole pages, as
    many a loop trip as ``paged.page_schedule`` fits in its buffers)
    and the q-group sublane pad.  ``dtype`` is the POOL dtype — ``int8``
    enumerates the quantized-pool variant (QuantCache: int8 K/V tiles
    + per-position scale tiles; q stays bf16), whose audit launches
    carry the extra scale blocks."""
    import numpy as np

    from veles_tpu.ops.pallas import paged

    quant = np.dtype(dtype) == np.dtype(np.int8)
    # int8 tiles want 32 sublanes on silicon; smaller blocks stay in
    # the grid (VP600 warns, never rejects) so interpret-mode CI still
    # proves the ranking machinery over the same budget trade-off
    sizes = (64, 32, 16) if quant else (32, 16, 8)
    out = []
    for bs in sizes:
        for gp in sorted({max(int(g), paged._MIN_G), 32}):
            out.append({
                "config": {"block": bs, "block_g": gp},
                "launches": paged.audit_launch(hd, bs, g=gp,
                                               dtype=dtype, nbm=nbm),
            })
    return out


# --------------------------------------------------------------------------
# Measurement harnesses
# --------------------------------------------------------------------------

def _chain(fn, iters, *args):
    """jit(fn chained ``iters`` times feeding dq/out back as q); the
    returned thunk runs one chained dispatch and returns wall seconds.
    The thunk must be built ONCE per config and called repeatedly —
    jax's jit cache keys on the callable's identity, so a rebuilt
    wrapper would retrace+recompile inside every timed call (the
    measure factories below memoize per config; the first, compiling,
    call lands in the sweep's discarded warm-up)."""
    import jax
    from jax import lax

    @jax.jit
    def chained(q, *rest):
        def body(y, _):
            return fn(y, *rest), None
        y, _ = lax.scan(body, q, None, length=iters)
        return y

    def run():
        t0 = time.perf_counter()
        jax.block_until_ready(chained(*args))
        return (time.perf_counter() - t0) / iters
    return run


def flash_measure(kind, t, d, dtype="bfloat16", b=None, h=8, iters=4,
                  causal=True, window=None, interpret=None, seed=0):
    """Measure-thunk factory for one flash kernel: returns
    ``measure(config) -> seconds`` for :meth:`KernelTuner.sweep`.

    Backward kernels are timed in ISOLATION by differentiating w.r.t.
    only their outputs' inputs — ``bwd_dq`` takes grad over q (XLA
    dead-codes the unused dkv pallas_call), ``bwd_dkv`` over (k, v)
    (the dq call dies) — so a dq candidate's score never includes dkv
    time.  The forward runs in both (its residuals feed the backward);
    candidates share one pinned forward config, so the delta between
    candidates is purely the tuned kernel."""
    import jax
    from veles_tpu.ops.pallas.flash import flash_attention

    if b is None:
        b = 4 if t <= 2048 else 1
    key = jax.random.key(seed)
    q, k, v = (jax.random.normal(kk, (b, h, t, d)).astype(dtype) * 0.1
               for kk in jax.random.split(key, 3))
    thunks = {}   # config -> compiled chained thunk (see _chain)

    def measure(config):
        bq, bk = int(config["block_q"]), int(config["block_k"])
        run = thunks.get((bq, bk))
        if run is not None:
            return run()
        kwargs = dict(causal=causal, window=window, interpret=interpret)
        if kind == "fwd":
            kwargs.update(block_q=bq, block_k=bk)
        else:
            # pin the forward (and the sibling backward kernel) to the
            # defaults so only the candidate's grid varies
            kwargs.update({"block_q_%s" % kind[4:]: bq,
                           "block_k_%s" % kind[4:]: bk})
        attn = functools.partial(flash_attention, **kwargs)
        if kind == "fwd":
            fn = lambda q_, k_, v_: attn(q_, k_, v_)  # noqa: E731
        elif kind == "bwd_dq":
            fn = jax.grad(
                lambda q_, k_, v_: attn(q_, k_, v_).sum(), argnums=0)
        elif kind == "bwd_dkv":
            def fn(q_, k_, v_):
                dk, dv = jax.grad(
                    lambda q2, k2, v2: attn(q2, k2, v2).sum(),
                    argnums=(1, 2))(q_, k_, v_)
                # keep BOTH outputs live through a cheap reduction that
                # still has q's shape for the chain feed-back
                return q_ + (dk.sum() + dv.sum()).astype(q_.dtype)
        else:
            raise ValueError("kind must be fwd/bwd_dq/bwd_dkv, got %r"
                             % (kind,))
        thunks[(bq, bk)] = run = _chain(fn, iters, q, k, v)
        return run()
    return measure


def paged_measure(hd, g=1, dtype="bfloat16", slots=8, pool_blocks=32,
                  hkv=4, iters=8, interpret=None, seed=0):
    """Measure-thunk factory for the fused paged decode kernel.  The
    pool layout depends on the candidate's block size, so inputs are
    built per config (pool token budget held constant — the real
    serving trade-off: more, smaller blocks vs fewer, larger ones).
    ``dtype="int8"`` measures the quantized-pool variant: the pools
    are QuantCache pairs (random f32 K/V quantized through the real
    ``quantize_kv``), q stays bf16."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from veles_tpu.ops.attention import QuantCache, quantize_kv
    from veles_tpu.ops.pallas.paged import paged_attention_decode

    quant = np.dtype(dtype) == np.dtype(np.int8)
    tokens = pool_blocks * 16     # constant budget across candidates
    hq = hkv * g
    thunks = {}   # config -> (jitted fn, inputs) built once per config

    def measure(config):
        bs = int(config["block"])
        gp = int(config.get("block_g", 0)) or None
        cached = thunks.get((bs, gp))
        if cached is None:
            npool = max(2, tokens // bs + 1)
            nbm = max(2, tokens // bs)
            key = jax.random.key(seed)
            kq, kk, kv = jax.random.split(key, 3)
            fdt = jnp.bfloat16 if quant else dtype
            q = jax.random.normal(kq, (slots, hq, hd)).astype(fdt) * 0.1
            pool_k = jax.random.normal(
                kk, (npool, hkv, bs, hd)).astype(jnp.float32) * 0.1
            pool_v = jax.random.normal(
                kv, (npool, hkv, bs, hd)).astype(jnp.float32) * 0.1
            if quant:
                pool_k = QuantCache(*quantize_kv(pool_k))
                pool_v = QuantCache(*quantize_kv(pool_v))
            else:
                pool_k = pool_k.astype(dtype)
                pool_v = pool_v.astype(dtype)
            table = (1 + (jnp.arange(slots * nbm)
                          % (npool - 1))).reshape(
                slots, nbm).astype(jnp.int32)
            pos = jnp.full((slots,), nbm * bs - 1, jnp.int32)
            fn = jax.jit(functools.partial(
                paged_attention_decode, interpret=interpret,
                block_g=gp))
            thunks[(bs, gp)] = cached = (
                fn, (q, pool_k, pool_v, table, pos))
        fn, args = cached
        # decode is one tiny dispatch; average a few inside the timer
        # (the first, compiling, call lands in the discarded warm-up)
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(fn(*args))
        return (time.perf_counter() - t0) / iters
    return measure


# --------------------------------------------------------------------------
# Whole-family sweeps (the CLI and bench phase both drive these)
# --------------------------------------------------------------------------

FLASH_KINDS = ("fwd", "bwd_dq", "bwd_dkv")


def sweep_flash(tuner, ts=(1024,), d=128, dtype="bfloat16", kinds=None,
                iters=4, repeats=3, warmup=1, causal=True,
                interpret=None, dry_run=False, mesh=None, log=None,
                source="sweep"):
    """Sweep the flash forward + split backward kernels over the given
    sequence lengths.  Returns ``{(kind, t): SweepResult}``."""
    from veles_tpu.tuner import flash_shape_key
    results = {}
    for kind in (kinds or FLASH_KINDS):
        for t in ts:
            cands = flash_candidates(kind, t, d, dtype=dtype,
                                     causal=causal)
            measure = (None if dry_run else
                       flash_measure(kind, t, d, dtype=dtype,
                                     iters=iters, causal=causal,
                                     interpret=interpret))
            res = tuner.sweep("flash.%s" % kind, flash_shape_key(t, d),
                              dtype, cands, measure, mesh=mesh,
                              repeats=repeats, warmup=warmup,
                              dry_run=dry_run, source=source)
            results[(kind, t)] = res
            if log:
                w = res.winner
                log("flash.%s t=%d d=%d: %s (candidates %d, "
                    "audit-rejected %d)"
                    % (kind, t, d,
                       "winner %r %.3f ms" % (w["config"], w["ms"])
                       if w else ("dry run" if dry_run
                                  else "no winner"),
                       len(res.candidates), len(res.audit_rejected)))
    return results


def sweep_paged(tuner, hd=128, g=1, dtype="bfloat16", iters=8,
                repeats=3, warmup=1, interpret=None, dry_run=False,
                mesh=None, log=None, source="sweep"):
    """Sweep the fused paged decode kernel's pool block + q-group pad.
    Winners key by (kernel ``paged.decode``, shape, POOL dtype) — the
    int8 QuantCache flavor is the same kernel family at dtype
    ``int8``, swept via ``dtype="int8"`` (the launch path's
    ``preferred_pool_block``/``_resolve_block_g`` look up with the
    pool's own dtype, so serving finds the right regime's winner
    automatically)."""
    from veles_tpu.tuner import paged_shape_key
    cands = paged_candidates(hd, g=g, dtype=dtype)
    measure = (None if dry_run else
               paged_measure(hd, g=g, dtype=dtype, iters=iters,
                             interpret=interpret))
    res = tuner.sweep("paged.decode", paged_shape_key(hd, g), dtype,
                      cands, measure, mesh=mesh, repeats=repeats,
                      warmup=warmup, dry_run=dry_run, source=source)
    if log:
        w = res.winner
        log("paged.decode[%s] hd=%d g=%d: %s (candidates %d, "
            "audit-rejected %d)"
            % (dtype, hd, g,
               "winner %r %.3f ms" % (w["config"], w["ms"])
               if w else ("dry run" if dry_run else "no winner"),
               len(res.candidates), len(res.audit_rejected)))
    return {("paged", dtype, hd): res}
