#!/usr/bin/env python3
"""chip_smoke.py — the first command of any chip session.

Drives the main path once on ONE TPU chip, in ONE process, at the full
width of the 124M flagship (``lm_large``, ``bench.py`` phase_lm_large):

  train    ``StandardWorkflow`` -> ``StagedTrainer``: two warm-up
           sweeps, then eight steps with no compilation inside them;
  barrier  one four-step sweep timed twice, ended by
           ``block_until_ready`` and by a ``device_get`` of the loss —
           a printed fact, not a metric;
  kernels  Pallas flash fwd + fused bwd, the paged decode kernel
           (bf16 pool and int8 QuantCache pool) and a prefill pass's
           masked attention (``veles_dsa_prefill``, with the
           milliseconds of kernel and reference), Mosaic-compiled,
           against their plain-XLA references in the tree;
  serve    the trained weights behind ``LMGenerator`` ->
           ``PagedContinuousBatcher`` -> ``RESTfulAPI``: eight
           concurrent POSTs + one NDJSON stream on a bf16 pool, then one
           request on an int8 pool, both through the fused tick.

Nothing here falls back: no TPU is a non-zero exit before any work, a
failed assertion in any leg is a non-zero exit, the memory ladder is not
walked, and an unmet fused tick raises inside the batcher.  The last
line of stdout is one JSON object naming the device JAX reported.

    python3 chip_smoke.py        # through the chip tool; ~minutes cold

The body is a function of :class:`Sizes` so tests/test_chip_smoke.py can
rehearse it on the CPU at d=64 / 2 layers / T=64 with the platform
check off — an argument the test owns, not a flag of this script."""

import concurrent.futures
import dataclasses
import functools
import http.client
import json
import sys
import time

#: fused steps per dispatch of the train leg (bench.py's lm_large value)
SPD = 4
WARM_SWEEPS = 2
STEPS = 8


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every size the body reads.  The defaults ARE the flagship: the
    ``lm_large`` configuration at its own width and depth, served at
    the lengths ``bench.py`` phase_serve uses."""
    vocab: int = 50304
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    batch: int = 16
    seq: int = 1024
    serve_len: int = 512
    slots: int = 8
    prompt_lens: tuple = (16, 32, 48, 64, 80, 96, 112, 128)
    max_news: tuple = (32, 36, 40, 44, 48, 52, 56, 64)
    #: (B, H, T, hd): the flagship's own attention shape, then the
    #: shape the site config's 512x512 blocks were swept at
    flash_shapes: tuple = ((16, 12, 1024, 64), (4, 8, 1024, 128))
    paged_hd: int = 64
    #: (query heads, KV heads, queries, head dim, keys a row, topk): a
    #: 2,048-token staged pass of ``keye30.serve_long`` — and the live
    #: widths it is checked and timed at
    dsa_shape: tuple = (32, 4, 2048, 128, 34816, 2048)
    dsa_live: tuple = (2048, 18432)
    #: (rows, query heads, KV heads, head dim, block, ring blocks,
    #: window, deepest position): the window layers' decode step of
    #: ``cmdaplus.serve_mixed`` — 16 rows on rings of 385 blocks of 16
    window_shape: tuple = (16, 128, 8, 128, 16, 385, 4096, 34000)
    #: (rows, query heads, KV heads, head dim): one retention layer's
    #: decode step of ``brumby14.serve_decode`` — 16 slots of float32
    #: state [8, 128, 8320], one of them idle
    retention_shape: tuple = (16, 40, 8, 128)


def check(ok, *why):
    """The smoke's assertion: raises where ``assert`` would, and is not
    stripped when the interpreter runs optimized."""
    if not ok:
        raise AssertionError(*why)


def say(leg, **facts):
    """One result line per leg: ``[smoke] leg key=value ...``."""
    print("[smoke] %-12s %s" % (leg, " ".join(
        "%s=%s" % (k, ("%.4g" % v) if isinstance(v, float) else v)
        for k, v in facts.items())), flush=True)


def report_device(require_tpu=True):
    """First act: what JAX found.  No TPU -> SystemExit before any
    work, so a CPU run can never print a result."""
    import jax
    import jaxlib
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    say("device", jax=jax.__version__, jaxlib=jaxlib.__version__,
        **device)
    if require_tpu and device["platform"] != "tpu":
        raise SystemExit(
            "chip_smoke: no TPU — jax.devices()[0].platform is %r; this "
            "script only runs on the chip (chiprun -- python3 "
            "chip_smoke.py)" % device["platform"])
    return device


def _counter_total(name, event=None):
    """Sum over one of compile_cache.install_metrics' counters (all
    labelled by ``event``), optionally of a single event."""
    from veles_tpu import telemetry
    inst = telemetry.registry.counter(name, labelnames=("event",))
    return sum(v for labels, v in inst.samples()
               if event is None or labels["event"] == event)


def memory_stats():
    import jax
    return jax.devices()[0].memory_stats() or {}


def _max_err(a, b):
    """(max abs error, the same over the reference's max magnitude)."""
    import jax.numpy as jnp
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    err = float(jnp.max(jnp.abs(a - b)))
    return err, err / max(float(jnp.max(jnp.abs(b))), 1e-30)


# ------------------------------------------------------------------ kernels
def check_flash(shape):
    """Flash forward and fused backward against ops.attention.attention
    at ``shape``, in f32 and bf16.  Absolute tolerances are bench.py
    phase_flash's; the relative ones keep a near-zero gradient from
    passing by magnitude alone.  The reference runs at the highest
    matmul precision so the error printed is the kernel's."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops.attention import attention
    from veles_tpu.ops.pallas.flash import flash_attention

    q, k, v = (jax.random.normal(kk, shape, jnp.float32) * 0.1
               for kk in jax.random.split(jax.random.key(0), 3))

    def loss(fn):
        return lambda q_, k_, v_: jnp.sum(
            fn(q_, k_, v_, causal=True).astype(jnp.float32) ** 2)

    with jax.default_matmul_precision("highest"):
        ref = attention(q, k, v, causal=True)
        gref = jax.grad(loss(attention), argnums=(0, 1, 2))(q, k, v)
    flash = jax.jit(lambda q_, k_, v_: flash_attention(
        q_, k_, v_, causal=True, backward="fused"))
    gflash = jax.jit(jax.grad(loss(flash_attention), argnums=(0, 1, 2)))
    facts = {"shape": "x".join(map(str, shape))}
    for name, dtype, tol_fwd, tol_bwd, tol_rel in (
            ("f32", jnp.float32, 5e-3, 5e-2, 2e-2),
            ("bf16", jnp.bfloat16, 5e-2, 5e-2, 5e-2)):
        qd, kd, vd = (x.astype(dtype) for x in (q, k, v))
        out = flash(qd, kd, vd)
        check(out.shape == shape and out.dtype == dtype, out.shape,
              out.dtype)
        fwd, fwd_rel = _max_err(out, ref)
        grads = gflash(qd, kd, vd)
        bwd, bwd_rel = max(_max_err(g, r) for g, r in zip(grads, gref))
        facts.update({name + "_fwd": fwd, name + "_bwd": bwd,
                      name + "_bwd_rel": bwd_rel})
        check(fwd <= tol_fwd and fwd_rel <= tol_rel, name, fwd, fwd_rel)
        check(bwd <= tol_bwd and bwd_rel <= tol_rel, name, bwd, bwd_rel)
    say("flash", **facts)


def check_paged(sizes, quant):
    """``paged_attention_decode`` against ``paged_attention_reference``
    at the serve leg's own geometry: one row per slot, staggered
    lengths, dead table entries on the dummy block.  ``quant``: the
    int8 QuantCache pool (block 32, the int8 sublane minimum) instead
    of the bf16 pool (block 16)."""
    import jax.numpy as jnp
    import numpy as np
    from veles_tpu.ops.attention import QuantCache, quantize_kv
    from veles_tpu.ops.pallas.paged import (paged_attention_decode,
                                            paged_attention_reference)

    b, hkv, hd = sizes.slots, sizes.n_heads, sizes.paged_hd
    bs = 32 if quant else 16
    nbm = sizes.serve_len // bs
    pool_blocks = b * nbm
    r = np.random.RandomState(7)
    dtype = jnp.float32 if quant else jnp.bfloat16
    q = jnp.asarray(r.randn(b, hkv, hd), dtype)
    pk = jnp.asarray(r.randn(1 + pool_blocks, hkv, bs, hd), dtype)
    pv = jnp.asarray(r.randn(1 + pool_blocks, hkv, bs, hd), dtype)
    if quant:
        pk, pv = QuantCache(*quantize_kv(pk)), QuantCache(*quantize_kv(pv))
    ids = r.permutation(pool_blocks).reshape(b, nbm) + 1
    pos = np.linspace(0, nbm * bs - 1, b).astype(np.int32)
    table = np.zeros((b, nbm), np.int32)
    for i in range(b):
        live = pos[i] // bs + 1
        table[i, :live] = ids[i, :live]
    table, pos = jnp.asarray(table), jnp.asarray(pos)
    out = paged_attention_decode(q, pk, pv, table, pos)
    ref = paged_attention_reference(q, pk, pv, table, pos)
    check(out.shape == q.shape and out.dtype == q.dtype, out.shape)
    err, rel = _max_err(out, ref)
    say("paged", pool="int8" if quant else "bf16", block=bs, hd=hd,
        rows=b, max_err=err, rel_err=rel)
    check(err <= 2e-2, err)


def check_window(sizes):
    """The window decode kernel (``veles_paged_decode_window``) against
    the gather formulation ``paged_attention_reference(window=)`` at a
    cell's own shapes: rows at positions from 1k to the deepest, each
    row's ring holding its last pages where the batcher writes them
    (entry ``page mod ring``), every other block of the pool poison.
    Prints the kernel's milliseconds (twelve calls chained in one
    program, the median of eight rounds)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from veles_tpu.ops.pallas.paged import (paged_attention_decode,
                                            paged_attention_reference)

    b, h, hkv, hd, bs, ring, window, deepest = sizes.window_shape
    r = np.random.RandomState(11)
    pos = np.linspace(min(1000, deepest), deepest, b).astype(np.int32)
    pos[1] = (pos[1] // bs) * bs + window % bs      # first on a page's edge
    pages = -(-window // bs) + 1
    pool_k = np.full((1 + b * ring, hkv, bs, hd), 1e4, np.float32)
    pool_v = np.full_like(pool_k, 1e4)
    table = (1 + r.permutation(b * ring).reshape(b, ring)).astype(np.int32)
    for i in range(b):
        last = pos[i] // bs
        for page in range(max(0, last - pages + 1), last + 1):
            blk = table[i, page % ring]
            pool_k[blk] = r.randn(hkv, bs, hd)
            pool_v[blk] = r.randn(hkv, bs, hd)
    q = jnp.asarray(r.randn(b, h, hd), jnp.bfloat16)
    pool_k, pool_v = (jnp.asarray(a, jnp.bfloat16) for a in (pool_k, pool_v))
    table, pos = jnp.asarray(table), jnp.asarray(pos)
    out = paged_attention_decode(q, pool_k, pool_v, table, pos,
                                 window=window)
    ref = paged_attention_reference(q, pool_k, pool_v, table, pos,
                                    window=window)
    check(out.shape == q.shape and out.dtype == q.dtype, out.shape)
    err, rel = _max_err(out, ref)

    @jax.jit
    def chained(q):
        for _ in range(12):
            q = paged_attention_decode(q, pool_k, pool_v, table, pos,
                                       window=window)
        return q

    jax.block_until_ready(chained(q))
    times = []
    for _ in range(8):
        t0 = time.perf_counter()
        jax.block_until_ready(chained(q))
        times.append((time.perf_counter() - t0) / 12 * 1e3)
    keys = int(np.minimum(np.asarray(pos) + 1, window).sum())
    say("window", rows=b, heads="%d/%d" % (h, hkv), hd=hd, block=bs,
        ring=ring, window=window, keys=keys, max_err=err, rel_err=rel,
        kernel_ms=sorted(times)[len(times) // 2])
    check(err <= 2e-2, err)


def check_retention(sizes):
    """The retention decode kernel (``veles_retention_decode``) against
    the XLA step it replaces (``ops.retention.retention_step``) at a
    cell's own shapes: every active row's output and new state, an idle
    row's state untouched.  Prints the kernel's milliseconds (twelve
    steps chained in one program on a donated state, the median of
    eight rounds) and the share of the HBM's 819 GB/s its state's one
    read and one write come to."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from veles_tpu.ops import retention

    b, h, hkv, hd = sizes.retention_shape
    r = np.random.RandomState(13)
    q, k, v = (jnp.asarray(r.randn(b, n, hd), jnp.bfloat16)
               for n in (h, hkv, hkv))
    logg = jnp.asarray(np.log(r.uniform(0.5, 0.999, (b, hkv))),
                       jnp.float32)
    dp = retention.phi_width(hd)
    state = retention.RetentionState(
        jnp.asarray(r.randn(b, hkv, hd, dp), jnp.float32),
        jnp.asarray(np.abs(r.randn(b, hkv, dp)) + 8.0, jnp.float32))
    active = jnp.arange(b) != b - 2
    prev = retention.KERNEL
    retention.KERNEL = True
    try:
        step = jax.jit(retention.retention_step_rows)
        out, new = step(q, k, v, logg, state, active)
        ref, ref_state = jax.jit(retention.retention_step)(
            q, k, v, logg, state)
        on = np.asarray(active)
        err, rel = _max_err(out[on], ref[on])
        s_err, _ = _max_err(new.s[on], ref_state.s[on])
        check(bool(jnp.all(new.s[~on] == state.s[~on])), "idle row moved")

        @functools.partial(jax.jit, donate_argnums=(0,))
        def chained(st):
            y = 0.0
            for _ in range(12):
                o, st = retention.retention_step_rows(q, k, v, logg, st,
                                                      active)
                y = y + o
            return st, y

        st, _ = chained(state)
        times = []
        for _ in range(8):
            t0 = time.perf_counter()
            st, y = chained(st)
            jax.block_until_ready(y)
            times.append((time.perf_counter() - t0) / 12 * 1e3)
    finally:
        retention.KERNEL = prev
    ms = sorted(times)[len(times) // 2]
    moved = 2 * 4 * int(on.sum()) * hkv * hd * dp
    say("retention", rows=b, idle=int((~on).sum()),
        heads="%d/%d" % (h, hkv), hd=hd, features=dp, max_err=err,
        rel_err=rel, state_err=s_err, step_ms=ms,
        state_gb_s=moved / ms / 1e6)
    check(rel <= 2e-3 and s_err <= 1e-3, (err, rel, s_err))


def check_dsa_prefill(sizes, live):
    """``veles_dsa_prefill`` against the XLA loop it replaces
    (``ops.attention.dsa_attend_blocks``) for the last ``tq`` queries of
    a row whose first ``live`` keys are live, under the mask the
    selection gives them; whatever lies past the live blocks is poison
    to both.  Prints the milliseconds of each: six calls chained in one
    program (a call's output is the next one's queries), the median of
    eight — the isolated probe of PERF.md, PR 32."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from veles_tpu.ops import attention as att
    from veles_tpu.ops.pallas import dsa

    h, hkv, tq, hd, tk, topk = sizes.dsa_shape
    kb = min(att.DSA_KEY_BLOCK, tk)
    n_live = att.dsa_live_blocks(live, tk)[0]
    check(att.dsa_prefill_tiles(tq, tk, hd), "shapes do not tile")
    r = np.random.RandomState(live)

    def a(*shape):
        return jnp.asarray(r.randn(*shape), jnp.bfloat16)

    q, k, v = a(1, hkv, h // hkv, tq, hd), a(1, hkv, tk, hd), \
        a(1, hkv, tk, hd)
    start = live - tq

    @jax.jit
    def select(qi, ki, wi):
        scores = att.index_scores(qi, ki, wi)             # [1, tq, tk]
        valid = jnp.arange(tk)[None] <= start + jnp.arange(tq)[:, None]
        chosen = att.dsa_select(scores, valid[None], topk)
        chosen = chosen.reshape(1, tq, tk // kb, kb).transpose(2, 0, 1, 3)
        return chosen.astype(jnp.int8).at[n_live:].set(1)

    mask = select(a(1, 4, tq, 64), a(1, tk, 64),
                  jnp.asarray(r.randn(1, tq, 4), jnp.float32))
    scale = hd ** -0.5
    k, v = (x.at[:, :, n_live * kb:].set(1e30) for x in (k, v))
    # the operands ride as arguments: closed over, they would be
    # constants of the programs (hundreds of MB to compile and cache)
    fns = {"kernel": lambda q, k, v, mask: dsa.dsa_prefill_attention(
               q, k, v, mask, start, n_live, scale),
           "loop": lambda q, k, v, mask: att.dsa_attend_blocks(
               q, k, v, mask, jnp.int32(n_live), scale)}
    out = {name: jax.jit(fn)(q, k, v, mask) for name, fn in fns.items()}
    check(out["kernel"].shape == q.shape
          and out["kernel"].dtype == q.dtype, out["kernel"].shape)
    err, rel = _max_err(out["kernel"], out["loop"])

    def chained_ms(fn, calls=6, rounds=8):
        chain = jax.jit(lambda q, *rest: functools.reduce(
            lambda x, _: fn(x, *rest), range(calls), q))
        chain(q, k, v, mask).block_until_ready()
        took = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            chain(q, k, v, mask).block_until_ready()
            took.append((time.perf_counter() - t0) / calls * 1e3)
        return sorted(took)[rounds // 2]

    say("dsa", live=live, blocks=n_live, shape="x".join(map(str, q.shape)),
        max_err=err, rel_err=rel,
        **{name + "_ms": chained_ms(fn) for name, fn in fns.items()})
    check(err <= 2e-2, err)


# -------------------------------------------------------------------- train
def build_flagship(sizes, mesh_config=None):
    """The ``lm_large`` workflow exactly as bench.py phase_lm_large
    builds it (rung ("dots", 16) of the ladder — the only rung tried),
    over seeded random tokens."""
    import numpy as np
    from veles_tpu import prng
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.models import zoo
    from veles_tpu.models.standard_workflow import StandardWorkflow

    prng.seed_all(5)
    n = sizes.batch * 4
    toks = np.random.RandomState(0).randint(
        0, sizes.vocab, (n, sizes.seq)).astype(np.int32)
    loader = FullBatchLoader(None, data=toks, labels=toks,
                             minibatch_size=sizes.batch,
                             class_lengths=[0, 0, n])
    wf = StandardWorkflow(
        layers=zoo.transformer_lm(
            vocab_size=sizes.vocab, d_model=sizes.d_model,
            n_heads=sizes.n_heads, n_layers=sizes.n_layers, dropout=0.0,
            impl="flash", pos="rope", solver="adamw", lr=6e-4,
            tie_embeddings=True, remat="dots"),
        loader=loader, loss="lm", gd_defaults={"clip_norm": 1.0},
        decision_config={"max_epochs": 1000}, steps_per_dispatch=SPD,
        mesh_config=mesh_config, name="chip-smoke-lm")
    wf.initialize()
    return wf


def run_steps(wf, steps):
    for _ in range(steps):
        wf.loader.run()
        wf.trainer.run()
    wf.trainer.flush()


def compile_sweep(trainer, require_tpu=True):
    """Compile the fused train sweep ahead of its first dispatch (the
    dispatch then finds this executable; nothing compiles twice) and
    say what the compiler made of it: seconds, the program's own memory
    needs — ``device.memory_stats()`` counts live buffers, not a
    program's temporaries — and, on the chip, that the flash kernel is
    in it as a Mosaic custom call and not as interpreted HLO."""
    t0 = time.perf_counter()
    compiled = trainer.lower_train_sweep().compile()
    compile_s = time.perf_counter() - t0
    if require_tpu:
        check("tpu_custom_call" in compiled.as_text(),
              "no Mosaic custom call in the compiled train sweep")
    mem = compiled.memory_analysis()
    say("compile", what="train_sweep", steps_per_dispatch=SPD,
        compile_s=compile_s,
        mosaic_custom_call="yes" if require_tpu else "not-checked",
        argument_bytes=getattr(mem, "argument_size_in_bytes", None),
        temp_bytes=getattr(mem, "temp_size_in_bytes", None),
        output_bytes=getattr(mem, "output_size_in_bytes", None))
    return compiled


def train_leg(wf, sizes):
    """Two warm-up sweeps, then eight steps on ``wf`` (from
    :func:`build_flagship`).  Returns the eight-step loss per token."""
    import jax
    import numpy as np
    from veles_tpu.loader.base import TRAIN

    tr = wf.trainer
    probe = next(layer.name for layer in tr.layers
                 if layer.type == "layer_norm")

    def probe_leaves():
        return [np.array(a) for a in
                jax.tree_util.tree_leaves(tr.params[probe])]

    before = probe_leaves()
    compile_s = _counter_total("veles_compile_seconds_total")
    t0 = time.perf_counter()
    run_steps(wf, WARM_SWEEPS * SPD)
    jax.block_until_ready(tr.class_stats)
    warm_s = time.perf_counter() - t0
    compile_s = _counter_total("veles_compile_seconds_total") - compile_s
    tr.read_class_stats(TRAIN)
    tr.reset_epoch_stats()
    jax.block_until_ready(tr.class_stats)

    compiles = _counter_total("veles_compile_events_total")
    run_steps(wf, STEPS)
    stats = tr.read_class_stats(TRAIN)
    new_compiles = _counter_total("veles_compile_events_total") - compiles
    check(new_compiles == 0,
          "%d compile events inside the eight steps" % new_compiles)

    check(stats["count"] == STEPS * sizes.batch * (sizes.seq - 1), stats)
    loss = stats["loss"] / stats["count"]
    check(np.isfinite(loss), stats)
    health = tr._health_host
    check(float(health["anomalies"]) == 0.0
          and float(health["skipped"]) == 0.0, health)
    check(wf.sentinel is not None and not wf.sentinel.history
          and wf.sentinel.strikes == 0, "sentinel struck")
    after = probe_leaves()
    moved = max(float(np.max(np.abs(a - b)))
                for a, b in zip(after, before))
    check(moved > 0.0 and all(np.isfinite(a).all() for a in after),
          "parameters did not change (or went non-finite)")
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(tr.params))
    say("train", params_M=n_params / 1e6, layers=sizes.n_layers,
        d_model=sizes.d_model, batch=sizes.batch, seq=sizes.seq,
        steps=STEPS, loss_per_token=loss, param_max_delta=moved,
        anomalies=0, compiles_in_steps=int(new_compiles),
        first_two_sweeps_s=warm_s, of_which_jax_compile_s=compile_s,
        peak_bytes_in_use=memory_stats().get("peak_bytes_in_use"))
    return loss


def barrier_line(wf):
    """One four-step sweep, timed twice: closed by ``block_until_ready``
    on the stats accumulators, and closed by fetching the loss value.
    bench.py's ``_fetch_sync`` exists because the first once returned
    early on the old remote backend; this prints whether it still
    does.  A fact for the next benchmark PR, not a measurement."""
    import jax

    def timed(close):
        jax.device_get(wf.trainer.class_stats[2]["loss"])   # drain
        t0 = time.perf_counter()
        run_steps(wf, SPD)
        close()
        return (time.perf_counter() - t0) * 1e3

    block_ms = timed(lambda: jax.block_until_ready(wf.trainer.class_stats))
    fetch_ms = timed(lambda: float(jax.device_get(
        wf.trainer.class_stats[2]["loss"])))
    say("barrier", sweep_steps=SPD, block_until_ready_ms=block_ms,
        device_get_loss_ms=fetch_ms)


# -------------------------------------------------------------------- serve
def _post(api, body):
    """One HTTP POST to the work endpoint -> (status, raw body)."""
    conn = http.client.HTTPConnection(api.host, api.port, timeout=600)
    try:
        conn.request("POST", api.path, json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def check_no_leaks(engine):
    """leak_check() all zero, once the engine has gone idle (it reads batcher
    state only the engine thread may touch while work is in flight)."""
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        m = engine.metrics()
        if not m["queued"] and not m["in_flight"]:
            break
        time.sleep(0.05)
    leaks = engine.leak_check()
    check(leaks.pop("engine_thread_alive") is True, "engine thread died")
    check(not any(leaks.values()), leaks)


def serve_leg(wf, sizes, quant):
    """The trained weights behind the REST endpoint on a paged pool.
    bf16 pool: eight concurrent buffered POSTs plus one NDJSON stream
    that repeats the first request — greedy decode through one compiled
    tick is row-independent, so the stream must reproduce it token for
    token.  int8 pool (``quant``): one request, so the quantized fused
    kernel compiles and answers."""
    import jax.numpy as jnp
    import numpy as np
    from veles_tpu.models.generate import LMGenerator
    from veles_tpu.services.restful import RESTfulAPI

    block = 32 if quant else 16
    gen = LMGenerator(wf.trainer, max_len=sizes.serve_len,
                      cache_dtype="int8" if quant else jnp.bfloat16)
    api = RESTfulAPI(lambda x: x, (sizes.serve_len,), port=0,
                     generator=gen, continuous_slots=sizes.slots,
                     paged_block=block,
                     pool_tokens=sizes.slots * sizes.serve_len)
    check(api.engine.cb.fused is True, "not the fused tick")
    r = np.random.RandomState(11)
    reqs = [(r.randint(0, sizes.vocab, plen).tolist(), new)
            for plen, new in zip(sizes.prompt_lens, sizes.max_news)]
    if quant:
        reqs = reqs[:1]
    api.start()
    try:
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(len(reqs) + 1) as pool:
            futs = [pool.submit(_post, api, {
                "input": prompt, "generate": {"max_new": new}})
                for prompt, new in reqs]
            stream = None if quant else pool.submit(_post, api, {
                "input": reqs[0][0],
                "generate": {"max_new": reqs[0][1], "stream": True}})
            answers = [f.result() for f in futs]
            streamed = None if stream is None else stream.result()
        wall = time.perf_counter() - t0
        rows = []
        for (prompt, new), (status, raw) in zip(reqs, answers):
            check(status == 200, status, raw[:300])
            row = json.loads(raw)["result"][0]
            check(row[:len(prompt)] == prompt, "prompt not echoed")
            check(len(row) == len(prompt) + new, len(row), len(prompt), new)
            check(all(0 <= t < sizes.vocab for t in row), "token range")
            rows.append(row)
        if streamed is not None:
            status, raw = streamed
            check(status == 200, status, raw[:300])
            lines = [json.loads(ln) for ln in raw.splitlines() if ln]
            check(lines[-1].get("done") is True, lines[-1])
            fresh = [t for ln in lines[:-1] for t in ln["tokens"]]
            check(len(fresh) == reqs[0][1], len(fresh), reqs[0][1])
            check(reqs[0][0] + fresh == lines[-1]["result"] == rows[0],
                  "stream disagrees with its buffered twin")
        check(api.engine.cb.fused is True, "not the fused tick")
        check_no_leaks(api.engine)
    finally:
        api.stop()
    say("serve", pool="int8" if quant else "bf16", block=block,
        requests=len(reqs), streamed=int(streamed is not None),
        new_tokens=sum(new for _, new in reqs), fused=True, leaks=0,
        wall_incl_compile_s=wall)


# --------------------------------------------------------------------- body
def run(sizes=Sizes(), require_tpu=True):
    """The whole smoke.  ``require_tpu=False`` (the CPU rehearsal in
    tests/test_chip_smoke.py) drops only what a CPU cannot show: the
    platform check and the compiled-by-Mosaic assertions."""
    device = report_device(require_tpu)
    from veles_tpu import compile_cache, tuner
    from veles_tpu.ops.pallas import autodetect_interpret
    cache_dir = compile_cache.enable()
    winners = tuner.get_tuner().cache
    say("setup", compile_cache=cache_dir, tuner_cache=winners.path,
        tuner_entries=len(winners))
    if require_tpu:
        check(autodetect_interpret(None) is False, "interpret mode")

    # the train leg goes first so that ``peak_bytes_in_use`` (a
    # process-lifetime high-water mark) is its own: the kernel checks'
    # O(T^2) references would otherwise set it
    try:
        wf = build_flagship(sizes)
        compile_sweep(wf.trainer, require_tpu)
        train_leg(wf, sizes)
    except BaseException:
        # ("dots", 16) is the only rung: say what the device held and
        # fail — a smaller batch under the same name would be a fallback
        print("[smoke] device.memory_stats(): %r" % (memory_stats(),),
              flush=True)
        raise
    barrier_line(wf)

    for shape in sizes.flash_shapes:
        check_flash(shape)
    check_paged(sizes, quant=False)
    check_paged(sizes, quant=True)
    for live in sizes.dsa_live:
        check_dsa_prefill(sizes, live)
    check_window(sizes)
    check_retention(sizes)
    say("kernels", interpret=autodetect_interpret(None))

    serve_leg(wf, sizes, quant=False)
    serve_leg(wf, sizes, quant=True)
    say("cache", dir=cache_dir,
        hits=int(_counter_total("veles_compile_cache_events_total",
                                event="cache_hits")),
        requests=int(_counter_total("veles_compile_cache_events_total",
                                    event="compile_requests_use_cache")),
        peak_bytes_in_use=memory_stats().get("peak_bytes_in_use"))
    return {"ok": True, "device": device}


def main():
    t0 = time.perf_counter()
    result = run()
    say("done", wall_s=time.perf_counter() - t0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
