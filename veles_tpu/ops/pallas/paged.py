"""Paged-KV decode attention as a Pallas TPU kernel.

The paged continuous batcher (models/generate.py PagedContinuousBatcher)
keeps every slot's KV cache in a SHARED block pool addressed through
per-slot block tables.  Its first implementation gathered each row's
blocks into a dense [B, H, T, hd] view every tick, ran the dense decode
core, and scattered one position back — ~2x cache traffic vs dense
slots.  This kernel erases the gather — the classic paged-attention
move (Kwon et al. 2023) recast for the TPU: the block table and the
positions ride in as SCALAR-PREFETCH arguments, and the kernel reads the
pool through them.

The schedule of work follows the row's LIVE pages, in steps and in
bytes alike.  One grid step is one row (times a group of KV heads where
VMEM asks for it); inside, a loop runs ``pos[b] // bs + 1`` pages — a
trip count read from the prefetched ``pos``, so a short row costs a
short loop and a dead table entry costs nothing: no grid step, no loop
trip, no copy.  A trip fetches a CHUNK of pages, each page with all the
step's KV heads in one copy ([Hkv, bs, hd] is contiguous in the pool's
own layout: 64 KiB a page at 16 heads x 16 keys x 128 x bf16), into one
slot of a double buffer while the other slot is computed on, and runs
the online softmax (f32 ``m``/``l``/``acc``) over the whole chunk's keys.
Pages a chunk and heads a step come from the pool's shape against a
VMEM budget (:func:`page_schedule`) — no knob.  Dense decode by
contrast streams every slot's full max_len; the first version of this
kernel read only live bytes but paid a grid step per (row, KV head,
table entry), 65,536 a call at 32 rows x 16 heads x 128 entries, 77% of
them past the row's position (PERF.md, PR 27).

Two fetch styles, one schedule, chosen by what the kernel sees in its
input: where a page's tiles fill the 128 lanes (head dim a multiple of
128, plain pool) the kernel copies pages by hand
(``pltpu.make_async_copy`` out of the pool left in HBM); where they do
not (head dim 64; a quantized pool's [bs, 1] scale tiles) Mosaic
cannot slice the pool by hand, and the chunk's pages arrive as
BlockSpec'd operands indexed through the table, pipelined by Mosaic —
there the grid walks the table in chunks, and a chunk past the row's
last live one costs an (empty) grid step but no copy and no compute.

A sliding-WINDOW layer's rows (``window``) keep their last keys in a
RING of pages: the table holds ``nbm`` entries, the key at absolute
position t lives in entry ``(t // bs) mod nbm``, and a row attends the
keys of ``[max(0, pos - window + 1), pos]`` only.  The same two kernels
take a third scalar-prefetch argument, the row's first live position:
the loop runs the pages from that position's to ``pos``'s (at most
``ceil(window / bs) + 1``, whatever the context's length), finds each
through the ring, and masks the keys of the first page that fell out
of the window.  Such a call carries its own name in a device trace
(``KERNEL_NAMES_WINDOW``).

Layout contract (matches PagedContinuousBatcher):
  q      [B, Hq, hd]        query at the position being decoded (rope
                            already applied), Hq = G * Hkv
  pool_k [1+P, Hkv, bs, hd] block 0 reserved as the dummy target
  pool_v [1+P, Hkv, bs, hd]
  table  [B, nbm] int32     per-row pool-block ids (0 = unallocated)
  pos    [B] int32          per-row position just written; keys
                            0..pos[b] inclusive are live
  -> out [B, Hq, hd]

Ground truth: ``paged_attention_reference`` (the gather formulation) —
the tests pin kernel == reference; off-TPU the kernel runs in interpret
mode like every kernel in this package.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.pallas import autodetect_interpret, register_kernel_audit

NEG_INF = -1e30
_LANES = 128

#: min sublane tile for the q block: bf16 wants 16 rows, f32 8 — 16
#: covers both; a padded row rides the same pass of a K/V tile through
#: the MXU as the real ones, so it costs nothing beside them
_MIN_G = 16

#: the decode kernel's two pool flavors, keyed by "is the pool a
#: QuantCache": (``pallas_call`` name, VP6xx audit display name).  The
#: first is the HLO instruction's name in a device trace
#: (``%veles_paged_decode.1 = ... custom-call``) — what the benchmark's
#: ``paged_roofline_pct`` sums; a contract recorded in PERF.md that any
#: later implementation of this layer keeps.  Both names come from
#: here so the lint and the trace cannot drift apart.
KERNEL_NAMES = {
    False: ("veles_paged_decode", "paged.decode"),
    True: ("veles_paged_decode_q8", "paged.decode.q8"),
}

#: the same two flavors where the rows are a sliding-window layer's
#: (``window``): a ring of pages walked from the row's first live
#: position.  A table of their own, so that a reader of the whole-context
#: kernel's time (``KERNEL_NAMES``) never counts a window layer's.
KERNEL_NAMES_WINDOW = {
    False: ("veles_paged_decode_window", "paged.decode.window"),
    True: ("veles_paged_decode_window_q8", "paged.decode.window.q8"),
}


#: VMEM the page buffers may take: K and V (and, for a quantized pool,
#: their scale tiles), two slots each — one computed on while the other
#: fills.  8 MiB holds 32 pages a chunk at the 1.3B serving shape
#: (16 KV heads x 16 keys x 128 x bf16 = 64 KiB a page), half of the
#: 16 MiB a v5e kernel may scope; the q/o blocks and the accumulators
#: take under 1 MiB beside them.  Measured on the v5e at that shape
#: (PERF.md, PR 27): 32 pages a chunk beat 16 and 8 at 466 keys a row
#: (0.43 against 0.51 and 0.46 ms a call) and at full 2048-key rows
#: (0.95 against 1.47 and 1.49) — more copies in flight and fewer
#: rescales of the accumulators a key.
_PAGE_BUFFER_BYTES = 8 << 20


def _ceil_to(n, k):
    return -(-n // k) * k


def _head_page_bytes(bs, hd, dtype, quant):
    """VMEM bytes of one KV head of one pool page as the buffers hold
    it: the [bs, hd] data tile padded to the dtype's (sublane, 128-lane)
    tile, plus — quantized pools — its [bs, 1] f32 scale column, which
    VMEM pads to 128 lanes."""
    import numpy as np
    from veles_tpu.ops.pallas import mosaic_sublane_min
    item = np.dtype(dtype).itemsize
    data = (_ceil_to(bs, mosaic_sublane_min(dtype))
            * _ceil_to(hd, _LANES) * item)
    return data + (_ceil_to(bs, 8) * _LANES * 4 if quant else 0)


def _sliceable(hd, quant):
    """Whether a kernel can copy a page out of the pool by hand: Mosaic
    slices an HBM operand only where its minor dim fills the 128 lanes,
    which a head dim of 64 and a [bs, 1] scale tile do not."""
    return hd % _LANES == 0 and not quant


def page_schedule(hkv, bs, hd, dtype, nbm, quant=False):
    """The decode kernel's schedule of work, from the pool's own shape:
    ``(chunk, heads)`` — pages fetched a loop trip, and KV heads a grid
    step handles.  Every copy moves one whole page over ``heads`` heads;
    ``chunk`` of them are in flight into one buffer slot while the
    other slot is computed on.  All heads a step where a lane-wide
    chunk (128 keys) of them fits ``_PAGE_BUFFER_BYTES``; fat pages
    (large blocks, quantized pools with their padded scale tiles)
    halve the heads until one does.  ``chunk`` is a power of two (so
    chunk x bs stays a lane multiple) and never more than the table."""
    per_head = 4 * _head_page_bytes(bs, hd, dtype, quant)  # K, V x 2 slots
    heads = hkv
    while (heads % 2 == 0
           and _PAGE_BUFFER_BYTES // (heads * per_head) * bs < _LANES):
        heads //= 2
    chunk = max(1, min(_PAGE_BUFFER_BYTES // (heads * per_head), nbm))
    return 1 << (chunk.bit_length() - 1), heads


def _attend_chunk(q_ref, tile, acc, m, l, k0, pos, scale, heads,
                  first=None):
    """The online-softmax update of ``heads`` KV heads over one chunk of
    keys starting at absolute position ``k0``: f32 ``m``/``l``/``acc``,
    scores masked past ``pos`` (and, a window layer's, before
    ``first``), ``p`` cast to the values' dtype for
    ``p.v``.  ``tile(stream, h)`` hands head ``h``'s [keys, hd] operand
    (stream 0 = keys, 1 = values).  The heads are a loop traced once
    and unrolled at lowering: Mosaic sees straight-line code it can
    interleave (rolled, the call takes 0.66 ms where unrolled takes
    0.51: PERF.md, PR 27), and a process that traces the tick pays for
    one head, not for sixteen."""
    def head(h, carry):
        k, v = tile(0, h), tile(1, h)
        s = jax.lax.dot_general(
            q_ref[0, h].astype(k.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kpos <= pos
        if first is not None:
            valid = valid & (kpos >= first)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m[h][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, corr)
        l[h] = l[h] * corr + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), l.shape[1:])
        m[h] = jnp.broadcast_to(m_new, m.shape[1:])
        acc[h] = acc[h] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, heads, head, 0, unroll=True)


def _attend_init(acc, m, l):
    acc[...] = jnp.zeros_like(acc)
    m[...] = jnp.full_like(m, NEG_INF)
    l[...] = jnp.zeros_like(l)


def _attend_finish(o_ref, acc, l):
    o_ref[0] = (acc[...] / jnp.maximum(l[...][:, :, :1], 1e-30)).astype(
        o_ref.dtype)


def _decode_kernel(table_ref, pos_ref, *refs, scale, bs, nbm, chunk,
                   heads, windowed=False):
    """One grid step = one row x ``heads`` KV heads: walk the row's LIVE
    pages (``pos // bs + 1`` of them — a trip count read from the
    prefetched ``pos``, data and not shape) in chunks of ``chunk``
    pages, each page ONE copy of all ``heads`` heads from the pool in
    HBM into a double-buffered VMEM slot — the next chunk in flight
    while this one is computed — and run the online softmax over a
    whole chunk's keys at a time.  ``windowed``: a third prefetched
    scalar a row, its first live position; the walk starts at that
    position's page and finds a page through the ring."""
    if windowed:
        first_ref, *refs = refs
    (q_ref, k_pool, v_pool, o_ref, k_buf, v_buf, sem, acc, m, l) = refs
    b = pl.program_id(0)
    h0 = pl.program_id(1) * heads
    pos = pos_ref[b]
    if windowed:
        first = first_ref[b]
        page0 = first // bs
        n_pages = pos // bs - page0 + 1
    else:
        first, page0 = None, 0
        n_pages = jnp.minimum(pos // bs, nbm - 1) + 1
    n_chunks = (n_pages + chunk - 1) // chunk
    keys, hd = chunk * bs, q_ref.shape[-1]

    def page_copies(c, slot, i):
        if windowed:
            page = table_ref[b, (page0 + c * chunk + i) % nbm]
        else:
            page = table_ref[b, jnp.minimum(c * chunk + i, nbm - 1)]
        return [pltpu.make_async_copy(pool.at[page, pl.ds(h0, heads)],
                                      buf.at[slot, i], sem.at[n, slot])
                for n, (pool, buf) in enumerate(((k_pool, k_buf),
                                                 (v_pool, v_buf)))]

    def each_page(c, visit):
        def page(i, carry):
            visit(i, c * chunk + i < n_pages)
            return carry
        jax.lax.fori_loop(0, chunk, page, 0)

    def start(c, slot):
        def visit(i, live):
            @pl.when(live)
            def _():
                for copy in page_copies(c, slot, i):
                    copy.start()

            # a dead slot of the row's last chunk is never fetched: its
            # keys are masked, and its values are zeroed here so that
            # 0 x (whatever VMEM held) cannot reach the output
            @pl.when(jnp.logical_not(live))
            def _():
                v_buf[slot, i] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)
        each_page(c, visit)

    def wait(c, slot):
        def visit(i, live):
            @pl.when(live)
            def _():
                for copy in page_copies(c, slot, i):
                    copy.wait()
        each_page(c, visit)

    _attend_init(acc, m, l)
    start(0, 0)

    def body(c, carry):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            start(c + 1, 1 - slot)

        wait(c, slot)
        _attend_chunk(
            q_ref, lambda stream, h: (k_buf, v_buf)[stream][
                slot, :, h].reshape(keys, hd),
            acc, m, l,
            c * keys + page0 * bs if windowed else c * keys,
            pos, scale, heads, first)
        return carry

    jax.lax.fori_loop(0, n_chunks, body, 0)
    _attend_finish(o_ref, acc, l)


def _decode_kernel_specs(table_ref, pos_ref, *refs, scale, bs,
                         chunk, heads, quant, windowed=False):
    """The same schedule where Mosaic cannot slice a page out of the
    pool by hand (a manual copy's source must be lane-aligned: hd a
    multiple of 128, no [bs, 1] scale tiles): the ``chunk`` pages of a
    step arrive as ``chunk`` BlockSpec'd operands indexed through the
    prefetched table, every KV head of the step a block, pipelined by
    Mosaic.  The grid walks a row's table in chunks; a chunk past the
    row's last live one keeps that one's indices (nothing is copied)
    and skips its compute.

    A quantized pool's int8 tile (HBM streams one byte per element —
    the whole point) is widened in VMEM by its per-position f32 scale
    column, with f32 accumulation throughout — the dense int8 cache's
    math, just narrower on the wire.  ``windowed``: a third prefetched
    scalar a row, its first live position; chunk ``c`` then holds the
    pages from that position's on (the index maps find them through the
    ring)."""
    if windowed:
        first_ref, *refs = refs
    q_ref, *refs = refs
    n_streams = 4 if quant else 2
    pages = [refs[n * chunk:(n + 1) * chunk] for n in range(n_streams)]
    o_ref, acc, m, l = refs[n_streams * chunk:]
    b, c = pl.program_id(0), pl.program_id(2)
    pos = pos_ref[b]
    first = first_ref[b] if windowed else None

    def k0():
        """the absolute position of the chunk's first key"""
        at = c * chunk * bs
        return at + first // bs * bs if windowed else at

    def tile(stream, h):
        stream *= n_streams // 2                  # k [, scale], v [, scale]
        parts = [ref[0, h] for ref in pages[stream]]
        if quant:
            parts = [x.astype(jnp.float32) * s[0, h]
                     for x, s in zip(parts, pages[stream + 1])]
        return jnp.concatenate(parts, axis=0)

    @pl.when(c == 0)
    def _():
        _attend_init(acc, m, l)

    @pl.when(k0() <= pos)
    def _():
        _attend_chunk(q_ref, tile, acc, m, l, k0(), pos, scale, heads,
                      first)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        _attend_finish(o_ref, acc, l)


def _resolve_block_g(g, hd, dtype, block_g=None):
    """Resolve the q-group sublane pad: explicit argument > site config
    (``root.common.serve.paged_block_g``) > autotuner winner
    (``veles_tpu.tuner``, kernel ``paged.decode``) > ``_MIN_G``.  Always
    clamped to hold the real group (>= g) and the sublane tile.
    Reachable from the audit hook and every decode trace, so a
    non-integer config value falls through to the tuner instead of
    raising (same contract as :func:`preferred_pool_block`)."""
    if block_g is None:
        from veles_tpu.config import root
        cfg = root.common.get("serve", {})
        block_g = (cfg or {}).get("paged_block_g") if cfg else None
    try:
        block_g = int(block_g or 0)
    except (TypeError, ValueError):      # "auto", garbage
        block_g = 0
    if not block_g:
        try:
            from veles_tpu import tuner
            win = tuner.lookup("paged.decode",
                               tuner.paged_shape_key(hd, g), dtype)
            if win:
                block_g = int(win.get("block_g") or 0)
        except Exception:  # noqa: BLE001 — tuning is advisory
            pass
    return max(int(block_g or 0), g, _MIN_G)


def preferred_pool_block(hd, g=1, dtype=jnp.bfloat16, default=16):
    """The KV pool block size serving should allocate when the caller
    did not pin one: site config (``root.common.serve.paged_block``) >
    autotuner winner > ``default``.  The pool layout is decided at
    admission time by PagedContinuousBatcher — the kernel then simply
    follows whatever block the pool was built with, so THIS is the
    point where a tuned ``paged.decode`` block takes effect.  The
    config value goes through the ONE ``serve.paged_block`` grammar
    (``models.generate.parse_paged_block`` — shared with the engine,
    so the audit hook and serving can never disagree): only an
    explicit positive block pins; ``"auto"``/``-1``/off-values/garbage
    fall through to the tuner — this is reachable from the lint's
    audit hook, so it must never raise on any config value."""
    from veles_tpu.config import root
    cfg = root.common.get("serve", {})
    pinned = (cfg or {}).get("paged_block") if cfg else None
    if pinned is not None:
        try:
            from veles_tpu.models.generate import parse_paged_block
            _, block = parse_paged_block(pinned)
        except (TypeError, ValueError):  # garbage ("fast", [1], ...)
            block = None
        if block:
            return int(block)
    try:
        from veles_tpu import tuner
        win = tuner.lookup("paged.decode", tuner.paged_shape_key(hd, g),
                           dtype)
        if win and win.get("block"):
            return int(win["block"])
    except Exception:  # noqa: BLE001 — tuning is advisory
        pass
    # untuned fallback is sublane-aware: int8 pools (QuantCache) need
    # 32-row tiles on real silicon, bf16/f32 keep the historical 16
    from veles_tpu.ops.pallas import mosaic_sublane_min
    return max(int(default), mosaic_sublane_min(dtype))


def paged_attention_decode(q, pool_k, pool_v, table, pos, scale=None,
                           interpret=None, block_g=None, window=None):
    """One decode step of attention over a paged KV pool (see module
    docstring for the layout contract).  Returns [B, Hq, hd].

    ``pool_k``/``pool_v`` may be plain arrays OR
    ``ops.attention.QuantCache`` pairs (int8 data [1+P, Hkv, bs, hd] +
    f32 per-position scales [1+P, Hkv, bs, 1]) — the quantized pool
    streams one byte per KV element from HBM and dequantizes in
    kernel with f32 accumulation (``_decode_kernel_specs``).

    ``block_g`` — the q-group sublane pad (rows per grid step); unset,
    it resolves through config > autotuner > ``_MIN_G``; quantized
    pools key the tuner lookup by the POOL dtype (int8), matching how
    ``tuner.sweeps.sweep_paged(dtype="int8")`` records winners.

    ``window``: the rows are a sliding-window layer's — ``table`` is a
    ring of ``nbm`` pages (the key at position t in entry ``(t // bs)
    mod nbm``; ``nbm * bs >= window + bs``) and a row attends the keys
    of ``[max(0, pos - window + 1), pos]``."""
    from veles_tpu.ops.attention import QuantCache
    quant = isinstance(pool_k, QuantCache)
    kd = pool_k.data if quant else pool_k
    hq, hd = q.shape[1:]
    hkv, bs = kd.shape[1:3]
    if hq % hkv:
        raise ValueError("Hq %d %% Hkv %d != 0" % (hq, hkv))
    gp = _resolve_block_g(hq // hkv, hd, kd.dtype if quant else q.dtype,
                          block_g)
    scale = (hd ** -0.5) if scale is None else scale
    chunk, heads = page_schedule(hkv, bs, hd, kd.dtype, table.shape[1],
                                 quant)
    fn = _decode_fn(float(scale), gp, chunk, heads, _sliceable(hd, quant),
                    autodetect_interpret(interpret), window is not None)
    if window is None:
        return fn(q, pool_k, pool_v, table, pos)
    if table.shape[1] * bs < int(window) + bs:
        raise ValueError("a ring of %d pages of %d keys cannot hold a "
                         "window of %d" % (table.shape[1], bs, window))
    return fn(q, pool_k, pool_v, table, pos,
              jnp.maximum(pos.astype(jnp.int32) - (int(window) - 1), 0))


@functools.lru_cache(maxsize=None)
def _decode_fn(scale, gp, chunk, heads, by_hand, interpret,
               windowed=False):
    """The launch for one resolved configuration, jitted: a model's
    layers all call the same one, so a process that traces the serving
    tick traces and lowers the kernel once, not once a layer."""
    from veles_tpu.ops.attention import QuantCache

    @jax.jit
    def decode(q, pool_k, pool_v, table, pos, *first):
        quant = isinstance(pool_k, QuantCache)
        kd = pool_k.data if quant else pool_k
        b, hq, hd = q.shape
        hkv, bs = kd.shape[1:3]
        nbm = table.shape[1]
        g = hq // hkv
        # [B, Hq, hd] -> [B, Hkv, Gp, hd]: group queries under their kv
        # head; pad the group dim up to the sublane tile (padded rows
        # carry zeros — their softmax is uniform over live keys, finite,
        # and the rows are sliced off below)
        qg = q.reshape(b, hkv, g, hd)
        if gp != g:
            qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))

        operands = ((pool_k.data, pool_k.scale, pool_v.data, pool_v.scale)
                    if quant else (pool_k, pool_v))
        scratch = [pltpu.VMEM((heads, gp, hd), jnp.float32),
                   pltpu.VMEM((heads, gp, _LANES), jnp.float32),
                   pltpu.VMEM((heads, gp, _LANES), jnp.float32)]
        names = KERNEL_NAMES_WINDOW if windowed else KERNEL_NAMES
        if by_hand:
            kernel = functools.partial(_decode_kernel, nbm=nbm,
                                       windowed=windowed)
            grid = (b, hkv // heads)
            pool_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
            scratch = [pltpu.VMEM((2, chunk, heads, bs, hd), kd.dtype),
                       pltpu.VMEM((2, chunk, heads, bs, hd), kd.dtype),
                       pltpu.SemaphoreType.DMA((2, 2))] + scratch
        else:
            kernel = functools.partial(_decode_kernel_specs, quant=quant,
                                       windowed=windowed)
            grid = (b, hkv // heads, -(-nbm // chunk))

            def at_page(i):
                def index(bi, hb, c, tbl, ps):
                    last = jnp.minimum(ps[bi] // bs, nbm - 1)
                    j = jnp.minimum(c, last // chunk) * chunk + i
                    return (jnp.where(j <= last,
                                      tbl[bi, jnp.minimum(j, nbm - 1)],
                                      0),
                            hb, 0, 0)

                def ring_index(bi, hb, c, tbl, ps, fs):
                    # pages relative to the row's first live one; the
                    # ring holds them all at distinct entries
                    page0 = fs[bi] // bs
                    last = ps[bi] // bs - page0
                    j = jnp.minimum(c, last // chunk) * chunk + i
                    return (jnp.where(j <= last,
                                      tbl[bi, (page0 + j) % nbm], 0),
                            hb, 0, 0)
                return ring_index if windowed else index

            pool_specs = [
                pl.BlockSpec((1, heads) + x.shape[2:], at_page(i))
                for x in operands for i in range(chunk)]
            operands = [x for x in operands for _ in range(chunk)]

        def at_q(bi, hb, *_):
            return (bi, hb, 0, 0)

        out = pl.pallas_call(
            functools.partial(kernel, scale=scale, bs=bs, chunk=chunk,
                              heads=heads),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2 + len(first),
                grid=grid,
                in_specs=[pl.BlockSpec((1, heads, gp, hd), at_q)]
                + pool_specs,
                out_specs=pl.BlockSpec((1, heads, gp, hd), at_q),
                scratch_shapes=scratch,
            ),
            out_shape=jax.ShapeDtypeStruct((b, hkv, gp, hd), q.dtype),
            interpret=interpret,
            name=names[quant][0],
        )(table.astype(jnp.int32), pos.astype(jnp.int32), *first, qg,
          *operands)
        return out[:, :, :g].reshape(b, hq, hd)

    return decode


def paged_attention_reference(q, pool_k, pool_v, table, pos,
                              scale=None, window=None):
    """Gather-formulation ground truth (identical math to the dense
    decode einsum in ops.attention.mha_step): materialize each row's
    blocks densely, run a masked softmax.  QuantCache pools
    dequantize the gathered view (data × per-position scale — exactly
    what the in-kernel fold computes).  ``window``: the table is a ring
    (entry e holds the latest page at or before ``pos``'s that is
    congruent to e mod ``nbm``) and a row attends its last ``window``
    keys.  Used by the tests and as the
    documentation of the kernel's exact semantics."""
    from veles_tpu.ops.attention import QuantCache
    b, hq, hd = q.shape
    kd = pool_k.data if isinstance(pool_k, QuantCache) else pool_k
    _, hkv, bs, _ = kd.shape
    nbm = table.shape[1]
    g = hq // hkv
    scale = (hd ** -0.5) if scale is None else scale

    def dense(pool):
        if isinstance(pool, QuantCache):
            v = (pool.data[table].astype(jnp.float32)
                 * pool.scale[table])         # [B, nbm, Hkv, bs, hd]
        else:
            v = pool[table]                   # [B, nbm, Hkv, bs, hd]
        v = jnp.moveaxis(v, 2, 1)             # [B, Hkv, nbm, bs, hd]
        return v.reshape(b, hkv, nbm * bs, hd)

    k = dense(pool_k)
    v = dense(pool_v)
    qg = q.reshape(b, hkv, g, hd)
    s = jnp.einsum("bkgd,bktd->bkgt", qg, k,
                   preferred_element_type=jnp.float32) * scale
    slot = jnp.arange(nbm * bs)[None, :]
    if window is None:
        live = slot <= pos[:, None]
    else:
        # the position a ring's slot holds: the latest at or before the
        # end of pos's page that is congruent to it mod the ring
        end = (pos[:, None] // bs + 1) * bs - 1
        kpos = end - (end - slot) % (nbm * bs)
        live = (kpos >= 0) & (kpos <= pos[:, None]) \
            & (pos[:, None] - kpos < window)
    s = jnp.where(live[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,bktd->bkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, hq, hd).astype(q.dtype)


# --------------------------------------------------------------------------
# VP6xx launch-audit hook (analysis.numerics_audit): the decode
# kernel's launch geometry as data — pure arithmetic, nothing traced.
# --------------------------------------------------------------------------

def audit_launch(hd, bs, g=1, dtype=jnp.bfloat16, nbm=32, masked=True,
                 checked=(), q_dtype=jnp.bfloat16, hkv=16):
    """Launch description for one paged-decode configuration.  ``bs``
    is the KV pool block (PagedContinuousBatcher ``block``), ``g`` the
    query-group size (Hq/Hkv) — padded to the sublane tile exactly as
    ``paged_attention_decode`` does — and ``hkv`` the pool's KV heads:
    a page is all of them.  ``dtype`` is the POOL dtype: int8
    describes the quantized pool (int8 K/V pages + f32 per-position
    scale pages, float q/out in ``q_dtype``).

    What is priced is what the launch holds in VMEM: the q/o blocks of
    ``heads`` KV heads, the accumulators, and the page buffers of
    :func:`page_schedule` — ``chunk`` pages of K and of V (and of
    their scales), two slots each: scratch the kernel fills by hand
    where it can slice a page out of the pool, else ``chunk`` page
    operands Mosaic double-buffers.  A tuned ``paged_block`` too fat
    for the budget (one page over ``_PAGE_BUFFER_BYTES``) shows here
    as buffers over the VMEM budget."""
    import numpy as np
    gp = max(g, _MIN_G)
    quant = np.dtype(dtype) == np.dtype(np.int8)
    chunk, heads = page_schedule(hkv, bs, hd, dtype, nbm, quant)
    io_dtype = q_dtype if quant else dtype
    tile = {"full_lane": True}
    blocks = [("q", (1, heads, gp, hd), io_dtype, tile),
              ("o", (1, heads, gp, hd), io_dtype, tile)]
    scratch = [("acc", (heads, gp, hd), jnp.float32),
               ("m", (heads, gp, _LANES), jnp.float32),
               ("l", (heads, gp, _LANES), jnp.float32)]
    pages = [(name, (chunk, heads, bs, hd), dtype, tile)
             for name in ("k", "v")]
    if quant:
        pages += [(name, (chunk, heads, bs, 1), jnp.float32, tile)
                  for name in ("k_scale", "v_scale")]
    if _sliceable(hd, quant):
        scratch = [(name + "_pages", (2,) + shape, dt, opts)
                   for name, shape, dt, opts in pages] + scratch
    else:
        blocks += pages
    return [{
        "kernel": KERNEL_NAMES[bool(quant)][1],
        "masked": masked, "checked": checked,
        "blocks": blocks,
        "scratch": scratch,
        # a row walks its live pages only, ``chunk`` a trip; the keys
        # of the last page past the row's position are masked
        "grid_axes": [("live-pages", nbm, chunk)],
    }]


@register_kernel_audit("paged")
def _configured_launches():
    """What ``--serve`` with paged KV would actually launch at the
    flagship head dim: the pool block through the same config > tuner >
    default chain the batcher uses (:func:`preferred_pool_block`), the
    q-group pad through :func:`_resolve_block_g` — so an over-budget
    tuned winner fails the lint exactly like a hand-misconfigured
    ``paged_block``.  BOTH pool flavors are audited: the bf16 pool and
    the int8 (``cache_dtype="int8"``) QuantCache pool, each resolved
    at its own dtype key."""
    hd, g = 128, 1      # 16 KV heads of 128: the 1.3B serving shape
    launches = []
    for dtype in (jnp.bfloat16, jnp.int8):
        bs = preferred_pool_block(hd, g, dtype)
        launches.extend(audit_launch(
            hd, bs, g=_resolve_block_g(g, hd, dtype), dtype=dtype))
    return launches
