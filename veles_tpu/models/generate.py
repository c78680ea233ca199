"""Autoregressive LM generation with a KV cache (incremental decoding).

Serve-time counterpart of the ``transformer_lm`` zoo stack (embedding →
[positional_encoding] → transformer_block* → layer_norm →
timestep_dense | tied_lm_head).
Each step feeds ONE token through the stack against per-block KV caches
([B, n_kv_heads, T_max, head_dim] — GQA stores only the kv heads, so its
smaller KV state is realized here), inside a single jitted ``lax.scan``
over positions: prefill and generation are the same loop, with the
prompt teacher-forcing the first ``prompt_len`` positions.

The reference served forward passes over REST (restful_api.py:112-217);
generation is the transformer-era equivalent and beyond-parity."""


import collections
import threading
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from veles_tpu.ops import attention, quant
from veles_tpu.telemetry.spans import SpanAggregate, span

#: compiled-executable cache capacity per generator.  Batch size (number
#: of prompt rows) and beam width are both client-controlled on the REST
#: serving path; each distinct value compiles an executable, so the cache
#: must be an LRU, not a grow-forever dict.
COMPILE_CACHE_SIZE = 12

#: the phases of one ``ContinuousBatcher.tick`` as a profiler capture
#: names them (``telemetry.span``: a TraceAnnotation on the profiler's
#: clock, so an idle gap of the device is charged to the phase the
#: host was in) — ``batcher.tick`` is the parent of the other five.
#: docs/services.md "Request tracing" says what each covers.
TICK_SPANS = ("batcher.tick", "batcher.admit", "batcher.dispatch",
              "batcher.wait", "batcher.fetch", "batcher.emit")

#: what one tick counts, each where the work happens (``last_tick``):
#: ``admitted``, ``prompt_tokens`` and the ``staged_*`` of what the call
#: ENQUEUED, the rest of the report it READ — the dispatch before its
#: own; ``drained`` (its first work went onto an empty device queue),
#: ``cold`` (nothing was on the device or in a slot when it began),
#: ``landed`` (dispatches it waited for: a report, passes) and
#: ``unseen`` (of them, those whose device time it could not see)
TICK_COUNTS = ("rows", "staging", "kv_tokens", "kv_pages", "admitted",
               "prompt_tokens", "staged_tokens", "staged_keys",
               "staged_kernel_tokens", "finished", "sel_keys",
               "experts_touched", "win_keys", "expert_pairs",
               "staged_expert_pairs", "fetch_bytes", "state_rows",
               "state_bytes", "staged_chunks", "state_launches",
               "admit_state_bytes", "admit_blocks", "drained", "cold",
               "landed", "unseen")

#: what one tick stamps beside its counts: ``device_s`` of the report
#: it read (None where an end was not seen, or nothing was read),
#: ``plain`` (that report's dispatching call admitted and staged
#: nothing), ``passes`` (``(tokens, device_s or None)`` of each staged
#: pass it landed), and where the call drained: the phase the queue was
#: first seen empty in (``fetch``, ``emit``, ``engine``, ``admit``) and
#: the seconds from that sight to the enqueue
TICK_STAMPS = ("device_s", "plain", "passes", "drained_in", "drained_s")


def _ready(array):
    """Whether the device has finished ``array`` — a PJRT query that
    never blocks."""
    return array.is_ready()


class _Stamp:
    """A dispatch the host will wait for — a tick's report, a staged
    pass's row — with what times its landing: the one before it in the
    device's queue, the first enqueue since that one (work nobody waits
    for, an admission's programs, is folded in) and whether that enqueue
    found the queue empty.  ``landed``: the end of an EXACT landing (the
    host was blocked when the device finished), else None; ``array`` is
    dropped once landed."""

    __slots__ = ("array", "prev", "enqueued", "onto_empty", "landed")

    def __init__(self, array, prev, enqueued, onto_empty):
        self.array, self.prev = array, prev
        self.enqueued, self.onto_empty = enqueued, onto_empty
        self.landed = None


#: shortest prompt length (tokens) at which the chunked-prefill decode
#: path kicks in — below this the one-executable full scan wins on
#: compile count and is cheap anyway
PREFILL_MIN = 32


def _truncate(logits, top_k, top_p):
    """top-k/top-p truncation with TRACED per-row parameters (lax.top_k
    would need a static k) over a sorted-descending view."""
    sl = jnp.sort(logits, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(
        sl, jnp.clip(top_k - 1, 0, sl.shape[-1] - 1)[:, None], axis=-1)
    k_thresh = jnp.where(top_k[:, None] > 0, kth, -jnp.inf)
    # nucleus: keep the smallest prefix of the distribution whose mass
    # reaches top_p
    ps = jax.nn.softmax(sl, axis=-1)
    keep = (jnp.cumsum(ps, axis=-1) - ps) < top_p[:, None]
    p_thresh = jnp.min(jnp.where(keep, sl, jnp.inf), axis=-1,
                       keepdims=True)
    # per-row escapes: a top_p=1.0 row must behave EXACTLY as if it
    # skipped truncation (f32 cumsum can reach 1.0 early and mask real
    # tail tokens), or coalescing would not be bit-identical to the
    # solo run — mirrors the top_k==0 guard
    p_thresh = jnp.where(top_p[:, None] < 1.0, p_thresh, -jnp.inf)
    return jnp.where((logits >= k_thresh) & (logits >= p_thresh),
                     logits, -1e30)


def _sample(logits, pos, keys, top_k, top_p, inv_temp):
    """Per-row categorical draw keyed on (row seed, position) ONLY — a
    row's randomness never depends on what it was batched with."""
    lg = logits * inv_temp[:, None]
    # plain temperature sampling skips the O(V log V) sort when NO row
    # asks for truncation
    lg = jax.lax.cond(
        jnp.any(top_k > 0) | jnp.any(top_p < 1.0),
        lambda l: _truncate(l, top_k, top_p),
        lambda l: l, lg)
    subs = jax.vmap(jax.random.fold_in)(
        keys, jnp.broadcast_to(pos, (lg.shape[0],)))
    return jax.vmap(jax.random.categorical)(subs, lg).astype(jnp.int32)


def _ngram_draft(row, cursor, kk, ll):
    """Draft ``kk`` candidate tokens for positions cursor+1..cursor+kk:
    copy the continuation of the most recent EARLIER occurrence of the
    last known bigram (row[cursor-1], row[cursor]); fallback = repeat
    from ``cursor``.  Shared by the solo speculative decode
    (LMGenerator._spec_fn, whose loop cursor ``cur`` equals cursor+1)
    and the batcher's speculative tick core — draft quality only
    affects how many positions verify, never which tokens come out,
    but the rule must not silently drift between the two."""
    j = jnp.arange(ll - 1)
    last2 = jax.lax.dynamic_slice(row, (jnp.maximum(cursor - 1, 0),),
                                  (2,))
    match = ((row[:-1] == last2[0]) & (row[1:] == last2[1])
             & (j + 1 < cursor))
    cand = jnp.max(jnp.where(match, j, -1))
    src = jnp.clip(jnp.where(cand >= 0, cand + 2, cursor), 0, ll - kk)
    return jax.lax.dynamic_slice(row, (src,), (kk,))


class LMGenerator:
    """Build from a trained ``transformer_lm`` workflow/trainer:

        gen = LMGenerator(wf.trainer, max_len=128)
        out = gen.generate(prompt_tokens, max_new=32)        # greedy
        out = gen.generate(prompt, max_new=32, temperature=0.8, seed=1)
    """

    def __init__(self, trainer, max_len, cache_dtype=None,
                 mesh_cfg="auto", weights=None, use_ema=False):
        #: ``use_ema=True`` decodes with the trainer's Polyak/EMA weight
        #: average (gd_defaults["ema_decay"]) instead of the live params.
        #: The duck-typed fallback only applies when EMA was NOT asked
        #: for — a use_ema request on a trainer without the API must
        #: fail loudly, never silently serve un-averaged weights.
        self.params = (trainer.serve_params(use_ema)
                       if use_ema or hasattr(trainer, "serve_params")
                       else trainer.params)
        #: ``weights="int8"`` quantizes the serving copy of the params
        #: (ops.quant W8A8-dynamic): attention/FFN/head matrices become
        #: int8 + per-channel scales, the embedding table int8 + per-row
        #: scales — halving decode-time weight HBM traffic vs bf16.
        #: Training params are untouched.
        self.weight_dtype = weights
        self.max_len = int(max_len)
        #: KV-cache storage dtype; default follows the params.  bfloat16
        #: halves serve-time cache memory (keys/values are MXU inputs
        #: anyway; softmax stays f32); "int8" quarters it vs f32 via
        #: per-position symmetric quantization (ops.attention.QuantCache)
        self.cache_dtype = cache_dtype
        self._compiled = collections.OrderedDict()
        self._cache_lock = threading.Lock()
        #: tensor-parallel decode: when the trainer ran under a mesh
        #: (``mesh_cfg="auto"``) or one is passed explicitly, the decode
        #: scan runs against the training shardings — column-parallel
        #: projections, KV caches sharded over the kv-head dim on the
        #: model axis, GSPMD inserting the collectives.  A model trained
        #: with TP/FSDP serves at the size it was trained.  (The
        #: reference only ever served single-process forward passes,
        #: restful_api.py:112-217.)
        if mesh_cfg == "auto":
            mesh_cfg = getattr(trainer, "mesh_config", None)
        self.mesh_cfg = mesh_cfg
        #: per-instance prefill threshold (module default PREFILL_MIN);
        #: tests pin it to force one path or the other
        self.prefill_min = PREFILL_MIN
        layers = trainer.layers
        by_type = {}
        self._blocks = []
        for layer in layers:
            if layer.type == "transformer_block":
                self._blocks.append(layer)
            else:
                by_type.setdefault(layer.type, layer)
        for need in ("embedding", "layer_norm"):
            if need not in by_type:
                raise ValueError(
                    "LMGenerator needs a transformer_lm-shaped stack "
                    "(missing %r; got %s)" % (need,
                                              [l.type for l in layers]))
        if not self._blocks:
            raise ValueError("no transformer_block layers to decode with")
        self._embed = by_type["embedding"]
        self._posenc = by_type.get("positional_encoding")
        self._ln = by_type["layer_norm"]
        self._head = by_type.get("timestep_dense",
                                 by_type.get("tied_lm_head"))
        if self._head is None:
            raise ValueError("LMGenerator needs a timestep_dense or "
                             "tied_lm_head LM head")
        if self._posenc is not None and self.max_len > \
                self._posenc.input_shape[0]:
            raise ValueError(
                "max_len %d exceeds the position table length %d"
                % (self.max_len, self._posenc.input_shape[0]))
        #: sliding-window blocks with window < max_len get a ROLLING
        #: ring-buffer cache of exactly ``window`` slots — serve-time
        #: KV memory is O(window) regardless of context length
        #: a block may keep FIXED-SIZE state a slot instead
        #: (``TransformerBlock.state_leaves``: a retention layer's S and
        #: z, a delta rule's S and conv tail): it cannot be overwritten
        #: as a cache row can, so a prefill
        #: takes a count of the tokens that may enter it (``_valid``),
        #: and whatever rolls back a cache (speculation, segmenting a
        #: dense batcher's prompt) is refused as for a rolling one
        self._stateful = any(layer.state_leaves() for layer in self._blocks)
        self._rolling = self._stateful or any(
            (layer.cfg.get("window") or self.max_len) < self.max_len
            for layer in self._blocks)
        if self.mesh_cfg is not None and self.mesh_cfg.model_size > 1:
            m = self.mesh_cfg.model_size
            for layer in self._blocks:
                # the KV cache shards its head dim over the model axis,
                # so every block's kv heads must divide the axis size
                if layer.n_kv_heads % m:
                    raise ValueError(
                        "tensor-parallel decode needs n_kv_heads (%d) "
                        "divisible by the model axis size (%d)"
                        % (layer.n_kv_heads, m))
        if self.weight_dtype is not None:
            if self.weight_dtype not in ("bf16", "int8", "w4a8"):
                raise ValueError("weights must be None, 'bf16', 'int8' "
                                 "or 'w4a8', got %r"
                                 % (self.weight_dtype,))
            # weight compression must never shift cache/compute
            # precision — that stays an explicit cache_dtype opt-in
            self._float_dtype = \
                self.params[self._embed.name]["table"].dtype
            if self.weight_dtype == "bf16":
                # training params are often f32; the float decode path
                # already streams a hoisted bf16 cast per step, so this
                # mainly halves RESIDENT param memory (no duplicate
                # f32 input + hoisted bf16 copy) — int8 is what cuts
                # the per-step traffic.  A model BUILT in bfloat16 (the
                # precision policy's ``param``) is served as it is: the
                # trainer's one copy, its float32 norm gains included
                if self._float_dtype != jnp.bfloat16:
                    self.params = jax.tree_util.tree_map(
                        lambda a: (a.astype(jnp.bfloat16)
                                   if hasattr(a, "dtype")
                                   and jnp.issubdtype(a.dtype, jnp.floating)
                                   else a), self.params)
            else:                       # int8 / w4a8
                if any(layer.cfg.get("n_experts")
                       for layer in self._blocks):
                    raise ValueError(
                        "%s serving weights do not cover MoE experts "
                        "yet (the GShard leaves moe.w1/w2 and the "
                        "dropless leaves moe.w_gate/w_up/w_down are "
                        "read whole by their matmuls): serve an "
                        "expert model in float32 or bfloat16"
                        % self.weight_dtype)
                if self.weight_dtype == "w4a8" and \
                        self.mesh_cfg is not None and \
                        self.mesh_cfg.model_size > 1:
                    # the nibble-packed payload halves the contraction
                    # axis, so the training partition specs no longer
                    # describe it — int8 carries the shardings, w4a8
                    # stays single-device for now
                    raise ValueError(
                        "w4a8 serving weights are single-device for "
                        "now — serve int8 under a model-axis mesh, or "
                        "drop the mesh")
                orig = self.params
                self.params = quant.quantize_lm_params(
                    self.params, embed_name=self._embed.name,
                    scheme=self.weight_dtype)
                if self.mesh_cfg is not None and \
                        self.mesh_cfg.model_size > 1:
                    # tensor-parallel int8: re-place every quantized
                    # leaf explicitly — the int8 payload sharded like
                    # the float weight it replaces (the eager
                    # quantization already computed under that
                    # sharding), the per-channel scales replicated so
                    # the rescale never inserts a collective
                    self.params = self._shard_quant_params(orig,
                                                           self.params)

    # ------------------------------------------------------------------
    def _shard_quant_params(self, orig, qparams):
        """Re-place quantized leaves under the tensor-parallel mesh:
        the payload gets the ORIGINAL weight's sharding (so the int8
        bytes stream exactly where the bf16 bytes did), the scales are
        replicated.  Walks the quantized tree against the pre-quant
        tree — a QuantWeight node's partner is the array it replaced."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        repl = NamedSharding(self.mesh_cfg.mesh, P())

        def place(qv, ov):
            # int8 only: the w4a8 constructor path refuses a model-axis
            # mesh outright (the packed contraction axis invalidates
            # the training specs), so QuantWeight4 never reaches here
            if not isinstance(qv, quant.QuantWeight):
                return qv
            sh = getattr(ov, "sharding", None)
            payload = (jax.device_put(qv.q, sh) if sh is not None
                       else qv.q)
            return quant.QuantWeight(payload,
                                     jax.device_put(qv.scale, repl))

        return jax.tree_util.tree_map(place, qparams, orig,
                                      is_leaf=quant.is_quant)

    def _embed_rows(self, params, idx):
        """Embedding lookup — quantized serving tables gather payload
        rows and dequantize only those (ops.quant.take_rows)."""
        table = params[self._embed.name]["table"]
        if quant.is_quant(table):
            return quant.take_rows(table, idx.astype(jnp.int32))
        return jnp.take(table, idx.astype(jnp.int32), axis=0)

    def _model_dtype(self):
        """Cache/init dtype: the embedding table's pre-compression
        dtype — weights="bf16"/"int8" must not silently shift cache
        precision (the user opts into cache compression via
        cache_dtype)."""
        if self.weight_dtype is not None:
            return self._float_dtype
        return self.params[self._embed.name]["table"].dtype

    def _pos_table(self, params):
        """The position table (learned weights or the sinusoid buffer);
        None when the stack has no positional-encoding layer (rope)."""
        if self._posenc is None:
            return None
        if self._posenc.learned:
            return params[self._posenc.name]["pos"]
        return self._posenc._sinusoid()

    def _pos_row(self, params, pos):
        table = self._pos_table(params)
        if table is None:
            return 0.0
        return jax.lax.dynamic_index_in_dim(table, pos, keepdims=False)

    def _step(self, params, caches, tok, pos):
        """tok [B] int32 at position ``pos`` → (logits [B, V], caches)."""
        x = self._embed_rows(params, tok)[:, None, :]
        x = x + self._pos_row(params, pos)
        new_caches = []
        for layer, cache in zip(self._blocks, caches):
            x, cache = layer.step(params[layer.name], x, cache, pos)
            new_caches.append(cache)
        logits = self._ln_head(params, x)
        return logits[:, 0].astype(jnp.float32), new_caches

    def load_adapter_bank(self, adapters):
        """Multi-LoRA serving (S-LoRA idea, Sheng et al. 2023): stack N
        fine-tuned adapters into per-layer banks so ONE slot pool
        serves base + any adapter, routed per request.

        ``adapters`` — list of host param trees from LoRA fine-tunes of
        THIS base model (each carries ``<layer>.mha.lora`` subtrees).
        Bank slot 0 is the identity adapter (zeros — the b-factors
        zero out the delta), so adapter id 0 == the base model and ids
        1..N follow ``adapters``' order.  Returns N.

        Banks live in ``params[layer]["mha"]["lora_bank"]``; the
        serving tick gathers a request's adapter into the live
        ``"lora"`` subtree (``_graft_adapters``) — the gathered leaves
        keep a leading row dim under the batched paged step, which
        ``_qkv_proj``'s jnp.matmul chain broadcasts natively.  Banks
        are a serving-path artifact: training, solo generate() and
        beam ignore them."""
        if not adapters:
            raise ValueError("adapters must be a non-empty list")
        # validate + build EVERY layer's bank before touching
        # self.params: a mid-list error (missing subtree, rank
        # mismatch breaking the stack) must leave the generator
        # exactly as it was, never half-banked
        banks = {}
        for layer in self._blocks:
            lp = self.params.get(layer.name, {})
            if "lora" in lp.get("mha", {}):
                raise ValueError(
                    "params already carry a single 'lora' subtree on "
                    "%s — serve it as a bank member instead"
                    % layer.name)
            subs = []
            for i, tree in enumerate(adapters):
                sub = tree.get(layer.name, {}).get("mha", {}).get(
                    "lora")
                if sub is None:
                    raise ValueError(
                        "adapter %d has no lora subtree on layer %s"
                        % (i, layer.name))
                subs.append(sub)
            try:
                banks[layer.name] = jax.tree_util.tree_map(
                    lambda *leaves: jnp.stack(
                        (jnp.zeros_like(jnp.asarray(leaves[0])),)
                        + tuple(jnp.asarray(l) for l in leaves)),
                    *subs)
            except (ValueError, TypeError) as e:
                raise ValueError(
                    "adapters disagree on layer %s (rank/shape "
                    "mismatch?): %s" % (layer.name, e)) from e
        if not banks:
            raise ValueError("model has no transformer blocks to bank")
        # rebind a shallow copy: self.params may BE the trainer's live
        # params dict (shared with training and other generators) —
        # banks belong to THIS generator only
        self.params = dict(self.params)
        for name, bank in banks.items():
            lp = self.params[name]
            mha = dict(lp["mha"])
            mha["lora_bank"] = bank
            self.params[name] = dict(lp, mha=mha)
        self._n_adapters = len(adapters)
        return self._n_adapters

    def _graft_adapters(self, params, aid):
        """``params`` with each banked layer's adapters gathered at
        ``aid`` (scalar for one row, [B] vector for the batched paged
        step) into the live ``"lora"`` subtree ``_qkv_proj`` reads.
        Identity (returns ``params`` itself) when no banks exist, so
        bank-free models trace the exact same program as before."""
        out = None
        for layer in self._blocks:
            lp = params.get(layer.name, {})
            bank = lp.get("mha", {}).get("lora_bank")
            if bank is None:
                continue
            if out is None:
                out = dict(params)
            mha = {k: v for k, v in lp["mha"].items()
                   if k != "lora_bank"}
            mha["lora"] = jax.tree_util.tree_map(
                lambda b_: b_[aid], bank)
            out[layer.name] = dict(lp, mha=mha)
        return params if out is None else out

    def _ring_spans(self):
        """The sliding windows shorter than ``max_len`` among the
        blocks' ``cache_span``s, ascending: the groups of per-token
        state the paged pool keeps as RINGS beside the whole-context
        group (a window that reaches ``max_len`` never bites, and its
        layer is one of that group)."""
        return tuple(sorted({layer.cache_span() for layer in self._blocks
                             if (layer.cache_span() or self.max_len)
                             < self.max_len}))

    def _ring_of(self, layer):
        """Which of ``_ring_spans`` ``layer`` belongs to; None: the
        whole-context group."""
        spans = self._ring_spans()
        return spans.index(layer.cache_span()) \
            if layer.cache_span() in spans else None

    def _valid(self, n):
        """What a prefill program of a model with state layers takes
        beside its tokens: how many of them enter the state (the
        position decoding resumes at — padding and the token the decode
        step takes again must not).  Nothing for any other model: its
        programs keep their arguments."""
        return (jnp.int32(n),) if self._stateful else ()

    def _step_paged(self, params, pool, tables, tok, pos, counts=None,
                    rings=(), active=None):
        """One decode step against the PAGED KV pool, batched over rows
        at PER-ROW positions: tok [B] int32, pos [B] int32 →
        (logits [B, V], pool).  The paged continuous batcher's fused
        path — unlike _step (scalar pos, dense caches, vmappable per
        row), the pool is SHARED across rows, so the whole step runs
        batched and each layer scatters/reads through the block table
        (layers.TransformerBlock.step_paged).  ``counts``: a dict that
        takes what the blocks counted on the device, each the mean over
        the blocks that count it (``attended`` [B], ``experts_touched``)
        — an argument, because callers outside the package wrap this
        method as a pair.  ``rings``: the ring tables of the window
        groups (``_ring_spans``' order); ``tables`` is the
        whole-context group's.  ``active`` [B]: the rows the tick
        advances (a state layer leaves the others' state alone)."""
        x = self._embed_rows(params, tok)[:, None, :]
        ptab = self._pos_table(params)
        if ptab is not None:
            x = x + jnp.take(ptab, pos.astype(jnp.int32),
                             axis=0)[:, None, :]
        new_pool, counted = [], {}
        for layer, leaves in zip(self._blocks, pool):
            ring = self._ring_of(layer) if rings else None
            x, leaves, seen = layer.step_paged(
                params[layer.name], x, leaves,
                tables if ring is None else rings[ring], pos,
                ring=ring is not None, active=active)
            new_pool.append(leaves)
            for name, value in seen.items():
                counted.setdefault(name, []).append(value)
        if counts is not None:
            counts.update({name: jnp.mean(jnp.stack(values).astype(
                jnp.float32), axis=0) for name, values in counted.items()})
        logits = self._ln_head(params, x)
        return logits[:, 0].astype(jnp.float32), new_pool

    def _ln_head(self, params, x):
        """Final LN + LM head (shared by every decode path — the
        needs_full_params head protocol lives in exactly one place)."""
        x = self._ln.apply(params[self._ln.name], x)
        head_p = (params if getattr(self._head, "needs_full_params",
                                    False) else params[self._head.name])
        return self._head.apply(head_p, x)

    def _cache_constraint(self, c):
        """Pin a KV cache's head dim to the model axis under a mesh —
        the annotation GSPMD propagates through the whole decode scan.
        Applied leaf-wise (a QuantCache carries data + scales, both
        [B, Hkv, T, ...])."""
        if self.mesh_cfg is None or self.mesh_cfg.model_size <= 1:
            return c
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(self.mesh_cfg.mesh,
                           P(None, self.mesh_cfg.model_axis))
        return jax.tree_util.tree_map(
            lambda a: jax.lax.with_sharding_constraint(a, sh), c)

    def _init_caches(self, batch, dtype, rings=None):
        """Dense cache rows, a tuple of leaves a block.  A window
        layer's row is a rolling cache of exactly ``window`` positions;
        with ``rings`` (positions a ring of each of ``_ring_spans``
        holds: the paged batcher's staging rows) it is that long."""
        dtype = self.cache_dtype or dtype

        def one(layer, heads, width):
            t_cache = min(self.max_len,
                          layer.cfg.get("window") or self.max_len)
            if rings and self._ring_of(layer) is not None:
                t_cache = rings[self._ring_of(layer)]
            shape = (batch, heads, t_cache, width)
            if jnp.dtype(dtype) == jnp.int8:
                # int8 KV cache: quarter the serve-time cache memory
                # (ops.attention.QuantCache; scales for unwritten
                # positions are never read — decode writes before use)
                return attention.QuantCache(
                    jnp.zeros(shape, jnp.int8),
                    jnp.ones(shape[:3] + (1,), jnp.float32))
            return jnp.zeros(shape, dtype)

        # a block declares its per-token leaves (k and v; the sparse-
        # attention indexer's key beside them); nothing here or in the
        # batchers counts them
        def block_cache(layer):
            state = layer.state_leaves()
            if state:
                # fixed-size a slot, float32 whatever the cache dtype,
                # in the container of the block's own state module
                return self._cache_constraint(layer.state_ops().State(**{
                    name: jnp.zeros((batch,) + shape, jnp.float32)
                    for name, shape in state.items()}))
            leaves = layer.cache_leaves()
            kind = (attention.KVIdxCache if "idx" in leaves
                    else attention.KVCache)
            return kind(**{name: self._cache_constraint(
                one(layer, *leaves[name])) for name in leaves})

        return [block_cache(layer) for layer in self._blocks]

    def _scan_fn(self, batch):
        """ONE compile per batch size: the scan always runs to
        max_len - 1, and prompt_len / seed / top_k / top_p / inv_temp /
        greedy are all TRACED per-row [B] vectors (a REST server sees
        arbitrary prompt lengths and client-chosen sampling configs —
        shape- or value-specializing on any of them would recompile per
        request and cache executables forever; per-ROW parameters are
        what lets the serving batcher coalesce heterogeneous requests
        into one device call).  Each row's draws depend only on its own
        (seed, position), so a request's output is invariant to which
        batch it was coalesced into.  Cached per-instance (NOT
        lru_cache: a class-level cache keyed on self would immortalize
        every generator and its params)."""
        cached = self._cache_get(batch)
        if cached is not None:
            return cached

        def run(params, tokens, prompt_len, seeds, top_k, top_p,
                inv_temp, greedy):
            caches = self._init_caches(batch, self._model_dtype())
            keys = jax.vmap(jax.random.key)(seeds)
            body = self._decode_body(params, prompt_len, keys, top_k,
                                     top_p, inv_temp, greedy, batch)
            (tokens, _), logits = jax.lax.scan(
                body, (tokens, caches),
                jnp.arange(self.max_len - 1))
            return tokens, logits

        return self._cache_put(batch, jax.jit(run))

    def _decode_body(self, params, prompt_len, keys, top_k, top_p,
                     inv_temp, greedy, batch):
        """The per-position decode body shared by the full scan and the
        prefilled generation scan (they must never diverge)."""
        def body(carry, pos):
            tokens, caches = carry
            logits, caches = self._step(params, caches,
                                        tokens[:, pos], pos)
            # an all-greedy batch (the serving default) skips the
            # whole-vocab gumbel draw — jnp.where alone would pay it
            smp = jax.lax.cond(
                jnp.any(~greedy),
                lambda: _sample(logits, pos, keys, top_k, top_p,
                                inv_temp),
                lambda: jnp.zeros((batch,), jnp.int32))
            nxt = jnp.where(
                greedy,
                jnp.argmax(logits, axis=-1).astype(jnp.int32), smp)
            keep = pos + 1 < prompt_len       # teacher-force prompt
            nxt = jnp.where(keep, tokens[:, pos + 1], nxt)
            tokens = jax.lax.dynamic_update_slice(
                tokens, nxt[:, None], (0, pos + 1))
            return (tokens, caches), logits

        return body

    def _pos_rows(self, params, tp):
        table = self._pos_table(params)
        return 0.0 if table is None else table[:tp]

    def _prefill_fn(self, batch, tp, rings=None):
        """ONE compile per (batch, prompt bucket): run the prompt chunk
        [B, tp] through every block's parallel prefill, returning the
        filled KV caches (``rings``: ``_init_caches``'; ``tp`` then
        fits the shortest).  Replaces tp sequential scan steps with one
        MXU-fed forward — the serving prefill."""
        key = ("pre", batch, tp) + ((rings,) if rings else ())
        cached = self._cache_get(key)
        if cached is not None:
            return cached

        # the name is the host plane's: PjitFunction(serve_prefill)
        def serve_prefill(params, toks, *valid):
            x = self._embed_rows(params, toks)
            x = x + self._pos_rows(params, tp)
            caches = self._init_caches(batch, self._model_dtype(), rings)
            out = []
            for layer, cache in zip(self._blocks, caches):
                x, cache = layer.prefill(params[layer.name], x, cache,
                                         *valid)
                out.append(cache)
            return out

        return self._cache_put(key, jax.jit(serve_prefill))

    def _gen_fn(self, batch, length):
        """ONE compile per (batch, generation-length bucket): the decode
        scan over ``length`` positions starting at traced ``start``
        (prefilled caches in, final tokens out).  Positions past
        max_len - 2 clamp — the body is idempotent at a repeated
        position (same inputs -> same token), so overshoot from the
        power-of-two bucket is harmless."""
        cached = self._cache_get(("gen", batch, length))
        if cached is not None:
            return cached

        def run(params, caches, tokens, start, prompt_len, seeds,
                top_k, top_p, inv_temp, greedy):
            keys = jax.vmap(jax.random.key)(seeds)
            body = self._decode_body(params, prompt_len, keys, top_k,
                                     top_p, inv_temp, greedy, batch)

            def body2(carry, i):
                pos = jnp.minimum(start + i, self.max_len - 2)
                return body(carry, pos)

            (tokens, _), _ = jax.lax.scan(body2, (tokens, caches),
                                          jnp.arange(length))
            return tokens

        return self._cache_put(("gen", batch, length), jax.jit(run))

    @staticmethod
    def _bucket(n, cap):
        return min(1 << max(0, n - 1).bit_length(), cap)

    def _prefill_dispatch(self, min_len, max_total):
        """(prompt bucket, scan start, scan length) for the chunked-
        prefill paths (greedy/sampled AND beam — one copy of the
        invariant): validate_request caps max_total <= max_len, so the
        pow2 length bucket, clamped to the remaining positions, always
        covers the needed steps — and overshoot positions are frozen/
        idempotent.

        ROLLING caches round the prompt chunk DOWN (largest pow2 <=
        min_len): a ring slot must always hold the latest position <=
        the scan cursor, so the prefill may never write a position past
        its own start — padding rows would poison the slot->position
        mapping.  Linear caches round UP (padding is overwritten before
        it can be read)."""
        if self._rolling:
            tp = max(1, min(1 << (min_len.bit_length() - 1),
                            self.max_len))
            start = tp - 1
        else:
            tp = self._bucket(min_len, self.max_len)
            start = min_len - 1
        need = max(1, max_total - 1 - start)
        length = self._bucket(need, max(1, self.max_len - 1 - start))
        return tp, start, length

    def _decode_rows(self, tokens_np, lens, totals, greedy, seeds,
                     top_k, top_p, inv_temp):
        """Shared decode orchestrator (generate / generate_batch): pick
        chunked-prefill + short generation scan when the shortest
        prompt is long enough, else the single full scan.  Correctness
        of padded prefill: the decode body overwrites cache row ``pos``
        BEFORE attending to it, so prefill garbage beyond a row's
        prompt (padding, or rows whose prompt is longer than the
        common prefix) is rewritten before it can ever be read."""
        b = tokens_np.shape[0]
        pad = self.max_len - tokens_np.shape[1]
        if pad:
            tokens_np = np.concatenate(
                [tokens_np, np.zeros((b, pad), np.int32)], axis=1)

        def row(x, dtype):
            return jnp.broadcast_to(jnp.asarray(x, dtype), (b,))

        min_len, max_total = int(min(lens)), int(max(totals))
        if min_len < self.prefill_min:
            out, _ = self._run(self.params, tokens_np, lens, greedy,
                               seeds, top_k, top_p, inv_temp)
            return np.asarray(out)
        tp, start, length = self._prefill_dispatch(min_len, max_total)
        caches = self._prefill_fn(b, tp)(
            self.params, jnp.asarray(tokens_np[:, :tp]),
            *self._valid(start))
        out = self._gen_fn(b, length)(
            self.params, caches, jnp.asarray(tokens_np),
            jnp.int32(start), row(lens, jnp.int32),
            row(seeds, jnp.int32), row(top_k, jnp.int32),
            row(top_p, jnp.float32), row(inv_temp, jnp.float32),
            row(greedy, jnp.bool_))
        return np.asarray(out)

    def _chunk_forward(self, params, caches, toks, start, counts=None,
                       valid=()):
        """toks [1, K] at positions [start, start+K) through every
        block's chunk_step against an existing cache → (x, caches).
        THE one chunk-positioning contract — the speculative verify
        (_chunk_logits) and the prefix-cache prefill resume
        (_prefill_resume_fn) must never diverge on it.  ``counts``: a
        dict that takes what the blocks counted (``_step_paged``'s
        way: the mean over the blocks that count a name).  ``valid``:
        ``_valid``'s."""
        x = self._embed_rows(params, toks)
        ptab = self._pos_table(params)
        if ptab is not None:
            x = x + jax.lax.dynamic_slice(
                ptab, (start, 0), (toks.shape[1], ptab.shape[1]))
        new_caches, counted = [], {}
        for layer, cache in zip(self._blocks, caches):
            seen = {} if counts is not None else None
            x, cache = layer.chunk_step(params[layer.name], x, cache,
                                        start, seen, *valid)
            new_caches.append(cache)
            for name, value in (seen or {}).items():
                counted.setdefault(name, []).append(value)
        if counts is not None:
            counts.update({name: jnp.mean(jnp.stack(values).astype(
                jnp.float32), axis=0) for name, values in counted.items()})
        return x, new_caches

    def _chunk_logits(self, params, caches, toks, start):
        """toks [1, K] at positions [start, start+K) → (logits [K, V]
        f32, caches) — the speculative verify forward."""
        x, new_caches = self._chunk_forward(params, caches, toks,
                                            start)
        return (self._ln_head(params, x)[0].astype(jnp.float32),
                new_caches)

    def _prefill_resume_fn(self, kb, counting=False):
        """ONE compile per resume-chunk bucket: positions
        [start, start+kb) of a prompt run through every block's
        chunk_step against an EXISTING cache (valid for [0, start)) —
        chunked prefill that RESUMES from a prefix another request
        already computed (the paged batcher's prefix-cache compute
        skip).  Identical K/V math to a full prefill of the same
        positions (chunk_step == K step() calls, the same contract the
        speculative verify rides).  ``counting``: the program returns
        ``(caches, counts)``, what the blocks counted on the device
        (``_chunk_forward``)."""
        key = ("presume", kb) + (("counting",) if counting else ())
        cached = self._cache_get(key)
        if cached is not None:
            return cached

        def serve_prefill_resume(params, caches, toks, start, *valid):
            if not counting:
                return self._chunk_forward(params, caches, toks, start,
                                           valid=valid)[1]
            counts = {}
            return self._chunk_forward(params, caches, toks, start,
                                       counts, valid)[1], counts

        # the cache row is donated: a row of a long-context model is
        # hundreds of MB, and every caller rebinds it to the result
        return self._cache_put(key, jax.jit(serve_prefill_resume,
                                            donate_argnums=(1,)))

    def _spec_fn(self, draft_k):
        """ONE compile per draft width: the whole speculative greedy
        decode — n-gram draft, K-wide verify chunk, acceptance — inside
        a single jitted lax.while_loop (no host round trips).  Each
        round advances >= 1 position; drafts that copy a continuation
        of the last bigram from earlier context verify several
        positions per model pass."""
        cached = self._cache_get(("spec", draft_k))
        if cached is not None:
            return cached
        kk = draft_k
        ll = self.max_len

        def run(params, caches, tokens, cur0, prompt_len, total):
            # tokens [1, max_len]; cache valid for [0, cur0)
            idx = jnp.arange(kk)

            def cond(state):
                return state[2] < total

            def body(state):
                tokens, caches, cur = state
                row = tokens[0]
                draft = _ngram_draft(row, cur - 1, kk, ll)
                # prompt positions teacher-force their own tokens
                in_prompt = (cur + idx) < prompt_len
                cur_slice = jax.lax.dynamic_slice(row, (cur,), (kk,))
                draft = jnp.where(in_prompt, cur_slice, draft)
                # verify: inputs are [token at cur-1, draft[:-1]]
                prev = jax.lax.dynamic_slice(row, (cur - 1,), (1,))
                chunk = jnp.concatenate([prev, draft[:-1]])[None]
                logits, caches = self._chunk_logits(
                    params, caches, chunk, cur - 1)
                g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                ok = (draft == g) | in_prompt
                # first rejection = number accepted; cap at kk-1 so the
                # "bonus" write is always a position we HAVE logits for
                # (if draft[kk-1] was accepted, g[kk-1] equals it)
                a = jnp.minimum(
                    jnp.argmin(jnp.concatenate(
                        [ok, jnp.zeros((1,), bool)])), kk - 1)
                # the bonus position must NEVER overwrite a
                # teacher-forced prompt token (a lands inside the
                # prompt tail when the whole chunk was in-prompt)
                bonus = jnp.where(jnp.take(in_prompt, a),
                                  jnp.take(cur_slice, a),
                                  jnp.take(g, a))
                newvec = jnp.where(
                    idx < a, draft,
                    jnp.where(idx == a, bonus, cur_slice))
                tokens = jax.lax.dynamic_update_slice(
                    tokens, newvec[None], (0, cur))
                return (tokens, caches, cur + a + 1)

            tokens, _, _ = jax.lax.while_loop(
                cond, body, (tokens, caches, cur0))
            return tokens

        return self._cache_put(("spec", draft_k), jax.jit(run))

    def generate_speculative(self, prompt, max_new, draft_k=8):
        """Greedy decode with in-jit n-gram speculation: repetitive or
        self-similar continuations verify up to ``draft_k`` positions
        per model pass instead of one.  Exact greedy semantics — the
        accepted tokens ARE the verify pass's own argmax.  Falls back
        to generate() when speculation can't apply (batch > 1, short
        prompts, rolling-window caches, no headroom for the draft
        overshoot)."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim == 1:
            prompt = prompt[None]
        b, t0 = prompt.shape
        draft_k = int(draft_k)
        if not 2 <= draft_k <= 64:
            raise ValueError("draft_k must be in [2, 64], got %r"
                             % (draft_k,))
        t0v, total, _, _, _, _ = self.validate_request(
            t0, {"max_new": max_new, "temperature": 0.0})
        if (b != 1 or self._rolling or t0 < max(4, self.prefill_min)
                or total + draft_k >= self.max_len):
            return self.generate(prompt, max_new)
        # prefill rounds DOWN: every cache row < cur0 must hold a REAL
        # prompt token (the verify chunk attends them before any
        # rewrite — round-up padding would poison later chunks)
        tp = max(2, min(1 << (t0.bit_length() - 1), self.max_len))
        caches = self._prefill_fn(1, tp)(
            self.params, jnp.asarray(prompt[:, :tp]))   # tp <= t0
        tokens = np.zeros((1, self.max_len), np.int32)
        tokens[0, :t0] = prompt[0]
        out = self._spec_fn(draft_k)(
            self.params, caches, jnp.asarray(tokens), jnp.int32(tp),
            jnp.int32(t0), jnp.int32(total))
        return np.asarray(out)[:, :total]

    def _cache_get(self, key):
        # the REST server is threaded and shares one generator: the
        # get/move_to_end pair must not race a concurrent eviction
        with self._cache_lock:
            fn = self._compiled.get(key)
            if fn is not None:
                self._compiled.move_to_end(key)
            return fn

    def _cache_put(self, key, fn):
        with self._cache_lock:
            self._compiled[key] = fn
            while len(self._compiled) > COMPILE_CACHE_SIZE:
                self._compiled.popitem(last=False)
        return fn

    def _run(self, params, tokens_np, prompt_len, greedy, seeds=0,
             top_k=0, top_p=1.0, inv_temp=1.0):
        """All per-row knobs accept a scalar (broadcast) or a [B]
        vector — the serving batcher passes vectors."""
        b = tokens_np.shape[0]
        pad = self.max_len - tokens_np.shape[1]
        if pad:
            tokens_np = np.concatenate(
                [tokens_np, np.zeros((b, pad), np.int32)], axis=1)

        def row(x, dtype):
            return jnp.broadcast_to(jnp.asarray(x, dtype), (b,))

        return self._scan_fn(b)(
            params, jnp.asarray(tokens_np), row(prompt_len, jnp.int32),
            row(seeds, jnp.int32), row(top_k, jnp.int32),
            row(top_p, jnp.float32), row(inv_temp, jnp.float32),
            row(greedy, jnp.bool_))

    # ------------------------------------------------------------------
    def generate(self, prompt, max_new, temperature=0.0, seed=0,
                 top_k=0, top_p=1.0):
        """prompt [B, T0] int tokens → [B, T0 + max_new].  temperature 0
        = greedy argmax; otherwise softmax sampling at that temperature,
        optionally truncated to the ``top_k`` best tokens and/or the
        ``top_p`` nucleus (smallest set reaching that probability
        mass)."""
        prompt = np.asarray(prompt, np.int32)
        b, t0 = prompt.shape
        t0, total, temperature, top_k, top_p, seed = \
            self.validate_request(
                t0, {"max_new": max_new, "temperature": temperature,
                     "seed": seed, "top_k": top_k, "top_p": top_p})
        greedy = temperature == 0.0
        out = self._decode_rows(
            prompt, [t0] * b, [total] * b, greedy, seed, top_k, top_p,
            1.0 if greedy else 1.0 / temperature)
        return out[:, :total]

    def validate_request(self, prompt_len, opts):
        """Validate ONE generate request's options against this model —
        raises ValueError; returns (t0, total, temperature, top_k,
        top_p, seed).  The serving batcher calls this BEFORE enqueueing
        so one bad request can never fail the batch it would have
        coalesced into."""
        t0 = int(prompt_len)
        max_new = int(opts.get("max_new", 16))
        if max_new < 0:
            raise ValueError("max_new must be >= 0, got %r" % (max_new,))
        total = t0 + max_new
        if total > self.max_len:
            raise ValueError("prompt + max_new = %d exceeds max_len %d"
                             % (total, self.max_len))
        temp = float(opts.get("temperature", 0.0))
        if temp < 0.0:
            raise ValueError("temperature must be >= 0, got %r"
                             % (temp,))
        top_p = float(opts.get("top_p", 1.0))
        top_k = int(opts.get("top_k", 0))
        if not 0.0 < top_p <= 1.0:
            raise ValueError("top_p must be in (0, 1], got %r"
                             % (top_p,))
        if not 0 <= top_k <= self._head.n_out:
            raise ValueError("top_k must be in [0, %d], got %r"
                             % (self._head.n_out, top_k))
        return t0, total, temp, top_k, top_p, int(opts.get("seed", 0))

    def generate_batch(self, prompts, opts_list):
        """Coalesce heterogeneous generate requests into ONE device
        call: ``prompts`` is a list of 1-D token sequences (any
        lengths), ``opts_list`` a parallel list of per-request dicts
        (max_new, temperature, seed, top_k, top_p).  Returns a list of
        1-D outputs, each trimmed to its request's prompt + max_new.
        Per-row traced parameters + per-(seed, position) sampling keys
        make each row's RANDOM DRAWS independent of what it was batched
        with; outputs equal the solo generate() call whenever the
        forward itself is batch-size-deterministic (exact on CPU — on
        TPU a different batch size can tile f32 reductions differently,
        so a near-tied argmax may flip on rare positions)."""
        if len(prompts) != len(opts_list):
            raise ValueError("prompts and opts_list lengths differ")
        b = len(prompts)
        lens, totals = [], []
        tk, tp, it, gr, sd = [], [], [], [], []
        for prompt, opts in zip(prompts, opts_list):
            t0, total, temp, top_k, top_p, seed = self.validate_request(
                len(prompt), opts)
            lens.append(t0)
            totals.append(total)
            tk.append(top_k)
            tp.append(top_p)
            it.append(1.0 if temp == 0.0 else 1.0 / temp)
            gr.append(temp == 0.0)
            sd.append(seed)
        t_max = max(lens)
        tokens = np.zeros((b, t_max), np.int32)
        for i, prompt in enumerate(prompts):
            tokens[i, :lens[i]] = np.asarray(prompt, np.int32)
        out = self._decode_rows(
            tokens, lens, totals, np.asarray(gr), np.asarray(sd),
            np.asarray(tk), np.asarray(tp, np.float32),
            np.asarray(it, np.float32))
        return [out[i, :totals[i]] for i in range(b)]

    def _beam_fn(self, batch, beam):
        """ONE compile per (batch, beam): scan over all max_len - 1
        positions; the prompt prefix teacher-forces every beam
        identically (scores pinned to 0 so beams only diverge after the
        prompt), then each step expands beam×V continuations and keeps
        the ``beam`` best, gathering the KV caches of the surviving
        parents."""
        cached = self._cache_get(("beam", batch, beam))
        if cached is not None:
            return cached
        bb = batch * beam

        def run(params, tokens, prompt_len, gen_end):
            # tokens: [batch, beam, max_len]
            caches = self._init_caches(bb, self._model_dtype())
            scores = self._beam_init_scores(batch, beam)
            body = self._beam_body(params, prompt_len, gen_end, batch,
                                   beam)
            (tokens, _, scores), _ = jax.lax.scan(
                body, (tokens, caches, scores),
                jnp.arange(self.max_len - 1))
            return tokens, scores

        return self._cache_put(("beam", batch, beam), jax.jit(run))

    @staticmethod
    def _beam_init_scores(batch, beam):
        # before any divergence only beam 0 may survive expansion,
        # or the result would be `beam` copies of one continuation
        scores = jnp.zeros((batch, beam), jnp.float32)
        return scores.at[:, 1:].set(-1e30)

    def _beam_body(self, params, prompt_len, gen_end, batch, beam):
        """Per-position beam-expansion body shared by the full scan and
        the prefilled beam scan.  Frozen steps (inside the prompt, past
        ``gen_end``, or a clamped overshoot position) keep an identity
        parent, so repeating them is a no-op — what makes power-of-two
        length buckets safe."""
        bb = batch * beam

        def body(carry, pos):
            tokens, caches, scores = carry
            logits, caches = self._step(
                params, caches, tokens.reshape(bb, -1)[:, pos], pos)
            logp = jax.nn.log_softmax(logits)        # [bb, V]
            v = logp.shape[-1]
            in_prompt = pos + 1 < prompt_len
            # beams freeze inside the prompt AND once max_new tokens
            # are out — scores must not accumulate past the horizon
            frozen = in_prompt | (pos + 1 >= gen_end)

            # candidate scores for every (beam, token) continuation
            cand = scores[:, :, None] + logp.reshape(batch, beam, v)
            flat = cand.reshape(batch, beam * v)
            top_s, top_i = jax.lax.top_k(flat, beam)
            parent = top_i // v                      # [batch, beam]
            tok = (top_i % v).astype(jnp.int32)

            # teacher forcing / frozen tail: every beam keeps its own
            # row and the already-present token, at no score cost
            keep_parent = jnp.broadcast_to(
                jnp.arange(beam)[None], (batch, beam))
            parent = jnp.where(frozen, keep_parent, parent)
            tok = jnp.where(frozen, tokens[:, :, pos + 1], tok)
            new_scores = jnp.where(frozen, scores, top_s)

            flat_parent = (parent
                           + jnp.arange(batch)[:, None] * beam
                           ).reshape(bb)
            tokens = jnp.take(tokens.reshape(bb, -1), flat_parent,
                              axis=0).reshape(batch, beam, -1)
            tokens = jax.lax.dynamic_update_slice(
                tokens, tok[:, :, None], (0, 0, pos + 1))
            # physical cache reorder: every step gathers the FULL
            # [B·beam, H, T_max, D] cache along the parent rows —
            # O(T·beam·H·D) HBM write traffic per position, so
            # O(T²·beam·H·D) per decode: fine at beam<=8 / T<=4k
            # (bench.py phase_beam records the T=4096 beam=8 rate);
            # a lazy ancestry-index reorder (gather at attention
            # time) would cut writes to O(1) per step but needs the
            # block step API to take per-position row indices —
            # revisit if long-context beam serving becomes hot
            caches = jax.tree_util.tree_map(
                lambda c: jnp.take(c, flat_parent, axis=0), caches)
            return (tokens, caches, new_scores), None

        return body

    def _beam_gen_fn(self, batch, beam, length):
        """ONE compile per (batch, beam, length bucket): beam expansion
        over ``length`` positions from traced ``start``, against
        prefilled BATCH caches tiled across the beams inside the jit
        (beam rows are identical during the prompt, so one batch-wide
        prefill serves all of them — the old path recomputed the prompt
        beam× through the serial scan)."""
        cached = self._cache_get(("beamgen", batch, beam, length))
        if cached is not None:
            return cached

        def run(params, caches, tokens, start, prompt_len, gen_end):
            caches = jax.tree_util.tree_map(
                lambda c: jnp.repeat(c, beam, axis=0), caches)
            scores = self._beam_init_scores(batch, beam)
            body = self._beam_body(params, prompt_len, gen_end, batch,
                                   beam)

            def body2(carry, i):
                pos = jnp.minimum(start + i, self.max_len - 2)
                return body(carry, pos)

            (tokens, _, scores), _ = jax.lax.scan(
                body2, (tokens, caches, scores), jnp.arange(length))
            return tokens, scores

        return self._cache_put(("beamgen", batch, beam, length),
                               jax.jit(run))

    def beam_search(self, prompt, max_new, beam=4):
        """Beam-search decode: prompt [B, T0] → (tokens [B, T0+max_new],
        log-probability of the returned best beam, [B]).

        Short prompts (< prefill_min) run the single full scan, which
        teacher-forces all ``beam`` rows identically — ONE executable
        per (batch, beam) regardless of prompt length.  Long prompts
        take the chunked-prefill path: ONE batch-wide prefill tiled
        across the beams plus a short expansion scan, compiling per
        ('pre', batch, prompt-bucket) and ('beamgen', batch, beam,
        length-bucket) — all LRU-bounded."""
        prompt = np.asarray(prompt, np.int32)
        b, t0 = prompt.shape
        total = t0 + int(max_new)
        if total > self.max_len:
            raise ValueError("prompt + max_new = %d exceeds max_len %d"
                             % (total, self.max_len))
        if not 1 <= int(beam) <= 64:
            # bounded like top_k: beam is client-controlled over REST,
            # and each distinct value compiles (and caches) an
            # executable whose cache memory scales with batch*beam
            raise ValueError("beam must be in [1, 64], got %r" % (beam,))
        tokens = np.zeros((b, beam, self.max_len), np.int32)
        tokens[:, :, :t0] = prompt[:, None, :]
        if t0 >= self.prefill_min:
            # batch-wide prefill, tiled to the beams in-jit: the prompt
            # is computed ONCE instead of beam x position-by-position
            tp, start, length = self._prefill_dispatch(t0, total)
            caches = self._prefill_fn(b, tp)(
                self.params, jnp.asarray(tokens[:, 0, :tp]),
                *self._valid(start))
            out, scores = self._beam_gen_fn(b, int(beam), length)(
                self.params, caches, jnp.asarray(tokens),
                jnp.int32(start), jnp.int32(t0), jnp.int32(total))
        else:
            out, scores = self._beam_fn(b, int(beam))(
                self.params, jnp.asarray(tokens), jnp.int32(t0),
                jnp.int32(total))
        best = np.asarray(jnp.argmax(scores, axis=1))
        out = np.asarray(out)[np.arange(b), best, :total]
        return out, np.asarray(scores)[np.arange(b), best]

    def score(self, tokens):
        """Per-position next-token logits from the incremental path
        (teacher forcing) — [B, T-1, V]; the equivalence oracle for the
        tests and a perplexity scorer."""
        tokens = np.asarray(tokens, np.int32)
        b, t = tokens.shape
        if t > self.max_len:
            raise ValueError("sequence %d exceeds max_len %d"
                             % (t, self.max_len))
        _, logits = self._run(self.params, tokens, t, True)
        return np.asarray(logits).transpose(1, 0, 2)[:, :t - 1]


class SlotState(NamedTuple):
    """What a batcher's jitted programs carry from tick to tick: one
    row a decode slot, and last the batcher's cache state — the
    slot-major caches of the dense batcher, ``(pool, tables)`` of the
    paged one.  The field order is the order of the programs'
    arguments."""

    tokens: jnp.ndarray     # int32 [B, max_len]: prompt + continuation
    pos: jnp.ndarray        # int32 [B]: the position the next tick reads
    plen: jnp.ndarray       # int32 [B]
    total: jnp.ndarray      # int32 [B]: plen + max_new
    active: jnp.ndarray     # bool  [B]
    seeds: jnp.ndarray      # int32 [B]
    inv_temp: jnp.ndarray   # f32   [B]: 0 = greedy
    cache: Any


class _Flight(NamedTuple):
    """A dispatched tick whose report the host has not read yet, with
    what the slots held WHEN IT WAS DISPATCHED: the report is read one
    call of ``tick()`` later, after admissions and releases the
    dispatch never saw, and is judged by its own occupancy."""

    report: Any             # the packed report, its copy to the host issued
    req: list               # slot -> the request id decoding there, or None
    total: np.ndarray       # int64 [B]: the host's ``_slot_total`` then
    staging: int            # slots reserved for a staged admission then
    stamp: _Stamp           # its landing's timing
    plain: bool             # its call admitted and staged nothing


def _pack_report(report):
    """A dispatch's report ``{name: [ticks, ...] array}`` as ONE int32
    ``[ticks, width]`` array and its layout ``[(name, shape a tick,
    dtype)]``: the host then makes one device->host copy a dispatch —
    a leaf of its own costs it 0.1 ms on a v5e's host (PERF.md, PR 35).
    Integers and flags widen to int32, float counts ride as their
    float32 bits."""
    layout, columns = [], []
    for name in sorted(report):
        leaf = report[name]
        layout.append((name, leaf.shape[1:], leaf.dtype))
        flat = leaf.reshape(leaf.shape[0], -1)
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            flat = jax.lax.bitcast_convert_type(
                flat.astype(jnp.float32), jnp.int32)
        columns.append(flat.astype(jnp.int32))
    return jnp.concatenate(columns, axis=1), layout


def _unpack_report(packed, layout):
    """``_pack_report``'s array, on the host, as the report again."""
    report, at = {}, 0
    for name, shape, dtype in layout:
        flat = packed[:, at:at + int(np.prod(shape))]
        at += flat.shape[1]
        if jnp.issubdtype(dtype, jnp.floating):
            flat = flat.view(np.float32)
        report[name] = flat.astype(dtype).reshape((-1,) + shape)
    return report


class ContinuousBatcher:
    """In-flight (continuous) batching over a fixed pool of decode
    slots: requests JOIN and LEAVE the batched decode at any step
    instead of waiting for a whole batch to finish together — the
    modern serving-engine admission model (capability beyond both the
    reference and this repo's coalescing ``GenerateBatcher``, which
    merges only same-phase requests).

    Design: one jitted per-tick step, ``jax.vmap`` of the generator's
    single-row incremental step with PER-ROW positions (each slot sits
    at its own depth in its own KV cache; the vmapped
    dynamic_update_slice becomes a scatter).  Admission chunk-prefills
    by default: the new prompt fills its slot's cache in one parallel
    pass and the row starts at the standard scan cursor
    (``chunked_prefill=False`` falls back to forcing the prompt
    token-by-token through the shared tick — the tick's prompt-forcing
    also finishes whatever a rolling-window prefill chunk leaves).
    Inactive slots tick too
    (uniform shapes beat recompiles); their writes stay inside their
    own slot so they cannot disturb live rows.

    Greedy and per-row temperature sampling; each row's draws depend
    only on its own (seed, position), so outputs are invariant to
    which slots or neighbors a request shared the pool with — the same
    contract GenerateBatcher proves for coalescing.

        cb = ContinuousBatcher(gen, slots=8)
        rid = cb.submit([1, 2, 3], max_new=16)
        while not cb.idle():
            cb.tick()
        tokens = cb.result(rid)
    """

    def __init__(self, gen, slots=8, ticks_per_dispatch=1,
                 chunked_prefill=True, speculative_k=0,
                 prefill_segment=0, prefill_tick_budget=0):
        self.gen = gen
        self.slots = int(slots)
        #: speculative_k > 0: n-gram speculative ticks — every active
        #: row verifies up to k drafted tokens per tick instead of
        #: decoding one (_make_core_spec; exact decode semantics).
        #: Dense pools, linear caches only.
        self.speculative_k = int(speculative_k)
        if self.speculative_k:
            if not 2 <= self.speculative_k <= 64:
                raise ValueError("speculative_k must be in [2, 64], "
                                 "got %d" % self.speculative_k)
            if self.speculative_k + 2 > gen.max_len:
                raise ValueError(
                    "speculative_k %d leaves no room for any request "
                    "at max_len %d (prompt+max_new+k must fit)"
                    % (self.speculative_k, gen.max_len))
            if gen._rolling:
                raise ValueError("speculative ticks need linear KV "
                                 "caches (rolling windows cannot "
                                 "absorb the rejected-draft tail)")
        #: fuse K engine ticks into ONE device dispatch (lax.scan over
        #: the tick body) — the same host→device amortization as the
        #: trainer's fused sweep.  Admission then happens at K-token
        #: boundaries; rows that hit their budget mid-scan freeze
        #: in-jit, so outputs stay EXACTLY the solo continuation at any
        #: K.  K=1 is pure per-token admission; K > 1 pays off where
        #: a dispatch costs more than a tick (not measured on the
        #: current machine).
        self.ticks_per_dispatch = max(1, int(ticks_per_dispatch))
        #: chunked-prefill admission: a new request's prompt fills its
        #: slot's KV cache in ONE parallel pass (TransformerBlock.
        #:prefill via _prefill_fn) and the row starts at the scan
        #: cursor _prefill_dispatch prescribes — instead of consuming
        #: one pool tick per prompt token.  The tick's prompt-forcing
        #: still covers whatever the prefill chunk didn't (rolling
        #: windows round the chunk DOWN).
        self.chunked_prefill = bool(chunked_prefill)
        #: segmented prefill admission (docs/services.md "Disaggregated
        #: prefill"): prefill_segment > 0 splits a long prompt's
        #: admission prefill into bounded chunk passes of at most
        #: ``prefill_segment`` tokens each, INTERLEAVED with decode
        #: ticks — one long admission can no longer stall every
        #: in-flight decode stream for its whole prompt.  The staged
        #: passes run _prefill_resume_fn's resume-from-cursor math
        #: (the prefix-cache resume contract: chunk_step == K step()
        #: calls), so the finished cache row and pos0 = plen - 1 are
        #: byte-identical to the unsegmented admission.  Per tick, at
        #: most ``prefill_tick_budget`` prefill tokens advance across
        #: ALL staged admissions (0 = one segment's worth; chunk
        #: passes are pow2-bucketed, so a tick may overshoot the
        #: budget by < 2x, never by a whole prompt).  0 = off.
        self.prefill_segment = max(0, int(prefill_segment or 0))
        self.prefill_tick_budget = max(0, int(prefill_tick_budget or 0))
        #: slot -> staged-admission record (a reserved slot whose
        #: prompt is still prefilling in segments; its row stays
        #: inactive so decode ticks skip it)
        self._staging = {}
        #: the head dims of the layers whose sparse-attention indexer
        #: ranks keys in every staged pass (``staged_keys`` counts how
        #: many, ``staged_kernel_tokens`` the tokens of the passes whose
        #: attention ran in ``veles_dsa_prefill``; both 0 without one)
        self._indexer_hd = {layer.head_dim for layer in gen._blocks
                            if layer.indexer}
        #: whether a staged pass hands out what its blocks counted: the
        #: pairs that landed on held experts, where the layers hold a
        #: share of theirs (``staged_expert_pairs``; with all held it
        #: is tokens x top_k, and the pass's program returns nothing
        #: beside its rows)
        self._pass_counts = any(
            layer.dropless and layer.experts_count < layer.n_experts
            for layer in gen._blocks)
        #: float32 bytes of the fixed-size state ONE slot holds over the
        #: state layers (0 without one), as allocated: the features'
        #: padding to whole lanes shows
        self._state_row_bytes = 4 * sum(
            int(np.prod(shape)) for layer in gen._blocks
            for shape in layer.state_leaves().values())
        #: the modules of the state layers, one a layer (the decode
        #: kernel launches a tick, the chunk a pass counts in)
        self._state_ops = [layer.state_ops() for layer in gen._blocks
                           if layer.state_leaves()]
        #: optional callable({"kind": "begin"|"segment"|"admit", ...})
        #: the serving engine hooks to surface serve.prefill flight
        #: events and gauges; runs on the tick() caller's thread
        self.prefill_observer = None
        self._set_state(self._fresh_state())
        #: per-slot adapter id (multi-LoRA routing; 0 = base).  Host-
        #: managed: changes only at admission, so it rides the tick as
        #: a separate non-donated argument, not a field of the state.
        self._aids = jnp.zeros((self.slots,), jnp.int32)
        self._slot_req = [None] * self.slots      # slot -> request id
        #: ``plen + max_new`` of what the host admitted into each slot:
        #: its own copy, never read back from the device
        self._slot_total = np.ones((self.slots,), np.int64)
        self._queue = collections.deque()
        self._results = {}
        #: rid -> monotonic timestamp of the request's FIRST decode
        #: tick (admission prefill complete, row active) — the
        #: prefill/decode boundary of the serving plane's per-request
        #: phase decomposition.  Survives slot release so the engine
        #: can read it at completion; pop_decode_start releases it.
        self._decode_start = {}
        #: rid -> the token list of a request in a slot: the prompt it
        #: was submitted with, then what the ticks' reports brought,
        #: APPENDED — ``partial(rid)`` while it decodes, its result at
        #: completion; dropped with the slot
        self._partials = {}
        #: request ids whose fixed-size state is kept when they finish
        #: (``keep_state``), and what was kept: rid -> the state layers'
        #: containers, each leaf the slot's row
        self._keep = set()
        self._kept = {}
        self._next_id = 0
        self._tick_fn = None
        self._admit_fn = None
        #: the last dispatch's report (``_make_core``), packed: ONE
        #: output of the tick program beside its donated state, and all
        #: the host reads of a tick; its layout is noted when
        #: ``_jit_ticks`` traces the program
        self._report = None
        self._report_layout = None
        #: the tick is dispatched ONE AHEAD: the ``_Flight`` a call of
        #: ``tick()`` left on the device, read by the next call after it
        #: has enqueued its own — at most one between calls, at most two
        #: inside one
        self._flying = None
        #: the staged passes enqueued and not yet waited for, oldest
        #: first (``_advance_staged`` / ``_land_passes``)
        self._passes = collections.deque()
        self._serial = 0        # calls of ``tick()`` so far
        #: the landing stamps (``time.perf_counter``): the newest
        #: dispatch a wait will land, the first enqueue since it (None:
        #: none yet), whether the call's first enqueue found the queue
        #: empty, and where and when it was first seen empty since the
        #: last enqueue (``(phase, seconds)`` or None)
        self._last_stamp = None
        self._since = None
        self._onto_empty = False
        self._empty_seen = None
        self._first_work = False
        #: per-tick seconds of each phase (reset at the top of a tick)
        #: and the tick's counts and stamps; ``last_tick`` is the
        #: finished tick's record, ``{<phase>_s: seconds, <count>: n,
        #: <stamp>: ...}`` — what the serving engine keeps a ring of
        #: (``ContinuousEngine.tick_records``)
        self._spans = {name: SpanAggregate(name) for name in TICK_SPANS}
        self._counts = dict.fromkeys(TICK_COUNTS, 0)
        self._stamps = {}
        self.last_tick = None

    #: whether the tick's state layers skip an idle row (the paged
    #: tick's kernel does; the vmapped dense step moves every slot)
    _skips_idle_rows = False

    def _span(self, name):
        return span(name, aggregate=self._spans[name])

    # ------------------------------------------------------------ public
    def submit(self, prompt, max_new, temperature=0.0, seed=0,
               adapter=0):
        """Queue a request; returns a request id.  The request enters
        the pool at the next tick with a free slot.  ``adapter``:
        multi-LoRA routing — 0 = base model, 1..N = the bank loaded by
        ``LMGenerator.load_adapter_bank``."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if int(max_new) < 1:
            raise ValueError("max_new must be >= 1, got %d"
                             % int(max_new))
        if len(prompt) + int(max_new) > self.gen.max_len:
            raise ValueError("prompt+max_new %d exceeds max_len %d"
                             % (len(prompt) + int(max_new),
                                self.gen.max_len))
        if self.speculative_k and (len(prompt) + int(max_new)
                                   + self.speculative_k
                                   > self.gen.max_len):
            raise ValueError(
                "speculative ticks draft %d positions past the "
                "cursor: prompt+max_new+k %d exceeds max_len %d"
                % (self.speculative_k,
                   len(prompt) + int(max_new) + self.speculative_k,
                   self.gen.max_len))
        n_bank = getattr(self.gen, "_n_adapters", 0)
        if not 0 <= int(adapter) <= n_bank:
            raise ValueError("adapter %d outside the loaded bank "
                             "(0..%d)" % (int(adapter), n_bank))
        rid = self._next_id
        self._next_id += 1
        self._queue.append((rid, prompt, int(max_new),
                            float(temperature), int(seed),
                            int(adapter)))
        return rid

    def idle(self):
        """Nothing queued, nothing in a slot, and nothing on the device
        that a call of ``tick()`` still has to read (the report
        dispatched one ahead, a staged pass's stamp)."""
        return not self._queue and self._flying is None \
            and not self._passes and not any(
                r is not None for r in self._slot_req)

    def active_requests(self):
        """Request ids currently holding a decode slot (admitted but
        not yet finished) — the serving plane's admission signal."""
        return {r for r in self._slot_req if r is not None}

    def keep_state(self, rid):
        """Keep request ``rid``'s fixed-size state as its last decode
        step leaves it: ``kept_state(rid)`` returns it once the request
        has finished.  The slot's rows are sliced on the device behind
        the tick in flight (a finished row is frozen in it), at its
        release: nothing waits."""
        self._keep.add(rid)

    def kept_state(self, rid):
        """What ``keep_state(rid)`` kept, releasing it: a list of the
        state layers' containers (``state_ops().State``) in layer order,
        each leaf ``[...]`` the request's slot row; None before the
        request has finished (or when it was cancelled)."""
        return self._kept.pop(rid, None)

    def result(self, rid):
        """Completed token list (prompt + continuation), or None while
        the request is still queued/decoding."""
        return self._results.get(rid)

    def pop_result(self, rid):
        """Like ``result`` but releases the stored tokens — long-running
        servers must not accumulate every completed request."""
        return self._results.pop(rid, None)

    def pop_decode_start(self, rid):
        """Monotonic timestamp of the request's first decode tick (the
        admit→decode phase boundary), releasing it — or None if the
        request never reached decode.  The serving engine reads it at
        completion to split queue/prefill/decode latency."""
        return self._decode_start.pop(rid, None)

    def cancel(self, rid):
        """Abort a request mid-flight: drop it from the queue, or —
        if already admitted — deactivate its row and free its slot
        (paged pools also free its KV blocks) WITHOUT waiting for the
        decode to finish.  The serving engine's cancellation path
        (client disconnect, deadline expiry, shutdown); single-caller
        contract like ``tick`` — only the engine thread may call it.
        Returns True if the request was queued or active; False if
        unknown or already finished (a finished result is released
        either way, so a cancelled request can never leak its
        tokens).  A tick dispatched before the cancel may still be on
        the device with the row in it: what its report says of the row
        is dropped when it is read (the slot is no longer this
        request's), and the row's blocks return to the free list now —
        a new owner's writes are enqueued behind that tick."""
        self._keep.discard(rid)
        for i, item in enumerate(self._queue):
            if item[0] == rid:
                del self._queue[i]
                return True
        if rid in self._slot_req:
            b = self._slot_req.index(rid)
            # the in-jit freeze flag: an inactive row neither writes
            # tokens nor advances, so the ticks dispatched from here on
            # stop paying for it; admission overwrites the whole slot
            # (incl. caches) for the next occupant
            self._active = self._active.at[b].set(False)
            self._decode_start.pop(rid, None)
            self._release_slot(b)
            return True
        self._results.pop(rid, None)
        self._decode_start.pop(rid, None)
        return False

    def reset_pool(self):
        """Hard reset after an engine fault: drop every queued and
        active request and rebuild the device-side state from scratch.
        A tick that raised mid-dispatch may have invalidated its
        DONATED buffers (state is donated into ``_jit_ticks``), so the
        arrays cannot be trusted — only their shapes/dtypes can.  The
        report in flight and the passes not yet stamped are dropped
        unread: they belong to the requests that are.
        Compiled tick/admit executables survive; callers own waking
        any waiters for the dropped requests."""
        self._queue.clear()
        self._results.clear()
        self._partials.clear()
        self._keep.clear()
        self._kept.clear()
        self._decode_start.clear()
        self._staging = {}
        self._flying = None
        self._passes.clear()
        self._last_stamp = self._since = self._empty_seen = None
        self._slot_req = [None] * self.slots
        self._set_state(self._fresh_state())
        self._aids = jnp.zeros((self.slots,), jnp.int32)

    def tick(self):
        """One engine step, the device kept ONE DISPATCH AHEAD of the
        host: admit queued requests into free slots (long prompts under
        segmented prefill only RESERVE their slot and stage — their
        prefill advances in bounded chunk passes, never in one
        whole-prompt pass), enqueue the staged passes within the
        per-tick budget, enqueue the decode tick that advances EVERY
        slot one token — and only THEN read the report of the tick the
        PREVIOUS call enqueued: emit its tokens and free its finished
        rows.  Nothing blocks before that read, so the host's read,
        emission, the caller's delivery and the next call's admission
        and dispatch all run while the device works on the tick behind
        the one being read.

        So a call's tokens are those of the dispatch before its own:
        the first call after an idle batcher reads nothing, completion
        and the admission into the freed slot act one tick late (a row
        that reached its budget is frozen in-jit, so the tick already
        behind it writes no token for it), and ``idle()`` stays false
        until a last call has read the report still in flight.  Returns
        the number of active slots as of the report read (of the
        dispatch, where none was).

        Every phase runs under a ``telemetry.span`` of ``TICK_SPANS``;
        ``last_tick`` holds their seconds, the call's counts (those of
        the report it read) and its stamps (``TICK_STAMPS``: the device
        time of what it landed, whether its first enqueue found the
        device's queue empty and in which phase that was first seen)."""
        for agg in self._spans.values():
            agg.reset()
        self._counts = dict.fromkeys(TICK_COUNTS, 0)
        self._stamps = dict.fromkeys(TICK_STAMPS)
        self._stamps.update(plain=False, passes=[])
        # nothing on the device and nobody in a slot: no work waited,
        # so an empty queue now is no drain
        self._counts["cold"] = int(
            self._flying is None and not self._passes
            and self._slot_req.count(None) == self.slots)
        self._first_work = True
        self._see_empty("engine")
        with self._span("batcher.tick"):
            n_active = self._tick_phases()
        self.last_tick = dict(
            self._counts, **self._stamps,
            **{name.partition(".")[2] + "_s": agg.total
               for name, agg in self._spans.items()})
        return n_active

    # ------------------------------------------------ landing stamps
    def _queue_empty(self):
        """Whether everything enqueued for a wait has finished: the
        newest such dispatch has landed or is ready (never blocks)."""
        last = self._last_stamp
        return last is None or last.array is None or _ready(last.array)

    def _see_empty(self, phase):
        """Note the first phase that sees the device's queue empty
        since the last enqueue."""
        if self._empty_seen is None and self._queue_empty():
            self._empty_seen = (phase, time.perf_counter())

    def _enqueuing(self):
        """Just before a program is enqueued — an admission's, a staged
        pass's, the tick's; eager work before it is folded in.  The
        call's first: did it go onto an empty queue?  Then the call
        ``drained``, charged to the phase that first saw the queue empty
        (``admit`` where none before this did) — unless it is ``cold``."""
        if self._first_work:
            self._first_work = False
            self._see_empty("admit")
            self._onto_empty = self._empty_seen is not None
            if self._onto_empty and not self._counts["cold"]:
                phase, seen = self._empty_seen
                self._counts["drained"] = 1
                self._stamps.update(drained_in=phase,
                                    drained_s=time.perf_counter() - seen)
            self._empty_seen = None
        if self._since is None:
            self._since = time.perf_counter()

    def _stamp(self, array):
        """A dispatch a later wait will land (enqueued just now, behind
        everything since the one before it)."""
        stamp = _Stamp(array, self._last_stamp, self._since,
                       self._onto_empty)
        self._last_stamp, self._since, self._onto_empty = stamp, None, False
        return stamp

    def _land(self, stamp, wait):
        """``wait(array)`` for ``stamp``'s dispatch under
        ``batcher.wait``; returns what it returned and the dispatch's
        device seconds.  A landing is EXACT where the array was not
        ready before the wait: the host was blocked when the device
        finished, so the wait's end is the landing.  Device seconds run
        from the exact landing of the dispatch before it in the queue
        (or from its first enqueue, where that was later or went onto
        an empty queue) to its own exact landing; None where either end
        was not seen — never an upper bound."""
        exact = not _ready(stamp.array)
        with self._span("batcher.wait"):
            out = wait(stamp.array)
            now = time.perf_counter()
        start, prev = None, stamp.prev
        if exact:
            stamp.landed = now
            if prev is not None and prev.landed is not None:
                start = max(prev.landed, stamp.enqueued)
            elif stamp.onto_empty:
                start = stamp.enqueued
        stamp.array = stamp.prev = None
        self._counts["landed"] += 1
        self._counts["unseen"] += start is None
        return out, None if start is None else now - start

    def _tick_phases(self):
        counts = self._counts
        # what the previous call left on the device: everything this
        # call enqueues goes BEHIND it, and it is read last
        flying, self._flying = self._flying, None
        self._serial += 1
        while self._can_admit():
            b = self._slot_req.index(None)
            if self._will_segment(len(self._queue[0][1])):
                self._begin_staged(b)
            else:
                self._admit(b)
        if self._staging:
            with self._span("batcher.admit"):
                self._advance_staged(
                    self.prefill_tick_budget or self.prefill_segment)
        # the rows this call's tick decodes: a slot that is occupied
        # and NOT staging (a staged slot is reserved, its device-side
        # cursor still the previous occupant's).  Without one no tick
        # is enqueued: a staging-only batcher runs its passes alone,
        # and a drained one reads its last report
        decoding = [None if b in self._staging else rid
                    for b, rid in enumerate(self._slot_req)]
        n_decoding = sum(rid is not None for rid in decoding)
        if n_decoding:
            # decode-start stamps: the first tick a row is dispatched in
            # (staged admissions land here the call their last segment
            # is enqueued)
            now = time.monotonic()
            for rid in decoding:
                if rid is not None and rid not in self._decode_start:
                    self._decode_start[rid] = now
            plain = not (counts["admitted"] or counts["prompt_tokens"]
                         or counts["staged_tokens"])
            self._set_state(self._tick(self._state()))
            self._flying = _Flight(self._report, decoding,
                                   self._slot_total.copy(),
                                   len(self._staging),
                                   self._stamp(self._report), plain)
        # the passes of the calls before this one finished ahead of the
        # report below (one device queue): stamping them here costs no
        # wait of its own
        self._land_passes(sum(entry["call"] < self._serial
                              for entry in self._passes))
        if flying is None:
            counts["staging"] = len(self._staging)
            return n_decoding
        # the call's one read: the host blocked on the report of the
        # PREVIOUS dispatch, with this call's queued behind it.  The
        # report's copy was enqueued behind its tick at the dispatch, so
        # it lands with it: a read issued after a wait would pay one
        # more round trip (0.5 ms a read on a v5e's host: PERF.md, PR 26)
        packed, self._stamps["device_s"] = self._land(flying.stamp,
                                                      np.asarray)
        self._stamps["plain"] = flying.plain
        with self._span("batcher.fetch"):
            # the report by its names, and the tick's counts from the
            # dispatch's last tick
            counts["fetch_bytes"] += packed.nbytes
            report = _unpack_report(packed, self._report_layout)
            last = {name: leaf[-1] for name, leaf in report.items()}
            pos = last["pos"]
            n_active = int(last["active"].sum())
            # a row is read against the occupancy of ITS OWN dispatch,
            # and only while that same request still holds the slot: a
            # row released since (finished in the report before this
            # one, cancelled) is a stranger's or nobody's by now, and
            # what this report says of it is dropped.  Completion is
            # that occupancy + the cursor (the in-jit freeze already
            # cleared ``active`` for rows that hit their budget
            # mid-scan, possibly several per fused dispatch).
            occupied = np.array([rid is not None and rid == now
                                 for rid, now in zip(flying.req,
                                                     self._slot_req)])
            done = occupied & (pos + 1 >= flying.total)
            counts["rows"] = int(occupied.sum())
            counts["staging"] = flying.staging
            # keys the occupied rows attended in this tick's (last)
            # decode step: the position it wrote + 1, which is the
            # cursor now — what the decode kernel had to read
            counts["kv_tokens"] = int(pos[occupied].sum())
            counts["kv_pages"] = self._kv_pages(pos[occupied])
            # keys the softmax ran over: all of a row's keys, unless
            # the blocks counted otherwise where the attention ran (a
            # sparse-attention indexer's selection) — device counts,
            # each a mean over the blocks
            counts["sel_keys"] = counts["kv_tokens"] \
                if "attended" not in last else float(
                    last["attended"][occupied].sum())
            # a ring group's layers count theirs apart (``win_keys``),
            # and a layer that holds a share of its experts the pairs
            # that landed on them
            if "win_attended" in last:
                counts["win_keys"] = float(
                    last["win_attended"][occupied].sum())
            for name in ("experts_touched", "expert_pairs"):
                if name in last:
                    counts[name] = float(last[name])
            if self._state_row_bytes:
                # the rows whose state the dispatch's decode steps moved
                # (a row that wrote a token was active), and the bytes
                # they read + wrote, from the shapes they ran on: the
                # kernel skips an idle row, XLA's form moves every slot
                moved = int((report["n"] > 0).sum()) \
                    if self._skips_idle_rows \
                    else self.slots * len(report["n"])
                counts["state_rows"] = moved
                counts["state_bytes"] = 2 * moved * self._state_row_bytes
                # one decode-kernel launch a state layer and tick where
                # the kernel runs (it launches with no row active too)
                counts["state_launches"] = len(self._state_ops) * len(
                    report["n"]) if self._skips_idle_rows else 0
        self._see_empty("fetch")
        with self._span("batcher.emit"):
            # the new tokens only: a tick wrote ``n`` of them, at the
            # positions up to its cursor.  Positions under ``plen`` are
            # the prompt's whatever forced them through the tick, and
            # the list holds them since admission: it takes the
            # positions from its own length on.
            wrote, cursor, tokens = (report[name].tolist()
                                     for name in ("n", "pos", "tokens"))
            for b in np.nonzero(occupied)[0]:
                rid = self._slot_req[b]
                out = self._partials[rid]
                for t, row in enumerate(wrote):
                    first = cursor[t][b] - row[b] + 1
                    out.extend(tokens[t][b][max(0, len(out) - first):row[b]])
                if done[b]:
                    self._results[rid] = out
                    if rid in self._keep:
                        self._keep.discard(rid)
                        self._kept[rid] = self._slot_state(int(b))
                    self._release_slot(int(b))
            counts["finished"] = int(done.sum())
        self._see_empty("emit")
        return n_active

    def partial(self, rid):
        """The tokens of a request in a slot so far, prompt included
        (the batcher's own list, which later ticks append to: slice it,
        do not keep it), or None before admission / after completion.
        Granularity is one dispatch (``ticks_per_dispatch`` tokens per
        update), and it lags the device by the one dispatch whose
        report is still in flight."""
        return self._partials.get(rid)

    # --- subclass hooks (the paged batcher reshapes the cache state) ---
    def _init_slot_caches(self):
        """Dense slot-major KV allocation; the paged subclass allocates
        its (smaller) pool and the tables instead — it must never pay a
        dense-sized startup spike."""
        return self.gen._init_caches(self.slots, self.gen._model_dtype())

    def _fresh_row(self):
        """A [1, ...] cache row no position of which is written: what a
        staged admission starts from and a token-by-token one admits."""
        return self.gen._init_caches(1, self.gen._model_dtype())

    def _prefill_row(self, plen, max_new):
        """``(program, tp, start)`` of a whole-prompt admission
        prefill: ``program(params, toks [1, tp])`` fills a row, and the
        row starts decoding at ``start`` (rolling windows round the
        chunk DOWN: ``LMGenerator._prefill_dispatch``)."""
        tp, start, _ = self.gen._prefill_dispatch(plen, plen + max_new)
        return self.gen._prefill_fn(1, tp), tp, start

    def _fresh_state(self):
        """Every slot free: what construction and ``reset_pool``
        start from."""
        B, L = self.slots, self.gen.max_len
        return SlotState(
            tokens=jnp.zeros((B, L), jnp.int32),
            pos=jnp.zeros((B,), jnp.int32),
            plen=jnp.ones((B,), jnp.int32),
            total=jnp.ones((B,), jnp.int32),
            active=jnp.zeros((B,), jnp.bool_),
            seeds=jnp.zeros((B,), jnp.int32),
            inv_temp=jnp.zeros((B,), jnp.float32),
            cache=self._init_slot_caches())

    def _can_admit(self):
        return bool(self._queue) and None in self._slot_req

    def _kv_pages(self, keys):
        """Pool pages the decode kernel walked for rows that attended
        ``keys`` keys each; the dense slots have no pages."""
        return 0

    def _layer_caches(self):
        """Each layer's cache, in layer order: the dense slots'."""
        return self._caches

    def _slot_state(self, b):
        """Slot ``b``'s rows of the state layers' leaves (device
        slices)."""
        return [jax.tree_util.tree_map(lambda a: a[b], cache)
                for layer, cache in zip(self.gen._blocks,
                                        self._layer_caches())
                if layer.state_leaves()]

    def _release_slot(self, b):
        self._partials.pop(self._slot_req[b], None)
        self._slot_req[b] = None
        # a cancelled staged admission drops its partial prefill row
        # (paged: the subclass's block free path runs either way)
        self._staging.pop(b, None)

    def _state(self):
        return SlotState(self._tokens, self._pos, self._plen,
                         self._total, self._active, self._seeds,
                         self._inv_temp, self._caches)

    def _set_state(self, st):
        (self._tokens, self._pos, self._plen, self._total,
         self._active, self._seeds, self._inv_temp, self._caches) = st

    def run_all(self):
        """Drive until every submitted request completed."""
        while not self.idle():
            self.tick()
        return self._results

    # ----------------------------------------------------------- internal
    def _will_chunk(self, plen):
        """Whether admission chunk-prefills this prompt — THE predicate
        _admission_row, _shareable_blocks, and the paged admit's
        resume-vs-full decision all share (a drifted hand-copy would
        let blocks register as shareable that the tick-by-tick path
        fills progressively)."""
        return self.chunked_prefill and plen >= 2

    def _admission_row(self, b, rec):
        """Chunked-prefill admission of request ``rec`` into slot
        ``b``: one parallel pass fills a [1, ...]
        cache row with the prompt and returns (cache_row, start_pos);
        the tick's prompt-forcing covers whatever the chunk didn't
        (rolling windows prefill a smaller chunk).  (None, 0) when the
        request prefills token-by-token through the shared tick.
        The prompt's K/V must be computed under the SAME adapter the
        decode will run (grafted params; id 0 = base).  The paged
        subclass claims the slot's blocks here first."""
        gen = self.gen
        prompt, plen, max_new, adapter = (
            rec["prompt"], rec["plen"], rec["max_new"], rec["adapter"])
        if self._will_chunk(plen):
            program, tp, start = self._prefill_row(plen, max_new)
            chunk = np.zeros((tp,), np.int32)
            chunk[:min(plen, tp)] = prompt[:tp]
            params = gen._graft_adapters(gen.params,
                                         jnp.int32(adapter))
            self._enqueuing()
            return program(params, jnp.asarray(chunk[None]),
                           *gen._valid(start)), start
        return None, 0

    # ------------------------------------------- segmented admission
    def _will_segment(self, plen):
        """Whether admission STAGES this prompt (segmented prefill):
        the knob is on, the prompt chunk-prefills at all, the cache is
        linear (a rolling ring must round its one prefill chunk DOWN —
        generate._prefill_dispatch — so it keeps the unsegmented
        path), and the prefill work [0, plen-1) exceeds one segment
        (otherwise one pass IS the bound)."""
        return (self.prefill_segment > 0 and self._will_chunk(plen)
                and not self.gen._rolling
                and plen - 1 > self.prefill_segment)

    def _staged_setup(self, b, prompt, plen, max_new, adapter):
        """Subclass hook: reserve admission resources and return the
        (cache_row, cursor, extras) a staged prefill starts from;
        ``cache_row`` is a thunk, called when the admission's first
        pass runs — every free slot may begin staging in one tick, and
        a [1, ...] row of a long-context model is hundreds of MB that
        nothing reads while the admission waits its turn.
        Dense pools start from a fresh [1, ...] row at cursor 0; the
        paged subclass claims KV blocks and may resume mid-prompt
        from a matched prefix."""
        return self._fresh_row, 0, {}

    def _take_head(self, b):
        """The queue's head leaves it for slot ``b``: its record."""
        (rid, prompt, max_new, temperature, seed,
         adapter) = self._queue.popleft()
        self._aids = self._aids.at[b].set(adapter)
        self._partials[rid] = list(prompt)
        self._slot_total[b] = len(prompt) + int(max_new)
        return {"rid": rid, "prompt": prompt, "plen": len(prompt),
                "max_new": int(max_new), "temperature": temperature,
                "seed": seed, "adapter": adapter}

    def _begin_staged(self, b):
        """Reserve slot ``b`` for the queue head and stage its
        segmented prefill — cheap (allocation only): the chunk passes
        run in _advance_staged under the per-tick budget, so beginning
        never stalls the tick and the requests queued behind a long
        prompt admit without waiting for its prefill."""
        with self._span("batcher.admit"):
            rec = self._take_head(b)
            caches, cursor, extras = self._staged_setup(
                b, rec["prompt"], rec["plen"], rec["max_new"],
                rec["adapter"])
            # the adapter graft is fixed for the whole admission:
            # build it ONCE here, not once per segment pass
            rec.update(extras, caches=caches, cursor=int(cursor),
                       params=self.gen._graft_adapters(
                           self.gen.params, jnp.int32(rec["adapter"])))
            self._slot_req[b] = rec["rid"]
            self._staging[b] = rec
            if self.prefill_observer is not None:
                self.prefill_observer({"kind": "begin", "rid": rec["rid"],
                                       "slot": b, "plen": rec["plen"],
                                       "cursor": rec["cursor"]})
            self._counts["admitted"] += 1

    def _advance_staged(self, budget):
        """Advance staged prefills by bounded chunk passes, spending
        at most ``budget`` prompt tokens this tick (pow2 bucketing may
        overshoot by < 2x); an admission whose cursor reaches
        plen - 1 finishes into its reserved slot — with the exact
        cache row and start position the unsegmented admission hands
        over.  Returns the budget left.

        A pass is ENQUEUED here and not waited for: it runs behind the
        tick in flight, and its wait, its ``seconds`` and its
        ``segment`` event come in ``_land_passes`` — one call later,
        just before the read of the report enqueued behind it."""
        gen = self.gen
        for b in sorted(self._staging):
            rec = self._staging[b]
            while budget > 0 and rec["cursor"] < rec["plen"] - 1:
                start = rec["cursor"]
                want = min(self.prefill_segment,
                           rec["plen"] - 1 - start, budget)
                # a long segment's tail runs in passes of at least an
                # eighth of it: a few compiled programs, not one per
                # power of two down to 1 (rows past the prompt are
                # rewritten before any mask lets them be read)
                kb = gen._bucket(max(1, want, self.prefill_segment // 8),
                                 gen.max_len - start)
                chunk = np.zeros((kb,), np.int32)
                n_real = min(rec["plen"] - start, kb)
                chunk[:n_real] = rec["prompt"][start:start + n_real]
                # the row is DONATED to the pass: an earlier pass of
                # this admission that is not stamped yet is waited for
                # on this very row, so it lands first — with the tick
                # in flight still queued behind it, where the pass was
                # enqueued by an earlier call
                self._land_passes(1 + max(
                    (i for i, entry in enumerate(self._passes)
                     if entry["event"]["rid"] == rec["rid"]), default=-1))
                if callable(rec["caches"]):
                    rec["caches"] = rec["caches"]()
                self._enqueuing()
                rec["caches"] = gen._prefill_resume_fn(
                    kb, self._pass_counts)(
                    rec["params"], rec["caches"],
                    jnp.asarray(chunk[None]), jnp.int32(start),
                    *gen._valid(min(kb, rec["plen"] - 1 - start)))
                pairs = None
                if self._pass_counts:
                    rec["caches"], seen = rec["caches"]
                    pairs = seen["expert_pairs"]
                    pairs.copy_to_host_async()
                rec["cursor"] = min(start + kb, rec["plen"] - 1)
                budget -= kb
                self._counts["staged_tokens"] += kb
                if gen._stateful:
                    self._counts["staged_chunks"] += \
                        -(-kb // self._state_ops[0].CHUNK)
                if self._indexer_hd:
                    # the live width as the pass's program bounds it,
                    # and the kernel where the pass's program chose it
                    self._counts["staged_keys"] += \
                        attention.dsa_live_blocks(start + kb,
                                                  gen.max_len)[1]
                    if all(attention.dsa_prefill_tiles(kb, gen.max_len, hd)
                           for hd in self._indexer_hd):
                        self._counts["staged_kernel_tokens"] += kb
                self._passes.append({
                    "call": self._serial, "pairs": pairs,
                    "stamp": self._stamp(
                        jax.tree_util.tree_leaves(rec["caches"])[0]),
                    "event": {"kind": "segment", "rid": rec["rid"],
                              "slot": b, "start": start, "tokens": kb,
                              "cursor": rec["cursor"],
                              "plen": rec["plen"]}})
            if rec["cursor"] >= rec["plen"] - 1:
                del self._staging[b]
                if callable(rec["caches"]):     # no pass was needed
                    rec["caches"] = rec["caches"]()
                self._finish_staged(b, rec)
                # behind the admission's last ``segment`` events
                self._passes.append({
                    "call": self._serial, "stamp": None,
                    "event": {"kind": "admit", "rid": rec["rid"],
                              "slot": b, "plen": rec["plen"]}})
        return budget

    def _land_passes(self, n):
        """Wait for the ``n`` oldest passes enqueued and not stamped
        yet, in the order they run, and hand each to the
        ``prefill_observer``.  A pass's ``seconds`` is its time ON THE
        DEVICE (``_land``): from the exact landing of what ran before it
        (a report, the pass before) — or from its own enqueue, where the
        device was idle — to its own exact landing; the tick it was
        enqueued behind is not in it.  None where either end was not
        seen.  An admission's ``admit`` event waits here behind its
        last ``segment``."""
        for _ in range(n):
            entry = self._passes.popleft()
            event = entry["event"]
            if entry["stamp"] is not None:
                _, event["seconds"] = self._land(entry["stamp"],
                                                 jax.block_until_ready)
                self._stamps["passes"].append(
                    (event["tokens"], event["seconds"]))
                if entry["pairs"] is not None:
                    # its copy rode behind the pass: no round trip
                    self._counts["staged_expert_pairs"] += float(
                        entry["pairs"])
                    self._counts["fetch_bytes"] += entry["pairs"].nbytes
            if self.prefill_observer is not None:
                self.prefill_observer(event)

    def _finish_staged(self, b, rec):
        """Staged prefill complete: run the normal admission scatter
        with the accumulated cache row at pos0 = plen - 1 (the same
        cursor _admission_row's full chunk hands over at)."""
        self._ensure_admit_fns()
        self._enqueuing()
        st = self._admit_fn(*self._admit_args(b, rec),
                            jnp.int32(rec["plen"] - 1), rec["caches"])
        self._set_state(st)
        self._counts["admit_state_bytes"] += self._state_row_bytes

    def _admit_args(self, b, rec):
        """The shared positional prefix of _admit_fn/_admit_fresh_fn
        (state + scalar slot writes) for one request record."""
        prow = np.zeros((self.gen.max_len,), np.int32)
        prow[:rec["plen"]] = rec["prompt"]
        return (self._state(), jnp.int32(b), jnp.asarray(prow),
                jnp.int32(rec["plen"]),
                jnp.int32(rec["plen"] + rec["max_new"]),
                jnp.int32(rec["seed"]),
                jnp.float32(0.0 if rec["temperature"] == 0.0
                            else 1.0 / rec["temperature"]))

    def prefill_backlog_tokens(self):
        """Queued-but-unprefilled prompt tokens: whole prompts still
        in the queue plus the unprefilled remainder of staged
        admissions — the serving plane's prefill-backlog gauge (the
        fleet autoscaler's early scale-up signal)."""
        queued = sum(len(item[1]) for item in self._queue)
        staged = sum(max(0, rec["plen"] - 1 - rec["cursor"])
                     for rec in self._staging.values())
        return queued + staged

    def state_in_use(self):
        """``(slots, bytes)`` of fixed-size state held for the requests
        in the slots (a staging request's row counts: it is as large) —
        the gauge of a state layer's memory, where ``blocks_in_use`` is
        a paged one's; ``(0, 0)`` without state layers."""
        held = sum(r is not None for r in self._slot_req) \
            if self._state_row_bytes else 0
        return held, held * self._state_row_bytes

    def staging_slots(self):
        """Slots currently mid-staged-prefill (reserved, not yet
        decoding)."""
        return len(self._staging)

    def _ensure_admit_fns(self):
        if self._admit_fn is not None:
            return
        gen = self.gen

        def serve_admit(st, b, prow, plen, total, seed, inv_temp,
                        *rest):
            # ONE fused dispatch: the slot's scalars and its cache
            # state.  ``rest``: what the batcher's own _admit_args
            # appends, then the start position and the [1, ...] row
            *own, pos0, cache_row = rest
            return st._replace(
                tokens=jax.lax.dynamic_update_slice(
                    st.tokens, prow[None], (b, 0)),
                pos=st.pos.at[b].set(pos0),
                plen=st.plen.at[b].set(plen),
                total=st.total.at[b].set(total),
                active=st.active.at[b].set(True),
                seeds=st.seeds.at[b].set(seed),
                inv_temp=st.inv_temp.at[b].set(inv_temp),
                cache=self._admit_cache(st.cache, b, cache_row, *own))

        def serve_admit_fresh(*args):
            # fresh values built INSIDE the jit (zeros, QuantCache
            # scale ones) — the non-prefill path pays no extra
            # dispatch and no host-built zero tree
            return serve_admit(*args, jnp.int32(0), self._fresh_row())

        self._admit_fn = jax.jit(serve_admit, donate_argnums=(0,))
        self._admit_fresh_fn = jax.jit(serve_admit_fresh,
                                       donate_argnums=(0,))

    def _admit_cache(self, caches, b, cache_row):
        """Subclass hook, inside the admission's jit: slot ``b``'s
        cache state taken over by the [1, ...] row — which replaces
        the slot's ENTIRE cache, either freshly initialized (stale K/V
        from the previous occupant must not leak) or chunk-prefilled
        with the new prompt."""
        return jax.tree_util.tree_map(
            lambda pool, one: jax.lax.dynamic_update_slice(
                pool, one.astype(pool.dtype),
                (b,) + (0,) * (pool.ndim - 1)),
            caches, cache_row)

    def _admit(self, b):
        with self._span("batcher.admit"):
            rec = self._take_head(b)
            self._ensure_admit_fns()
            cache_row, pos0 = self._admission_row(b, rec)
            args = self._admit_args(b, rec)
            self._enqueuing()       # where no prefill program ran
            if cache_row is None:
                st = self._admit_fresh_fn(*args)
            else:
                st = self._admit_fn(*args, jnp.int32(pos0), cache_row)
            self._set_state(st)
            self._slot_req[b] = rec["rid"]
            self._counts["admitted"] += 1
            self._counts["prompt_tokens"] += rec["plen"]
            # the admission's two costs: the slot's state written whole
            # (here), the pool's blocks claimed (``_claim_blocks``)
            self._counts["admit_state_bytes"] += self._state_row_bytes

    def _make_core(self, step_all=None):
        """The per-tick body ``core(params, state, aids) -> (state,
        report)`` over a ``SlotState`` — shared verbatim by the dense
        tick and the paged one, so the admission models can never
        diverge on decode semantics.

        The REPORT is what the host needs of the tick and nothing else,
        an output of its own and no leaf of the donated state: the
        token(s) each row wrote into ``tokens`` (``tokens`` [B, 1] here,
        [B, k] from the speculative core) and how many (``n``: 0 for a
        frozen or inactive row), the row's cursor and its flag after
        the tick (``pos``, ``active``), and what ``step_all`` counted.
        A few hundred bytes at any ``max_len``.

        ``step_all(params, cache_state, cur, pos, aids, active) ->
        (logits, cache_state, counts)`` abstracts how a tick runs the
        stack (``active``: the rows the tick advances): the
        dense default vmaps gen._step per row over slot-major caches
        and counts nothing (``{}``); the paged batcher substitutes the
        pool-batched gen._step_paged (the pool is shared across rows,
        so it cannot vmap) and what its blocks counted on the device.
        Token selection, sampling, prompt forcing, and the freeze logic
        stay this one function either way."""
        gen = self.gen

        if step_all is None:
            def row_step(params, caches, tok, pos, aid):
                # single-row view: add the batch dim the stack expects;
                # under vmap the per-row ``pos`` scatter-writes each
                # slot at its own depth.  Adapter grafting happens per
                # row (scalar aid) — identity without banks.
                c1 = jax.tree_util.tree_map(lambda a: a[None], caches)
                logits, c1 = gen._step(
                    gen._graft_adapters(params, aid), c1, tok[None],
                    pos)
                return logits[0], jax.tree_util.tree_map(
                    lambda a: a[0], c1)

            def step_all(params, caches, cur, pos, aids, active):
                return jax.vmap(row_step, in_axes=(None, 0, 0, 0, 0))(
                    params, caches, cur, pos, aids) + ({},)

        def core(params, st, aids):
            tokens, pos, active = st.tokens, st.pos, st.active
            seeds, inv_temp = st.seeds, st.inv_temp
            B = tokens.shape[0]
            rows = jnp.arange(B)
            cur = tokens[rows, pos]
            logits, cache, counts = step_all(params, st.cache, cur, pos,
                                             aids, active)
            greedy_tok = jnp.argmax(logits, axis=-1).astype(
                jnp.int32)

            def draw(_):
                keys = jax.vmap(
                    lambda s, p: jax.random.fold_in(
                        jax.random.key(s), p))(seeds, pos)
                sampled = jax.vmap(
                    lambda lg, k, it: jax.random.categorical(
                        k, lg * it))(logits, keys,
                                     inv_temp).astype(jnp.int32)
                return jnp.where(inv_temp > 0.0, sampled,
                                 greedy_tok)

            # all-greedy pools (the serving default) skip the
            # whole-vocab gumbel draw entirely — same guard as
            # _decode_body's lax.cond
            nxt = jax.lax.cond(jnp.any(inv_temp > 0.0), draw,
                               lambda _: greedy_tok, None)
            # prefilling rows force their own next prompt token
            in_prompt = pos + 1 < st.plen
            forced = tokens[rows, jnp.minimum(pos + 1,
                                              tokens.shape[1] - 1)]
            nxt = jnp.where(in_prompt, forced, nxt)
            write = active & (pos + 1 < tokens.shape[1])
            tokens = tokens.at[rows, jnp.minimum(
                pos + 1, tokens.shape[1] - 1)].set(
                jnp.where(write, nxt, tokens[rows, jnp.minimum(
                    pos + 1, tokens.shape[1] - 1)]))
            pos = jnp.where(active, pos + 1, pos)
            # rows that just hit their budget freeze IN-JIT, so a
            # fused multi-tick scan can't overshoot max_new (the
            # host derives completion from slot occupancy)
            active = active & (pos + 1 < st.total)
            return (st._replace(tokens=tokens, pos=pos, active=active,
                                cache=cache),
                    dict(counts, tokens=nxt[:, None],
                         n=write.astype(jnp.int32), pos=pos,
                         active=active))

        return core

    def _make_core_spec(self, draft_k):
        """Speculative tick core (``speculative_k`` > 0, dense slot
        pools): every active row drafts ``draft_k`` candidate tokens
        from its own history (the n-gram rule of LMGenerator._spec_fn)
        and verifies them in ONE chunk pass per tick, advancing by
        1 + accepted instead of 1.

        EXACT decode semantics, PER ROW:
        * greedy rows accept exactly the prefix of drafts that equal
          the verify pass's own argmax — the accepted tokens ARE the
          argmax chain, so outputs match the 1-token core token for
          token;
        * prompt positions auto-accept their own forced tokens (a
          prefilling row fast-forwards through its prompt — same
          tokens and cache writes, fewer ticks);
        * sampled rows accept only forced prompt positions, then draw
          their ONE new token from the chunk's logits at that position
          with the identical (seed, position) key the 1-token core
          would have used — bit-equal streams.

        Routing is PER ROW: the draft/verify/acceptance math runs
        identically for every row regardless of what it shares the
        pool with, and each row's ``sampled = inv_temp > 0`` flag
        selects its own token in a ``where``.  The only pool-wide
        ``lax.cond`` left gates the PRICE of the gumbel draws (the
        1-token core's own all-greedy guard) — never the speculation
        semantics, so one sampled request cannot strip speculation
        from (or perturb by one bit) the greedy rows around it.  The
        old pool-wide branch between a sampled and a greedy step
        function — the `serve.spec_degraded` cliff — is gone.

        The chunk writes draft-conditioned K/V up to ``draft_k``
        positions past a row's cursor; rejected-tail entries are
        rewritten by a later chunk before any mask lets them be
        attended (mha_chunk_step's contract).  submit() therefore
        requires plen + max_new + draft_k <= max_len."""
        gen = self.gen
        kk = int(draft_k)
        ll = gen.max_len
        idx = jnp.arange(kk)

        def row_verify(params, caches, row, pos, aid, inv_temp, plen,
                       total):
            """Per-row draft + K-wide verify + acceptance count — NO
            sampling in here; the draw routes per row outside the
            vmap, so the verify math is one program for every pool
            mix."""
            params = gen._graft_adapters(params, aid)
            c1 = jax.tree_util.tree_map(lambda a: a[None], caches)
            draft = _ngram_draft(row, pos, kk, ll)
            # candidate positions are pos+1 .. pos+kk; submit()'s
            # total + kk <= max_len bound keeps every slice in range
            # (no clamping, so read/write windows always align)
            in_prompt = (pos + 1 + idx) < plen
            old = jax.lax.dynamic_slice(row, (pos + 1,), (kk,))
            draft = jnp.where(in_prompt, old, draft)
            cur_tok = jax.lax.dynamic_slice(row, (pos,), (1,))
            chunk = jnp.concatenate([cur_tok, draft[:-1]])[None]
            logits, c1 = gen._chunk_logits(params, c1, chunk, pos)
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)

            sampled = inv_temp > 0.0
            ok = in_prompt | (~sampled & (draft == g))
            # first rejection = acceptance count; cap so the bonus
            # position always has its own logits AND the row never
            # writes past total - 1
            a = jnp.minimum(jnp.argmin(jnp.concatenate(
                [ok, jnp.zeros((1,), bool)])), kk - 1)
            a = jnp.minimum(a, jnp.maximum(total - 2 - pos, 0))
            return (jax.tree_util.tree_map(lambda x: x[0], c1),
                    draft, old, in_prompt, a, jnp.take(g, a),
                    logits[a])

        verify_all = jax.vmap(row_verify,
                              in_axes=(None, 0, 0, 0, 0, 0, 0, 0))

        def core(params, st, aids):
            tokens, pos, active = st.tokens, st.pos, st.active
            seeds, inv_temp = st.seeds, st.inv_temp
            (caches, draft, old, in_prompt, a, g_a, logits_a) = \
                verify_all(params, st.cache, tokens, pos, aids,
                           inv_temp, st.plen, st.total)
            sampled = inv_temp > 0.0

            def draw(_):
                keys = jax.vmap(
                    lambda s, p: jax.random.fold_in(
                        jax.random.key(s), p))(seeds, pos + a)
                smp = jax.vmap(
                    lambda lg, k, it: jax.random.categorical(
                        k, lg * it))(logits_a, keys,
                                     inv_temp).astype(jnp.int32)
                return jnp.where(sampled, smp, g_a)

            # all-greedy pools (the serving default) skip the
            # whole-vocab gumbel draws entirely — same cost guard as
            # the 1-token core's lax.cond; greedy rows select g_a on
            # BOTH sides of it, so the branch can never change a
            # greedy row's bytes
            gen_tok = jax.lax.cond(jnp.any(sampled), draw,
                                   lambda _: g_a, None)
            old_a = jnp.take_along_axis(old, a[:, None], 1)[:, 0]
            prompt_a = jnp.take_along_axis(in_prompt, a[:, None],
                                           1)[:, 0]
            # the bonus position must never overwrite a teacher-forced
            # prompt token
            bonus = jnp.where(prompt_a, old_a, gen_tok)
            newvec = jnp.where(idx[None, :] < a[:, None], draft,
                               jnp.where(idx[None, :] == a[:, None],
                                         bonus[:, None], old))
            # frozen rows write their own old values back (idempotent)
            newvec = jnp.where(active[:, None]
                               & (idx[None, :] <= a[:, None]),
                               newvec, old)
            tokens = jax.vmap(
                lambda r, nv, p: jax.lax.dynamic_update_slice(
                    r, nv, (p + 1,)))(tokens, newvec, pos)
            n = jnp.where(active, a + 1, 0)
            pos = pos + n
            active = active & (pos + 1 < st.total)
            return (st._replace(tokens=tokens, pos=pos, active=active,
                                cache=caches),
                    dict(tokens=newvec, n=n, pos=pos, active=active))

        return core

    def _jit_ticks(self, tick_fn):
        """ticks_per_dispatch engine ticks fused into ONE jitted
        dispatch (lax.scan over ``tick_fn(params, state, aids) ->
        (state, report)``), state donated: without aliasing, every
        per-token tick would copy the whole slots×layers KV-cache pool.
        Returns the state and the ticks' reports (``{name:
        [ticks_per_dispatch, ...]}``) packed into one fresh output
        (``_pack_report``; ``_report_layout`` is how the host unpacks
        it).  One helper shared by the dense and the paged tick so the
        dispatch-fusion contract can never diverge between them."""
        # the name is the host plane's: PjitFunction(serve_tick)
        def serve_tick(params, st, aids):
            st, report = jax.lax.scan(
                lambda carry, _: tick_fn(params, carry, aids), st, None,
                length=self.ticks_per_dispatch)
            # static, so noted while the program is traced
            packed, self._report_layout = _pack_report(report)
            return st, packed

        return jax.jit(serve_tick, donate_argnums=(1,))

    def _tick_body(self):
        """The un-jitted tick body ``fn(params, state, aids) -> (state,
        report)`` this batcher dispatches (through :meth:`_jit_ticks`).
        ONE construction point shared by the engine and the decode-path
        auditor (``analysis.decode_audit``), which abstractly traces
        exactly this function — so the lint can never audit a different
        tick than serving runs."""
        return (self._make_core_spec(self.speculative_k)
                if self.speculative_k else self._make_core())

    def _tick(self, st):
        """Enqueue one dispatch on ``st`` (futures: nothing blocks) and
        issue its report's copy to the host; ``_report`` is that
        dispatch's, read a call later (``_tick_phases``)."""
        with self._span("batcher.dispatch"):
            if self._tick_fn is None:
                self._tick_fn = self._jit_ticks(self._tick_body())
            self._enqueuing()
            st, self._report = self._tick_fn(self.gen.params, st,
                                             self._aids)
            # the one device->host copy of the dispatch, issued before
            # any wait so that it rides behind the tick
            self._report.copy_to_host_async()
            return st


def parse_paged_block(value):
    """The ``serve.paged_block`` grammar, shared by the engine and the
    CLI: ``0``/``''``/``None``/``"off"`` → dense slot pool; a positive
    int → paged KV with that pool block; ``"auto"``/``-1`` → paged KV
    with the block resolved at admission through config > the kernel
    autotuner > default (``PagedContinuousBatcher(block=None)``, see
    ops.pallas.paged.preferred_pool_block).  Returns
    ``(paged, block_or_None)``."""
    if value in (None, "", 0, "0", False, "off"):
        return False, None
    if value in ("auto", -1, "-1"):
        return True, None
    n = int(value)
    if n <= 0:
        return False, None
    return True, n


class PagedContinuousBatcher(ContinuousBatcher):
    """Paged-KV continuous batching: slot caches live in a SHARED block
    pool addressed through per-slot block tables, so KV memory scales
    with the pool budget (sum of active request lengths, rounded up to
    blocks) instead of ``slots x max_len`` — the vLLM block-table idea
    (Kwon et al. 2023) recast for XLA's static shapes.

    Layout: every dense cache leaf [B, H, T, *] becomes a pool leaf
    [P, H, block, *] plus one shared int32 table [B, T/block]; block 0
    is a reserved dummy all unallocated table entries point at.  A
    request's block count is KNOWN at admission (prompt + max_new), so
    allocation is a host-side free-list pop at admit and a push at
    completion — no in-decode growth, and ADMISSION BACKPRESSURES on
    pool exhaustion exactly like on slot exhaustion (a queued request
    waits until both a slot and enough blocks free up).

    THREE KINDS OF STATE, ONE BATCHER.  A block declares what it keeps
    a token, how far back that is read, and what it keeps a slot
    (``TransformerBlock.cache_leaves`` / ``cache_span`` /
    ``state_leaves``).  A layer of FIXED-SIZE state (a retention
    layer's float32 S and z) lies SLOT-MAJOR in the pool's list, leaves
    ``[slots, ...]``: no table entry, nothing to claim or free — a slot
    IS its state; admission writes the staging row's state into the
    slot and release returns nothing.  A model none of whose layers
    keeps per-token state builds no pool (``pool_blocks`` 0,
    ``_blocks_needed`` 0, admission a matter of slots; ``pool_tokens``
    given for it is an error that says so).  Per-token layers declare
    how far back they are read: the whole context, or a sliding
    ``window``.  Layers of one span form a GROUP
    with its own pool leaves, table and free list.  The whole-context
    group is the layout above (``pool_tokens`` is ITS budget).  A
    window group (``LMGenerator._ring_spans``) is a RING: a slot's
    table has ``ring = ceil((window + prefill_segment) / block) + 1``
    entries, position p lives in entry ``(p // block) mod ring``, so a
    window layer never holds more than the ring, whatever the context's
    length, and nothing is copied as the window slides; its pool has
    ``slots x ring`` blocks (what the slots can ever hold: admission
    never waits for it), a request claims ``min(ring, its blocks)`` of
    them at admission and returns them at completion.  The staging row
    of a segmented admission holds the ring for such a layer, not
    ``max_len`` positions.  A model with window layers admits every
    prompt longer than a ring in passes (``prefill_segment``, by
    default the window).  A model whose layers are all of one span
    builds exactly the pool above.

    The tick shares the dense batcher's decode core (sampling/
    forcing/freeze logic — _make_core): attention reads the pool
    THROUGH the block table inside a scalar-prefetch Pallas kernel
    (ops.pallas.paged), and each layer writes its new k/v straight
    into its pool block — no dense re-materialization at all, and
    reads stop at each row's own length instead of max_len.
    QuantCache pools run the kernel's quantized variant.  It differs
    from the dense batcher only at the last-ulp level (online softmax
    + pool-dtype MXU inputs, same as flash vs naive).  What cannot be
    served is an error at construction: ``prefix_cache`` with window
    layers (a shared block can serve the whole-context group only: a
    ring's entries are overwritten as the window slides) or with state
    layers (a prefix's state would have to be snapshotted), and a pool
    block under Mosaic's sublane minimum wherever Mosaic compiles the
    kernel (interpret mode, off the TPU, takes any block).

        cb = PagedContinuousBatcher(gen, slots=8, block=16,
                                    pool_tokens=512)
    """

    #: read by benchmarks/kinds/serve_closed.py:71, serve_closed_sparse.py:51,
    #: compile_check.py:94: a `benchmark` issue removes the reads, then this
    fused = True

    def __init__(self, gen, slots=8, ticks_per_dispatch=1,
                 chunked_prefill=True, block=None, pool_tokens=None,
                 prefix_cache=False, speculative_k=0,
                 prefill_segment=0, prefill_tick_budget=0):
        if int(speculative_k):
            raise ValueError(
                "speculative ticks are dense-pool only (the chunk "
                "verify would write draft K/V through the block "
                "table) — use ContinuousBatcher(speculative_k=...)")
        L = gen.max_len
        ring_spans = gen._ring_spans()
        #: the layers whose state is per token and paged (the pool's
        #: whole-context and ring groups); the others' is fixed-size a
        #: slot and lies SLOT-MAJOR in the pool's list — no table entry,
        #: nothing to claim or free: a slot IS its state
        self._paged_layers = [i for i, layer in enumerate(gen._blocks)
                              if layer.cache_leaves()]
        if gen._stateful and prefix_cache:
            raise ValueError(
                "prefix_cache cannot serve this model: it has layers "
                "with fixed-size state a slot, and a shared prefix's "
                "state would have to be snapshotted at the prefix's end "
                "— blocks of keys and values can be shared, a state "
                "that every later token rewrites cannot")
        if not self._paged_layers and pool_tokens:
            raise ValueError(
                "pool_tokens means nothing for this model: none of its "
                "layers keeps per-token state, so there is no pool — "
                "admission waits for slots only")
        if ring_spans and prefix_cache:
            raise ValueError(
                "prefix_cache cannot serve this model: it has sliding-"
                "window layers, whose blocks are a ring that is "
                "overwritten as the window slides — a shared block can "
                "serve whole-context layers only")
        # shapes WITHOUT allocating the dense caches (eval_shape): the
        # whole point of paging is that dense slots x max_len may not
        # fit, so construction must never spike to dense + pool; ONE
        # abstract trace serves both the auto-block probe below and
        # the pool layout
        cache_shapes = self._cache_shapes = jax.eval_shape(
            lambda: gen._init_caches(slots, gen._model_dtype()))
        if block is None:
            # unpinned pool block: config > tuned paged.decode winner >
            # 16 (ops.pallas.paged.preferred_pool_block) — the pool
            # layout is THE launch geometry of the fused decode kernel,
            # and admission is the only point it can be chosen
            from veles_tpu.ops.pallas import paged as _paged
            block = 16
            if self._paged_layers:
                first = self._paged_layers[0]
                leaf = jax.tree_util.tree_leaves(cache_shapes[first])[0]
                hkv, hd = leaf.shape[1], leaf.shape[-1]
                g = max(1, int(gen._blocks[first].n_heads) // int(hkv))
                block = _paged.preferred_pool_block(hd, g, leaf.dtype)
            # a tuned block must still divide max_len; config/explicit
            # blocks keep the hard error below instead
            if L % int(block):
                block = 16
        if L % int(block):
            raise ValueError("max_len %d %% block %d != 0"
                             % (L, int(block)))
        self.block = int(block)
        self.max_blocks = L // self.block
        # a model with no per-token layer has no pool: no blocks to
        # claim (``_blocks_needed`` 0), admission a matter of slots
        self.pool_blocks = max(
            1, int(pool_tokens or slots * L) // self.block) \
            if self._paged_layers else 0
        self._free = list(range(1, 1 + self.pool_blocks))
        self._slot_blocks = {}               # slot -> [block ids]
        # the window groups: a prompt longer than a ring is admitted in
        # passes (of the window, where the caller set no segment), and a
        # ring holds the window and the longest pass (a pass length is a
        # power of two) and a block for the ends that lie inside blocks
        if ring_spans and not int(prefill_segment or 0):
            prefill_segment = ring_spans[0]
        longest_pass = gen._bucket(int(prefill_segment or 0), L)
        #: blocks a slot's ring holds, a window group
        self.ring_blocks = tuple(
            min(self.max_blocks,
                -(-(w + longest_pass) // self.block) + 1)
            for w in ring_spans)
        self._ring_tokens = tuple(n * self.block for n in self.ring_blocks)
        self._ring_free = [list(range(1, 1 + slots * n))
                           for n in self.ring_blocks]
        self._slot_ring_blocks = {}          # slot -> [[block ids] a ring]
        #: which ring a block's leaves live in (None: the whole-context
        #: group), in the order of the pool's layers
        self._ring_idx = [gen._ring_of(layer) for layer in gen._blocks]
        #: prefix caching (copy-on-write block sharing): concurrent
        #: requests whose prompts share a prefix share the pool blocks
        #: that hold it — the system-prompt serving case pays for the
        #: prefix ONCE in KV memory.  Sharing is CORRECT because a
        #: block's K/V is a deterministic function of (params, token
        #: prefix, absolute positions): only blocks fully covered by
        #: the prompt AND fully written at admission (chunked prefill
        #: ran) are registered, later sharers skip the admit scatter
        #: for matched blocks (diverted to the dummy block) so an
        #: in-flight sharer's K/V is never rewritten with anything but
        #: identical bytes, and generation never writes into a
        #: registered block (those end before the first generated
        #: position).  Blocks free when their last owner releases.
        self.prefix_cache = bool(prefix_cache)
        self._prefix_reg = {}                # token-prefix -> block id
        self._prefix_ref = {}                # block id -> owner count
        self._block_key = {}                 # block id -> its reg key
        self._resume_gather_fn = None        # jitted row gather (lazy)
        # a pool block is the kernel's K/V tile (32 rows at least for
        # an int8 pool)
        from veles_tpu.ops import pallas as _pallas
        state_ops = [layer.state_ops() for layer in gen._blocks
                     if layer.state_leaves()]
        self._skips_idle_rows = bool(state_ops) and all(
            ops.rows_skipped() for ops in state_ops)
        pool_dtype = jax.tree_util.tree_leaves(
            [cache_shapes[i] for i in self._paged_layers[:1]]
            or [jax.ShapeDtypeStruct((), jnp.float32)])[0].dtype
        sublane_min = _pallas.mosaic_sublane_min(pool_dtype)
        if self._paged_layers and not _pallas.autodetect_interpret(None) \
                and self.block < sublane_min:
            raise ValueError(
                "PagedContinuousBatcher cannot compile its decode kernel "
                "on the TPU: pool block %d is below Mosaic's %d-row "
                "sublane minimum for a %s pool"
                % (self.block, sublane_min, pool_dtype))
        super(PagedContinuousBatcher, self).__init__(
            gen, slots=slots, ticks_per_dispatch=ticks_per_dispatch,
            chunked_prefill=chunked_prefill,
            prefill_segment=prefill_segment,
            prefill_tick_budget=prefill_tick_budget)

    def _group_blocks(self, layer):
        """(pool blocks, table entries a slot) of the group that layer
        ``layer`` of the pool belongs to."""
        ring = self._ring_idx[layer]
        if ring is None:
            return self.pool_blocks, self.max_blocks
        return self.slots * self.ring_blocks[ring], self.ring_blocks[ring]

    def _init_slot_caches(self):
        def to_pool(blocks):
            # [B, H, T, *] -> [1 + P, H, block, *]; block 0 = dummy
            return lambda leaf: jnp.zeros(
                (1 + blocks, leaf.shape[1], self.block) + leaf.shape[3:],
                leaf.dtype)

        # zero-filled pool is safe for every leaf kind: QuantCache
        # scales for unwritten positions are never read (decode writes
        # before use, _init_caches' own invariant), and the dummy
        # block 0 is never read at all
        def slot_major(leaf):
            return jnp.zeros(leaf.shape, leaf.dtype)

        return ([jax.tree_util.tree_map(
                    to_pool(self._group_blocks(i)[0])
                    if i in self._paged_layers else slot_major, leaves)
                 for i, leaves in enumerate(self._cache_shapes)],
                jnp.zeros((self.slots, self.max_blocks), jnp.int32),
                *[jnp.zeros((self.slots, n), jnp.int32)
                  for n in self.ring_blocks])

    def _fresh_row(self):
        return self.gen._init_caches(1, self.gen._model_dtype(),
                                     self._ring_tokens)

    def _prefill_row(self, plen, max_new):
        # the rings are read by position, never by rounding a prompt
        # down: the chunk rounds UP as for a linear cache, and fits the
        # shortest ring (_will_segment stages what would not)
        tp = self.gen._bucket(plen, self.gen.max_len)
        return (self.gen._prefill_fn(1, tp, self._ring_tokens), tp,
                plen - 1)

    def _will_segment(self, plen):
        """Whether admission STAGES this prompt: its prefill work
        exceeds one segment — or, with window groups, its whole-prompt
        chunk would not fit the shortest ring."""
        if not self._will_chunk(plen):
            return False
        if self.prefill_segment > 0 and plen - 1 > self.prefill_segment:
            return True
        return bool(self._ring_tokens) and self.gen._bucket(
            plen, self.gen.max_len) > min(self._ring_tokens)

    # ------------------------------------------------------------ hooks
    def _blocks_needed(self, plen, max_new):
        if not self._paged_layers:
            return 0
        total = plen + max_new
        return -(-total // self.block)

    def submit(self, prompt, max_new, temperature=0.0, seed=0,
               adapter=0):
        """Reject a request larger than the ENTIRE pool up front — it
        could never be admitted, and a forever-queued request would
        deadlock run_all()/the serving engine."""
        nb = self._blocks_needed(len(prompt), int(max_new))
        if nb > self.pool_blocks:
            raise ValueError(
                "request needs %d KV blocks (prompt %d + max_new %d, "
                "block %d) but the pool only has %d — raise "
                "pool_tokens or shorten the request"
                % (nb, len(prompt), int(max_new), self.block,
                   self.pool_blocks))
        return super(PagedContinuousBatcher, self).submit(
            prompt, max_new, temperature=temperature, seed=seed,
            adapter=adapter)

    def _shareable_blocks(self, plen):
        """Blocks of an admitted request that decode NEVER writes:
        chunked-prefill admission starts ticking at pos0 = plen - 1
        (the last prompt token re-enters the step), so only blocks
        strictly before the one holding position plen - 1 are safe to
        share — on BOTH sides (registration by the first owner, and
        matching by later sharers, whose own writes start at their own
        plen - 1).  The tick-by-tick admission path writes every
        position from 0 and can share nothing."""
        if not self._will_chunk(plen):
            return 0
        return (plen - 1) // self.block

    def _match_prefix(self, prompt, adapter=0):
        """Longest run of registered blocks covering this prompt's
        prefix, from block 0 — the block ids a new sharer reuses.
        Keys chain per block — (parent block id, adapter id, that
        block's own tokens) — so matching is one O(plen) walk and
        registry memory is O(plen), not O(plen^2) full-prefix tuples.
        The adapter id is part of every link: adapters change the
        prefix's K/V, so sharing is only valid within one adapter."""
        if not self.prefix_cache:
            return []
        out, parent = [], 0
        for i in range(self._shareable_blocks(len(prompt))):
            blk = self._prefix_reg.get(
                (parent, int(adapter),
                 tuple(prompt[i * self.block:(i + 1) * self.block])))
            if blk is None:
                break
            out.append(blk)
            parent = blk
        return out

    def _can_admit(self):
        if not self._queue or None not in self._slot_req:
            return False
        _, prompt, max_new, _, _, adapter = self._queue[0]
        need = self._blocks_needed(len(prompt), max_new) \
            - len(self._match_prefix(prompt, adapter))
        return need <= len(self._free)

    def free_blocks(self):
        """Unallocated blocks of the whole-context group — the serving
        plane's memory gauge (``pool_tokens`` is that group's)."""
        return len(self._free)

    def blocks_in_use(self):
        """``(whole-context blocks, window-ring blocks)`` claimed by
        the requests in the slots: the two gauges of the pool's two
        kinds of state (the second 0 without window layers)."""
        return (self.pool_blocks - len(self._free),
                sum(self.slots * n - len(free) for n, free
                    in zip(self.ring_blocks, self._ring_free)))

    def prefix_stats(self):
        """(registered shared blocks, total owner refs) — the prefix-
        cache gauge; refs > blocks means live sharing.  Public
        accessor: the engine reads gauges only through methods."""
        return len(self._prefix_ref), sum(self._prefix_ref.values())

    def _kv_pages(self, keys):
        # the kernel's trip count a row: its written position (the last
        # of the row's keys) // block + 1
        return int(((keys - 1) // self.block + 1).sum())

    def _release_slot(self, b):
        super(PagedContinuousBatcher, self)._release_slot(b)
        for blk in self._slot_blocks.pop(b, ()):
            if blk in self._prefix_ref:
                self._prefix_ref[blk] -= 1
                if self._prefix_ref[blk] == 0:
                    del self._prefix_ref[blk]
                    del self._prefix_reg[self._block_key.pop(blk)]
                    self._free.append(blk)
            else:
                self._free.append(blk)
        for free, ids in zip(self._ring_free,
                             self._slot_ring_blocks.pop(b, ())):
            free.extend(ids)
        # enqueued BEHIND the tick already in flight, which still walks
        # the row through its old table (a frozen row writes its final
        # position once more, into a block it owned, never a shared
        # one: ``_shareable_blocks``); a new owner's admission scatter
        # is enqueued behind this in turn — device order is program
        # order
        self._caches = (self._pool, self._tables.at[b].set(0),
                        *[t.at[b].set(0) for t in self._rings])

    def reset_pool(self):
        """Fault reset, paged flavor: also rebuild the block pool, the
        tables, the free list, and the prefix-cache registries —
        every block returns to the free list (``cancel``/release paths
        already keep per-request accounting exact; this is the big
        hammer for a corrupted-pool fault)."""
        ContinuousBatcher.reset_pool(self)
        self._free = list(range(1, 1 + self.pool_blocks))
        self._slot_blocks = {}
        self._ring_free = [list(range(1, 1 + self.slots * n))
                           for n in self.ring_blocks]
        self._slot_ring_blocks = {}
        self._prefix_reg = {}
        self._prefix_ref = {}
        self._block_key = {}

    def _layer_caches(self):
        return self._pool

    # the cache state is ``(pool, tables, *ring tables)``
    _pool = property(lambda self: self._caches[0])
    _tables = property(lambda self: self._caches[1])
    _rings = property(lambda self: self._caches[2:])

    # -------------------------------------------------------- admission
    def _claim_blocks(self, b, prompt, max_new, adapter,
                      register=True):
        """Allocate slot ``b``'s KV blocks (reusing matched prefix
        blocks, ref-counted; of each window group ``min(ring, the
        request's blocks)``, which never runs out) and return
        ``(matched, will_chunk, table_row, srow, ring_rows)``.
        ``register=False`` defers prefix-cache
        REGISTRATION: a staged (segmented) admission's new blocks hold
        no K/V until the finish scatter runs, so they must not be
        matchable by another admission in between —
        _register_staged_blocks publishes them at finish instead."""
        plen = len(prompt)
        nb = self._blocks_needed(plen, max_new)
        will_chunk = self._will_chunk(plen)
        matched = self._match_prefix(prompt, adapter)
        # registerable = blocks the chunk prefill writes COMPLETELY at
        # admit and that decode never touches (_shareable_blocks); the
        # tick-by-tick path fills blocks progressively — a later
        # sharer could attend positions nobody has written
        registerable = self._shareable_blocks(plen) if will_chunk \
            else 0
        ids, scatter_row, parent = [], [], 0
        for i in range(nb):
            if i < len(matched):
                blk = matched[i]
                self._prefix_ref[blk] += 1
                # skip the admit scatter for matched blocks (divert to
                # the dummy block): they already hold the prefix K/V,
                # and a fresh-init scatter would zero them under an
                # in-flight sharer
                scatter_row.append(0)
            else:
                blk = self._free.pop()
                if register and self.prefix_cache \
                        and i < registerable:
                    key = (parent, int(adapter), tuple(
                        prompt[i * self.block:(i + 1) * self.block]))
                    self._prefix_reg[key] = blk
                    self._prefix_ref[blk] = 1
                    self._block_key[blk] = key
                scatter_row.append(blk)
            parent = blk
            ids.append(blk)
        self._slot_blocks[b] = ids
        table_row = np.zeros((self.max_blocks,), np.int32)
        table_row[:nb] = ids
        srow = np.zeros((self.max_blocks,), np.int32)
        srow[:nb] = scatter_row
        ring_rows, claimed = [], []
        for ring, free in zip(self.ring_blocks, self._ring_free):
            claimed.append([free.pop() for _ in range(min(ring, nb))])
            row = np.zeros((ring,), np.int32)
            row[:len(claimed[-1])] = claimed[-1]
            ring_rows.append(row)
        self._slot_ring_blocks[b] = claimed
        self._counts["admit_blocks"] += nb - len(matched) + sum(
            len(ids) for ids in claimed)
        return matched, will_chunk, table_row, srow, tuple(ring_rows)

    def _register_staged_blocks(self, prompt, adapter, ids,
                                registerable, matched):
        """Publish a finished staged admission's shareable blocks in
        the prefix registry (deferred from _claim_blocks: their K/V
        exists only after the finish scatter).  A key another request
        registered meanwhile keeps ITS block — ours stays a private
        allocation and frees normally on release."""
        if not self.prefix_cache:
            return
        parent = 0
        for i, blk in enumerate(ids):
            if i >= registerable:
                break
            if i < len(matched):
                parent = blk
                continue
            key = (parent, int(adapter), tuple(
                prompt[i * self.block:(i + 1) * self.block]))
            if key not in self._prefix_reg \
                    and blk not in self._prefix_ref:
                self._prefix_reg[key] = blk
                self._prefix_ref[blk] = 1
                self._block_key[blk] = key
            parent = blk

    def _staged_setup(self, b, prompt, plen, max_new, adapter):
        """Paged staging: claim the blocks now (admission
        backpressure accounting stays exact — _can_admit already
        checked them against the free list) and start the cache row
        from the matched prefix when there is one."""
        matched, will_chunk, table_row, srow, rrows = self._claim_blocks(
            b, prompt, max_new, adapter, register=False)
        extras = {"trow": table_row, "srow": srow, "rrows": rrows,
                  "matched": matched,
                  "registerable": (self._shareable_blocks(plen)
                                   if will_chunk else 0)}
        caches, cursor, _ = super(
            PagedContinuousBatcher, self)._staged_setup(
                b, prompt, plen, max_new, adapter)
        if matched:
            # resume from the shared prefix blocks: gather this row's
            # table view (real K/V for [0, start), dummy elsewhere) —
            # when its first pass runs; the shared blocks cannot change
            # while this request holds them
            def caches():
                return self._gather_row_view(table_row, rrows)
            cursor = len(matched) * self.block
        return caches, cursor, extras

    def _finish_staged(self, b, rec):
        self._register_staged_blocks(
            rec["prompt"], rec["adapter"], self._slot_blocks.get(b, ()),
            rec["registerable"], rec["matched"])
        super(PagedContinuousBatcher, self)._finish_staged(b, rec)

    def _admit_args(self, b, rec):
        return super(PagedContinuousBatcher, self)._admit_args(b, rec) \
            + (jnp.asarray(rec["trow"]), jnp.asarray(rec["srow"]),
               tuple(jnp.asarray(row) for row in rec["rrows"]))

    def _admission_row(self, b, rec):
        matched, will_chunk, rec["trow"], rec["srow"], rec["rrows"] = \
            self._claim_blocks(b, rec["prompt"], rec["max_new"],
                               rec["adapter"])
        if matched and will_chunk:
            # prefix-cache COMPUTE skip: the matched blocks already
            # hold positions [0, start) — resume the chunk prefill
            # from there instead of re-running the whole prompt
            # forward (the dominant admission cost for long shared
            # system prompts).  The resume row gathers this row's
            # table view (real prefix + dummies), chunk-steps
            # [start, start+kb), and the admit scatter then stores
            # only the NEW blocks (srow already diverts matched ones).
            self._enqueuing()
            return self._resume_row(rec["prompt"], rec["plen"], matched,
                                    rec["trow"], rec["adapter"],
                                    rec["rrows"])
        return super(PagedContinuousBatcher, self)._admission_row(b, rec)

    def _admit_cache(self, cache, b, crow, trow, srow, rrows=()):
        # the table rows, and the prompt cache blocks scattered into
        # the pool, each layer's into its group's leaves through its
        # group's row (a ring layer's [1, H, ring x block, *] row is
        # the ring entry by entry).  Dummy table entries (0) scatter
        # into the dummy block — harmless, never read.  ``srow`` is
        # ``trow`` with prefix-shared blocks diverted to the dummy
        # block: their K/V already lives in the pool and must not be
        # rewritten under an in-flight sharer.
        pool, tables, *rings = cache
        bs = self.block
        tables = jax.lax.dynamic_update_slice(tables, trow[None], (b, 0))
        rings = [jax.lax.dynamic_update_slice(t, row[None], (b, 0))
                 for t, row in zip(rings, rrows)]

        def scatter(layer):
            if layer not in self._paged_layers:
                # fixed-size state: the staging row's IS the slot's
                return ContinuousBatcher._admit_cache(
                    self, pool[layer], b, crow[layer])
            ring = self._ring_idx[layer]
            rows = srow if ring is None else rrows[ring]
            entries = self._group_blocks(layer)[1]

            def one(pl, rw):
                blocks = jnp.moveaxis(
                    rw[0].reshape((rw.shape[1], entries, bs)
                                  + rw.shape[3:]), 1, 0)
                return pl.at[rows].set(blocks.astype(pl.dtype))

            return jax.tree_util.tree_map(one, pool[layer], crow[layer])

        return ([scatter(layer) for layer in range(len(pool))], tables,
                *rings)

    def _gather_row_view(self, table_row, ring_rows=()):
        """Gather ONE slot's table view from the pool into a dense
        [1, ...] cache row: real K/V for every allocated block, dummy-
        block content elsewhere (rewritten or masked before any read —
        the round-up-prefill argument); a ring layer's row is its ring,
        entry by entry.  Shared by the prefix-resume admission and
        segmented staging."""
        bs = self.block
        if self._resume_gather_fn is None:
            def row_view(pool, trow, rrows):
                def view(layer):
                    if layer not in self._paged_layers:
                        # reached with a shared prefix only, which a
                        # state layer refuses at construction
                        raise ValueError("no table view of a slot's "
                                         "fixed-size state")
                    ring = self._ring_idx[layer]
                    rows = trow if ring is None else rrows[ring]
                    entries = self._group_blocks(layer)[1]

                    def one(pl):
                        v = pl[rows]                 # [n, H, bs, *]
                        v = jnp.moveaxis(v, 1, 0)    # [H, n, bs, *]
                        return v.reshape(
                            (1, v.shape[0], entries * bs) + v.shape[3:])
                    return jax.tree_util.tree_map(one, pool[layer])
                return [view(layer) for layer in range(len(pool))]
            self._resume_gather_fn = jax.jit(row_view)
        return self._resume_gather_fn(
            self._pool, jnp.asarray(table_row),
            tuple(jnp.asarray(row) for row in ring_rows))

    def _resume_row(self, prompt, plen, matched, table_row, adapter,
                    ring_rows=()):
        """Build an admission cache row by RESUMING from the matched
        prefix blocks: gather this row's table view into a dense
        [1, ...] row (real K/V for positions [0, start), dummy-block
        content elsewhere — rewritten below or masked until decode
        overwrites it, the round-up-prefill argument), then chunk-step
        positions [start, start+kb) under the request's adapter.
        Returns (cache_row, plen - 1) — the same cursor the full
        chunk prefill hands over at."""
        gen = self.gen
        start = len(matched) * self.block
        kb = gen._bucket(plen - start, gen.max_len - start)
        caches = self._gather_row_view(table_row, ring_rows)
        chunk = np.zeros((kb,), np.int32)
        chunk[:min(plen - start, kb)] = prompt[start:start + kb]
        params = gen._graft_adapters(gen.params, jnp.int32(adapter))
        return gen._prefill_resume_fn(kb)(
            params, caches, jnp.asarray(chunk[None]),
            jnp.int32(start)), plen - 1

    # ------------------------------------------------------------- tick
    def _tick_body(self):
        gen = self.gen
        # a model whose blocks select keys or route to experts hands
        # out what they counted, on the device, beside the state
        counting = bool(self.ring_blocks) or any(
            layer.dropless or layer.indexer for layer in gen._blocks)

        def paged_step_all(params, cache_state, cur, pos, aids, active):
            pool, tables, *rings = cache_state
            counts = {}
            # vector-aid graft: gathered lora leaves carry a
            # leading [B] dim that _qkv_proj's matmul broadcasts
            logits, pool = gen._step_paged(
                gen._graft_adapters(params, aids), pool, tables,
                cur, pos, counts=counts if counting else None,
                rings=tuple(rings), active=active)
            return logits, (pool, tables, *rings), counts

        return self._make_core(step_all=paged_step_all)
