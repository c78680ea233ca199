"""Staged NN units (replaces the reference's per-unit kernel dispatch).

In the reference every forward/GD unit launched its own kernel per
iteration (AcceleratedUnit.execute_kernel, SURVEY.md §3.3).  Here
:class:`StagedTrainer` *stages* the whole forward → loss → backward →
update chain into two jitted functions (train step, eval step) built once
at initialize.  Per iteration the host moves only a [minibatch_size] index
vector to the device; metrics accumulate in device-resident per-class
accumulators, read back exactly once per class sweep by the Decision unit —
the hot loop never blocks on device→host sync.

Per-layer ``Forward`` units still exist as introspection/export handles
(weights live in the trainer's param pytree; they expose views), keeping the
reference's unit-graph UX without its dispatch cost."""

import time

import jax
import jax.numpy as jnp
import numpy as np

from veles_tpu import prng, telemetry
from veles_tpu.config import root
from veles_tpu.loader.base import CLASS_NAMES, TRAIN
from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.models import optimizer
from veles_tpu.ops import losses
from veles_tpu.units import Unit


class Forward(Unit):
    """Introspection handle for one layer (ref Znicz forward units).  Its
    run() is a no-op — compute happens inside the staged step."""

    def __init__(self, workflow, layer, trainer, **kwargs):
        kwargs.setdefault("name", layer.name)
        super(Forward, self).__init__(workflow, **kwargs)
        self.layer = layer
        self._trainer = trainer
        self.view_group = "WORKER"

    @property
    def weights(self):
        p = self._trainer.params.get(self.layer.name)
        return None if p is None else p.get("weights")

    @property
    def bias(self):
        p = self._trainer.params.get(self.layer.name)
        return None if p is None else p.get("bias")

    @property
    def output_shape(self):
        return self.layer.output_shape


class StagedTrainer(Unit):
    """Runs the staged train/eval step for the current minibatch.

    Demands (data links from the loader): ``minibatch_indices``,
    ``minibatch_valid``, ``minibatch_class``."""

    def __init__(self, workflow, layers, loss="softmax", gd_defaults=None,
                 mesh_config=None, dataset_placement="shard",
                 steps_per_dispatch=1, **kwargs):
        super(StagedTrainer, self).__init__(workflow, **kwargs)
        self.layers = layers
        self.loss = loss
        self.gd_defaults = dict(gd_defaults or {})   # caller's dict stays
        #: global gradient-norm clip applied to the WHOLE grad tree
        #: before the per-layer updates (gd_defaults["clip_norm"]; a
        #: workflow-level knob — per-layer clipping would change the
        #: norm's meaning)
        self.clip_norm = self.gd_defaults.pop("clip_norm", None)
        #: gradient accumulation (gd_defaults["grad_accum_steps"]): every
        #: step's gradient joins a running sum; one optimizer update per
        #: k microbatches with the mean — k× the effective batch without
        #: k× the activation memory.  Composes with steps_per_dispatch
        #: (the scan body carries the accumulator like any other state).
        self.grad_accum = int(self.gd_defaults.pop("grad_accum_steps", 1))
        if self.grad_accum < 1:
            raise ValueError("grad_accum_steps must be >= 1")
        #: Polyak/EMA weight averaging (gd_defaults["ema_decay"], e.g.
        #: 0.999): a decayed average of the params advances on every
        #: real update; ``ema_params`` serves/evaluates with it
        self.ema_decay = self.gd_defaults.pop("ema_decay", None)
        if self.ema_decay is not None and not 0.0 < self.ema_decay < 1.0:
            raise ValueError("ema_decay must be in (0, 1), got %r"
                             % (self.ema_decay,))
        #: fuse this many minibatch steps into ONE device dispatch
        #: (lax.scan inside the jitted sweep).  Amortizes host→device
        #: dispatch latency — the dominant cost for small models —
        #: exactly k× fewer dispatches; numerics are the same per-step
        #: ops in the same order.  Index-mode
        #: loaders only (data-carrying loaders stream host tensors, so
        #: the host must intervene every step anyway).
        self.steps_per_dispatch = int(steps_per_dispatch)
        if self.steps_per_dispatch < 1:
            raise ValueError("steps_per_dispatch must be >= 1")
        self._pending = []          # queued (idx, valid, step, lr) rows
        self._pending_cls = None
        #: parallel.MeshConfig or None (single device).  With a mesh, params
        #: shard over the model axis (tp) and the minibatch over the data
        #: axis (dp) — XLA inserts the gradient psum over ICI.
        self.mesh_config = mesh_config
        #: 'shard' (default): the HBM dataset rows shard over the data axis
        #: — each device holds 1/D of the dataset, the in-step gather rides
        #: a psum_scatter (one minibatch of ICI traffic).  'replicate':
        #: r1 behavior, every device holds a full copy (fastest when the
        #: dataset is small).
        if dataset_placement not in ("shard", "replicate"):
            raise ValueError("dataset_placement must be 'shard' or "
                             "'replicate', got %r" % (dataset_placement,))
        self.dataset_placement = dataset_placement
        self.demand("loader")
        self.params = {}
        self.velocity = {}
        self.class_stats = [None, None, None]  # device accumulators
        self._step_counter = 0
        #: multiplier on every layer's learning rate, set per epoch by an
        #: LRAdjuster unit (ref Znicz lr_adjust); traced, so changing it
        #: does NOT recompile the step
        self.lr_scale = 1.0
        self.train_only_classes = (TRAIN,)
        self.view_group = "TRAINER"
        #: the numeric-fault sentinel (services.sentinel): build-time
        #: probe knobs, the device-resident health accumulator carried
        #: through every train step, the traced replay skip list, and
        #: the HealthSentinel unit observing the sync point (set by
        #: StandardWorkflow wiring; None = probes report to nobody)
        from veles_tpu.services import sentinel as _sentinel
        self._sentinel_cfg = _sentinel.probe_config()
        self.sentinel = None
        self.health = None
        self._health_host = None
        self._health_committed = {}
        self._skip_steps = _sentinel.skip_steps_array(
            self._sentinel_cfg["force_skip_steps"],
            self._sentinel_cfg["max_skip_steps"])
        self._skip_dev = None
        #: step telemetry: per-class sweep accumulators
        #: {cls: [t0, steps]} — opened by the first staged step of a
        #: class sweep, closed (and emitted) at the read_class_stats
        #: sync point, so sweep wall time includes the device work the
        #: async dispatches deferred
        self._sweep_ = {}
        self._mem_watcher = None
        #: what the traced train step's LM head took: "fused" (one op
        #: with its loss), "xla" (the layer, then the loss), or None
        #: before a train step was traced (``_note_head``)
        self.head_loss = None

    # ------------------------------------------------------------ building
    def initialize(self, **kwargs):
        loader = self.loader
        sample_shape = (tuple(loader.sample_shape) if loader.carries_data
                        else tuple(loader.data.shape[1:]))
        shape = sample_shape
        rng = prng.get("weights")
        hypers = {}
        for i, layer in enumerate(self.layers):
            layer.name = "l%02d_%s" % (i, layer.type)
            shape = layer.setup(shape)
            if layer.has_params:
                self.params[layer.name] = jax.tree_util.tree_map(
                    jnp.asarray, layer.init_params(rng))
                hypers[layer.name] = optimizer.resolve_hyper(
                    layer.gd, self.gd_defaults, layer_type=layer.type)
                if int(layer.cfg.get("lora_rank", 0)) > 0:
                    # LoRA freeze is stop_gradient on the base leaves —
                    # but weight DECAY applies outside the gradient
                    # (adamw's decoupled w - lr*wd*w especially), so a
                    # configured weights_decay would silently shrink
                    # the "frozen" base matrices every step.  Adapted
                    # layers therefore decay nothing.
                    hypers[layer.name] = dict(
                        hypers[layer.name], weights_decay=0.0,
                        weights_decay_bias=0.0)
        self.velocity = optimizer.init_state(self.params,
                                             grad_accum=self.grad_accum,
                                             ema_decay=self.ema_decay,
                                             hypers=hypers)
        self._hypers = hypers
        # resolve weight-tying references now that layers are named:
        # tie_to may be a layer NAME or a layer TYPE (e.g. "embedding");
        # a bad reference must fail here, not as a KeyError mid-trace
        by_type = {}
        for layer in self.layers:
            by_type.setdefault(layer.type, layer.name)
        for layer in self.layers:
            tie = layer.cfg.get("tie_to")
            if not tie:
                continue
            if tie not in self.params:
                resolved = by_type.get(tie)
                if resolved is None or resolved not in self.params:
                    raise ValueError(
                        "%s: tie_to=%r matches no parameterized layer "
                        "(names: %s)" % (layer.name, tie,
                                         sorted(self.params)))
                layer.cfg["tie_to"] = resolved
                if hasattr(layer, "tie_to"):
                    layer.tie_to = resolved
        self.output_features = int(np.prod(shape))
        self._base_key = jax.random.key(
            int(prng.get("trainer")._seed))
        if self.mesh_config is not None:
            from veles_tpu.parallel import sharding
            mc = self.mesh_config
            # layers that build their own shard_map need the mesh: the
            # flash kernel over data/model, sequence-parallel attention
            # (impl=ring/ulysses), expert-parallel MoE, pipelined stages
            for layer in self.layers:
                if hasattr(type(layer), "mesh"):
                    layer.mesh = mc.mesh
            if loader.minibatch_size % mc.data_size:
                raise ValueError(
                    "minibatch_size %d not divisible by data axis %d"
                    % (loader.minibatch_size, mc.data_size))
            self._param_overrides = {
                layer.name: ov for layer in self.layers if layer.has_params
                for ov in [layer.param_partition_specs(
                    dict(mc.mesh.shape))] if ov is not None}
            self.params = sharding.shard_params(self.params, mc,
                                                self._param_overrides)
            self.velocity = sharding.shard_params(self.velocity, mc,
                                                  self._param_overrides)
        self.reset_epoch_stats()
        from veles_tpu.services import sentinel as _sentinel
        self.health = self._replicated(_sentinel.init_health())
        self._skip_dev = jnp.asarray(self._skip_steps)
        self._build_steps()

    def _replicated(self, tree):
        """Fresh accumulators enter the staged step the way it hands
        them back: replicated over the mesh (``_shard_pins``).  Left on
        the default device they are a different argument sharding, and
        every (fresh, carried) combination of stats and health would
        compile the whole step again."""
        if self.mesh_config is None:
            return tree
        from veles_tpu.parallel import sharding
        return sharding.replicate(tree, self.mesh_config)

    # ----------------------------------------------------- numeric fault
    def add_skip_steps(self, steps):
        """Arm the replay skip list (services.sentinel rung 2): these
        staged-step counters' updates are gated off inside the jitted
        step.  Values change without a recompile (the list's CAPACITY
        is the static shape); overflowing the capacity raises — a
        replay that cannot represent its skip set is not exact."""
        from veles_tpu.services import sentinel as _sentinel
        cap = self._sentinel_cfg["max_skip_steps"]
        merged = sorted(
            {int(s) for s in self._skip_steps if int(s) >= 0}
            | {int(s) for s in steps})
        if len(merged) > cap:
            raise ValueError(
                "skip list overflow: %d poisoned steps exceed "
                "root.common.sentinel.max_skip_steps=%d — the replay "
                "could not stay exact" % (len(merged), cap))
        self._skip_steps = _sentinel.skip_steps_array(merged, cap)
        self._skip_dev = jnp.asarray(self._skip_steps)

    def reset_health_marks(self):
        """Clear the per-incident first/last-bad-step marks (the
        sentinel calls this after latching an incident, so the NEXT
        sweep's marks identify freshly poisoned steps instead of
        re-reporting the all-time minimum).  Host-side leaf swap, no
        device sync; the counters stay cumulative."""
        if self.health is None:
            return
        from veles_tpu.services import sentinel as _sentinel
        self.health = dict(self.health, **self._replicated({
            "first_bad_step": jnp.full((), _sentinel.NO_BAD_STEP,
                                       jnp.int32),
            "last_bad_step": jnp.full((), -1, jnp.int32)}))

    def _chaos_poison(self, grads, step):
        """The numerics-chaos injection hooks
        (``root.common.chaos.nan_grads_step`` / ``nan_grads_from``,
        tools/numerics_chaos.py): poison the whole gradient tree with
        NaN at the configured staged step(s).  A build-time gate —
        identity (zero ops traced) when unarmed."""
        from veles_tpu.config import root as _root
        nan_step = _root.common.chaos.get("nan_grads_step", None)
        nan_from = _root.common.chaos.get("nan_grads_from", None)
        if nan_step is None and nan_from is None:
            return grads
        hit = jnp.zeros((), bool)
        if nan_step is not None:
            hit = hit | (step == jnp.int32(int(nan_step)))
        if nan_from is not None:
            hit = hit | (step >= jnp.int32(int(nan_from)))
        return jax.tree_util.tree_map(
            lambda g: jnp.where(hit, jnp.full_like(g, jnp.nan), g),
            grads)

    def _sentinel_gate(self, params, velocity, new_params, new_velocity,
                       health, loss, grads, step, skip_steps):
        """In-jit rung 1 (services.sentinel): run the health probes and
        select the pre-step params/velocity when the step is poisoned
        or policy-skipped — a ``where`` with a scalar predicate, so the
        applied branch is bit-exact either way.  Disabled sentinel
        passes everything through untouched (same traced signature, no
        extra ops)."""
        if not self._sentinel_cfg["enabled"]:
            return new_params, new_velocity, health
        from veles_tpu.services import sentinel as _sentinel
        health, ok = _sentinel.apply_probes(
            health, loss, grads, new_params, params, step, skip_steps,
            self._sentinel_cfg)

        def sel(new, old):
            return jax.tree_util.tree_map(
                lambda a, b: jnp.where(ok, a, b), new, old)

        return sel(new_params, params), sel(new_velocity, velocity), \
            health

    def health_verdict(self):
        """Commit-time health stamp for the snapshotter: ``"healthy"``
        when no anomaly landed since the previous verdict,
        ``"unhealthy:<kind>"`` otherwise (consumes the delta).  Reads
        the device accumulator directly — the commit path already
        gathers the whole model, one more scalar fetch is noise."""
        if self.health is None:
            return None
        from veles_tpu.services import sentinel as _sentinel
        h = jax.device_get(self.health)
        keys = _sentinel.ANOMALY_KINDS + ("anomalies",)
        deltas = {}
        for k in keys:
            cur = float(h.get(k, 0.0))
            deltas[k] = cur - self._health_committed.get(k, 0.0)
            self._health_committed[k] = cur
        if deltas.get("anomalies", 0) > 0:
            kind = _sentinel.dominant_kind(deltas) or "unknown"
            return "unhealthy:%s" % kind
        return "healthy"

    def _forward(self, params, x, train, key, depth=None):
        """The layers' forward; ``depth`` stops it before that layer."""
        for i, layer in enumerate(self.layers[:depth]):
            lkey = (jax.random.fold_in(key, i)
                    if (train and layer.needs_rng) else None)
            if getattr(layer, "needs_full_params", False):
                # weight tying (TiedLMHead): the layer reads another
                # layer's params; remat would checkpoint the whole tree
                # for no gain, so tied heads run un-remat'd
                x = layer.apply(params, x, train=train, key=lkey)
                continue
            if train and layer.cfg.get("remat"):
                # rematerialize this layer's activations in the backward
                # pass (jax.checkpoint) — memory for FLOPs, the standard
                # long-context trade.  Aux values (MoE router loss) must
                # cross the remat boundary as outputs, not side effects.
                #
                # remat=True recomputes EVERYTHING (max memory savings,
                # but the recompute FLOPs don't count toward MFU);
                # remat="dots" keeps matmul outputs and recomputes only
                # the cheap elementwise ops (jax dots_saveable policy) —
                # near-no-remat step time at a fraction of the activation
                # memory, usually the right default for MXU-bound
                # transformer training.
                policy = (jax.checkpoint_policies.dots_saveable
                          if layer.cfg.get("remat") == "dots" else None)

                def fn(p, xx, kk, layer=layer):
                    y = layer.apply(p, xx, train=True, key=kk)
                    return y, getattr(layer, "last_aux", None)
                # prevent_cse=False: we are always under jit (and often
                # inside the fused sweep's lax.scan), where the CSE
                # barriers the default inserts only cost fusion
                x, aux = jax.checkpoint(fn, prevent_cse=False,
                                        policy=policy)(
                    params.get(layer.name), x, lkey)
                if aux is not None:
                    layer.last_aux = aux
            else:
                x = layer.apply(params.get(layer.name), x, train=train,
                                key=lkey)
        return x

    def _loss_and_stats(self, params, data, labels, targets, idx, valid,
                        train, key):
        """Index mode: gather the minibatch from HBM-resident arrays
        (``_gather`` is the plain jnp.take on one device, or the
        psum_scatter collective gather when the dataset is row-sharded)."""
        tgt = (self._gather(targets, idx)
               if losses.get_loss(self.loss)[1] == "regression" else None)
        return self._loss_from_batch(
            params, self._gather(data, idx),
            self._gather(labels, idx), tgt, valid, train, key)

    def _fused_head(self, params):
        """``losses.HeadLogits`` (its ``h`` None, the head's input to
        come) where the last layer and the loss run as ONE op
        (``losses.lm_head_xent``), else None: the loss is ``"lm"`` and
        the last layer an LM head of a plain weight
        (``Layer.lm_head_weight``) that no mesh axis shards.  Decided at
        trace time from what the trainer can see; the other heads keep
        the layer and the registered loss."""
        if self.loss != "lm" or not self.layers:
            return None
        head = self.layers[-1]
        found = head.lm_head_weight(params)
        if found is None:
            return None
        owner, leaf, transposed = found
        w = params[owner][leaf]
        shard = None
        mc = self.mesh_config
        if mc is not None and mc.mesh.size > 1:
            from veles_tpu.parallel import sharding
            spec = sharding.param_shardings(
                {owner: {leaf: w}}, mc,
                getattr(self, "_param_overrides", None))[owner][leaf].spec
            axes = [a for entry in spec if entry is not None
                    for a in (entry if isinstance(entry, tuple)
                              else (entry,))]
            if any(mc.mesh.shape[a] > 1 for a in axes):
                return None
            # the kernel runs per device: rows over the data axis, all
            # of them on each device of a mesh without one
            shard = (mc.mesh, mc.data_axis if mc.data_size > 1 else None)
        return losses.HeadLogits(None, w, head.policy.compute, transposed,
                                 shard)

    def _note_head(self, fused):
        """What the traced train step's head took: the
        ``veles_train_head_fused`` gauge and the step record's
        ``head_loss``."""
        self.head_loss = "fused" if fused else "xla"
        telemetry.registry.gauge(
            "veles_train_head_fused",
            "1 when the traced train step runs the LM head and its loss "
            "as one op (ops.losses.lm_head_xent), 0 otherwise").set(
            1.0 if fused else 0.0)

    def _loss_from_batch(self, params, x, lbl, tgt, valid, train, key):
        head = self._fused_head(params)
        if train:
            self._note_head(head is not None)
        out = self._forward(params, x, train, key,
                            depth=-1 if head is not None else None)
        if head is not None:
            out = head._replace(h=out)
        # router auxiliary losses (MoE load balancing): layers stash the
        # traced value during _forward; read it back inside the same trace
        aux_total = 0.0
        for layer in self.layers:
            la = getattr(layer, "last_aux", None)
            if la is not None:
                aux_total = aux_total + float(
                    layer.cfg.get("aux_weight", 0.01)) * la
                layer.last_aux = None
        loss_fn, _ = losses.get_loss(self.loss)
        loss_sum, err_sum, n_valid, n_features = loss_fn(out, lbl, tgt,
                                                         valid)
        # optimized loss is per-element mean (keeps lr scale comparable
        # across output widths); stats carry the raw sum for epoch metrics
        denom = jnp.maximum(n_valid, 1.0) * n_features
        return loss_sum / denom + aux_total, {"loss": loss_sum,
                                              "n_errors": err_sum,
                                              "count": n_valid}

    def _build_steps(self):
        if self.loader.carries_data:
            self._build_steps_direct()
            return
        loader = self.loader
        labels = (loader.labels if loader.labels is not None
                  else jnp.zeros((loader.total_samples,), jnp.int32))
        targets = loader.targets
        if losses.get_loss(self.loss)[1] == "regression" and targets is None:
            targets = loader.data   # autoencoder: reconstruct the input
        hypers = self._hypers

        def train_step(params, velocity, acc, health, data, labels,
                       targets, idx, valid, step, lr_scale, skip_steps):
            key = jax.random.fold_in(self._base_key, step)

            def loss_fn(p):
                loss, stats = self._loss_and_stats(
                    p, data, labels, targets, idx, valid, True, key)
                return loss, stats

            (loss, stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads = self._chaos_poison(grads, step)
            new_params, new_velocity = optimizer.update(
                params, grads, velocity, hypers, lr_scale=lr_scale,
                clip_norm=self.clip_norm, grad_accum=self.grad_accum,
                ema_decay=self.ema_decay)
            params, velocity, health = self._sentinel_gate(
                params, velocity, new_params, new_velocity, health,
                loss, grads, step, skip_steps)
            acc = jax.tree_util.tree_map(jnp.add, acc, stats)
            return params, velocity, acc, health

        def eval_step(params, acc, data, labels, targets, idx, valid):
            _, stats = self._loss_and_stats(
                params, data, labels, targets, idx, valid, False,
                jax.random.key(0))
            return jax.tree_util.tree_map(jnp.add, acc, stats)

        self._jit_steps(train_step, eval_step)
        self._build_sweeps(train_step, eval_step)
        self._gather = FullBatchLoader.gather
        if self.mesh_config is not None:
            from veles_tpu.parallel import sharding
            mc = self.mesh_config
            if self.dataset_placement == "shard" and mc.data_size > 1:
                self._gather = sharding.make_sharded_gather(mc)
                place = lambda x: sharding.shard_dataset(np.asarray(x), mc)
            else:
                place = lambda x: sharding.replicate(x, mc)
            labels = place(labels)
            self._data_dev = place(loader.data)
            if targets is loader.data:
                targets = self._data_dev  # autoencoder: don't copy twice
            elif targets is not None:
                targets = place(targets)
        else:
            self._data_dev = loader.data
        self._labels_dev = labels
        self._targets_dev = (targets if targets is not None
                             else jnp.zeros((1,), jnp.float32))

    def _build_sweeps(self, train_step, eval_step):
        """k-step fused dispatch (steps_per_dispatch > 1, index mode):
        one jitted lax.scan advances k minibatches per host→device round
        trip.  The scan body IS train_step / eval_step — the exact
        functions the per-step path jits — so the two paths cannot
        diverge; partial groups (class change, epoch end) fall back to
        the per-step functions, so nothing ever recompiles on a ragged
        tail."""
        self._sweeps = None
        if self.steps_per_dispatch <= 1:
            return

        def train_sweep(params, velocity, acc, health, data, labels,
                        targets, idxs, valids, steps, lr_scales,
                        skip_steps):
            def body(carry, inp):
                idx, valid, step, lr_s = inp
                return train_step(*carry, data, labels, targets, idx,
                                  valid, step, lr_s, skip_steps), None

            (params, velocity, acc, health), _ = jax.lax.scan(
                body, (params, velocity, acc, health),
                (idxs, valids, steps, lr_scales))
            return params, velocity, acc, health

        def eval_sweep(params, acc, data, labels, targets, idxs, valids):
            def body(a, inp):
                idx, valid = inp
                return eval_step(params, a, data, labels, targets, idx,
                                 valid), None

            return jax.lax.scan(body, acc, (idxs, valids))[0]

        pins = self._shard_pins()
        if pins is None:
            self._sweeps = (
                jax.jit(train_sweep, donate_argnums=(0, 1, 2, 3)),
                jax.jit(eval_sweep, donate_argnums=(1,)))
            return
        p_sh, v_sh, acc_sh, health_sh = pins
        self._sweeps = (
            jax.jit(train_sweep, donate_argnums=(0, 1, 2, 3),
                    out_shardings=(p_sh, v_sh, acc_sh, health_sh)),
            jax.jit(eval_sweep, donate_argnums=(1,),
                    out_shardings=acc_sh))

    def _shard_pins(self):
        """(params, velocity, acc, health) output shardings under a
        mesh (params/velocity per the partition rules, stat and
        sentinel-health accumulators replicated); None on a single
        device."""
        if self.mesh_config is None:
            return None
        from veles_tpu.parallel import sharding
        mc = self.mesh_config
        repl = sharding.replicated_sharding(mc)
        overrides = getattr(self, "_param_overrides", None)
        from veles_tpu.services import sentinel as _sentinel
        health_struct = (self.health if self.health is not None
                         else _sentinel.init_health())
        return (sharding.param_shardings(self.params, mc, overrides),
                sharding.param_shardings(self.velocity, mc, overrides),
                jax.tree_util.tree_map(lambda _: repl,
                                       self._zero_stats()),
                jax.tree_util.tree_map(lambda _: repl, health_struct))

    def _jit_steps(self, train_step, eval_step):
        """jit the pair with donation; under a mesh, pin the output
        shardings — shared by the index and data-carrying builders (and
        the fused sweeps) so the paths cannot diverge."""
        pins = self._shard_pins()
        if pins is None:
            self._train_step = jax.jit(train_step,
                                       donate_argnums=(0, 1, 2, 3))
            self._eval_step = jax.jit(eval_step, donate_argnums=(1,))
            return
        p_sh, v_sh, acc_sh, health_sh = pins
        self._train_step = jax.jit(
            train_step, donate_argnums=(0, 1, 2, 3),
            out_shardings=(p_sh, v_sh, acc_sh, health_sh))
        self._eval_step = jax.jit(eval_step, donate_argnums=(1,),
                                  out_shardings=acc_sh)

    def _build_steps_direct(self):
        """Steps for data-carrying loaders (streaming/replay/host-fallback):
        the minibatch tensor arrives from the host each step.  Under a mesh
        the arriving batch shards over the data axis (host-streaming SPMD —
        lifts the r1 restriction); because every dispatch is async, the
        host-side production of batch t+1 naturally overlaps the device
        compute of step t (double buffering for free — nothing below blocks
        until Decision reads the epoch stats).  mse uses the loader's
        minibatch_targets when present, else reconstructs the input."""
        hypers = self._hypers

        def train_step(params, velocity, acc, health, x, lbl, tgt,
                       valid, step, lr_scale, skip_steps):
            key = jax.random.fold_in(self._base_key, step)

            def loss_fn(p):
                return self._loss_from_batch(p, x, lbl, tgt, valid, True,
                                             key)

            (loss, stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads = self._chaos_poison(grads, step)
            new_params, new_velocity = optimizer.update(
                params, grads, velocity, hypers, lr_scale=lr_scale,
                clip_norm=self.clip_norm, grad_accum=self.grad_accum,
                ema_decay=self.ema_decay)
            params, velocity, health = self._sentinel_gate(
                params, velocity, new_params, new_velocity, health,
                loss, grads, step, skip_steps)
            acc = jax.tree_util.tree_map(jnp.add, acc, stats)
            return params, velocity, acc, health

        def eval_step(params, acc, x, lbl, tgt, valid):
            _, stats = self._loss_from_batch(params, x, lbl, tgt, valid,
                                             False, jax.random.key(0))
            return jax.tree_util.tree_map(jnp.add, acc, stats)

        self._sweeps = None     # fused sweeps are index-mode only
        self._jit_steps(train_step, eval_step)

    def _direct_batch(self, loader):
        x = np.asarray(loader.minibatch_data)
        lbl = (np.asarray(loader.minibatch_labels)
               if getattr(loader, "minibatch_labels", None) is not None
               else np.zeros((x.shape[0],), np.int32))
        tgt = (np.asarray(loader.minibatch_targets)
               if getattr(loader, "minibatch_targets", None) is not None
               else None)   # None → reuse x's device copy (one transfer)
        if self.mesh_config is not None:
            from veles_tpu.parallel import sharding
            mc = self.mesh_config
            x_dev = sharding.shard_batch(x, mc)
            return (x_dev, sharding.shard_batch(lbl, mc),
                    x_dev if tgt is None else sharding.shard_batch(tgt, mc))
        x_dev = jnp.asarray(x)
        return (x_dev, jnp.asarray(lbl),
                x_dev if tgt is None else jnp.asarray(tgt))

    # ------------------------------------------------------------- hot loop
    def run(self):
        # free when no trace is active; under --profile each step shows up
        # as a named region in the xplane timeline (ref per-unit timing,
        # units.py:805-817 → SURVEY §5 "TPU equivalent: jax profiler")
        with jax.profiler.StepTraceAnnotation("veles_step",
                                              step_num=self._step_counter):
            self._run_step()
        if root.common.engine.get("sync_run"):
            # honest per-unit wall time: charge the device work to THIS
            # unit instead of the next host sync (ref --sync-run,
            # accelerated_units.py:186-193); queued sweep steps must
            # dispatch now or their device time would land on whichever
            # step finally flushes
            self.flush()
            jax.block_until_ready(self.class_stats)

    def _run_step(self):
        loader = self.loader
        self._note_step(loader.minibatch_class)
        if loader.carries_data:
            cls = loader.minibatch_class
            x, lbl, tgt = self._direct_batch(loader)
            if self.mesh_config is not None:
                from veles_tpu.parallel import sharding
                valid = sharding.shard_batch(
                    np.asarray(loader.minibatch_valid), self.mesh_config)
            else:
                valid = jnp.asarray(loader.minibatch_valid)
            if cls in self.train_only_classes:
                self._step_counter += 1
                (self.params, self.velocity, self.class_stats[cls],
                 self.health) = self._train_step(
                    self.params, self.velocity, self.class_stats[cls],
                    self.health, x, lbl, tgt, valid, self._step_counter,
                    jnp.float32(self.lr_scale), self._skip_dev)
            else:
                self.class_stats[cls] = self._eval_step(
                    self.params, self.class_stats[cls], x, lbl, tgt, valid)
            return
        cls = loader.minibatch_class
        if self._sweeps is not None:
            if self._pending and self._pending_cls != cls:
                self.flush()
            train = cls in self.train_only_classes
            if train:
                self._step_counter += 1
            self._pending_cls = cls
            self._pending.append((
                np.array(loader.minibatch_indices),
                np.array(loader.minibatch_valid, np.float32),
                self._step_counter, float(self.lr_scale)))
            if len(self._pending) >= self.steps_per_dispatch:
                self.flush()
            return
        if self.mesh_config is not None:
            from veles_tpu.parallel import sharding
            idx = sharding.shard_batch(
                jnp.asarray(loader.minibatch_indices), self.mesh_config)
            valid = sharding.shard_batch(
                jnp.asarray(loader.minibatch_valid), self.mesh_config)
        else:
            idx = jnp.asarray(loader.minibatch_indices)
            valid = jnp.asarray(loader.minibatch_valid)
        if cls in self.train_only_classes:
            self._step_counter += 1
            (self.params, self.velocity, self.class_stats[cls],
             self.health) = self._train_step(
                self.params, self.velocity, self.class_stats[cls],
                self.health, self._data_dev, self._labels_dev,
                self._targets_dev, idx, valid, self._step_counter,
                jnp.float32(self.lr_scale), self._skip_dev)
        else:
            self.class_stats[cls] = self._eval_step(
                self.params, self.class_stats[cls], self._data_dev,
                self._labels_dev, self._targets_dev, idx, valid)

    # ---------------------------------------------------------- fused sweep
    def _place_stack(self, x):
        """Device placement for a [k, B] stacked index/valid matrix: one
        transfer per flush instead of one per step."""
        if self.mesh_config is None:
            return jnp.asarray(x)
        from veles_tpu.parallel import sharding
        return sharding.shard_batch_stack(x, self.mesh_config)

    def flush(self):
        """Dispatch any queued minibatches (steps_per_dispatch > 1).  Full
        k-groups ride the fused sweep; the ragged tail (class change or
        epoch end) rides the per-step functions — both compiled once."""
        if not self._pending:
            return
        # the fused dispatch is its own device-trace span: in an xplane
        # capture the k-step scan shows up under the same name the host
        # telemetry uses
        ann = telemetry.trace_annotation()
        if ann is None:
            return self._flush_pending()
        with ann("trainer.dispatch:%s" % self.name):
            return self._flush_pending()

    def _flush_pending(self):
        cls = self._pending_cls
        pending, self._pending = self._pending, []
        self._pending_cls = None
        train = cls in self.train_only_classes
        train_sweep, eval_sweep = self._sweeps
        k = self.steps_per_dispatch
        i = 0
        while len(pending) - i >= k:
            group = pending[i:i + k]
            i += k
            idxs = self._place_stack(np.stack([g[0] for g in group]))
            valids = self._place_stack(np.stack([g[1] for g in group]))
            if train:
                steps = jnp.asarray([g[2] for g in group], jnp.int32)
                lrs = jnp.asarray([g[3] for g in group], jnp.float32)
                (self.params, self.velocity, self.class_stats[cls],
                 self.health) = train_sweep(
                    self.params, self.velocity, self.class_stats[cls],
                    self.health, self._data_dev, self._labels_dev,
                    self._targets_dev, idxs, valids, steps, lrs,
                    self._skip_dev)
            else:
                self.class_stats[cls] = eval_sweep(
                    self.params, self.class_stats[cls], self._data_dev,
                    self._labels_dev, self._targets_dev, idxs, valids)
        for idx, valid, step, lr in pending[i:]:
            if self.mesh_config is not None:
                from veles_tpu.parallel import sharding
                idx = sharding.shard_batch(jnp.asarray(idx),
                                           self.mesh_config)
                valid = sharding.shard_batch(jnp.asarray(valid),
                                             self.mesh_config)
            else:
                idx, valid = jnp.asarray(idx), jnp.asarray(valid)
            if train:
                (self.params, self.velocity, self.class_stats[cls],
                 self.health) = self._train_step(
                    self.params, self.velocity, self.class_stats[cls],
                    self.health, self._data_dev, self._labels_dev,
                    self._targets_dev, idx, valid, step,
                    jnp.float32(lr), self._skip_dev)
            else:
                self.class_stats[cls] = self._eval_step(
                    self.params, self.class_stats[cls], self._data_dev,
                    self._labels_dev, self._targets_dev, idx, valid)

    def stop(self):
        # a run stopped mid-sweep leaves an open accumulator whose t0
        # would poison the NEXT run's first sweep (wall time spanning
        # the idle gap → garbage examples/s and a spurious MFU
        # shortfall); Workflow.run calls stop() on every unit at run
        # end, so drop any un-emitted accumulator here
        self._sweep_.clear()

    # ------------------------------------------------------------- metrics
    def _note_step(self, cls):
        """Open/advance the class sweep accumulator (host-side only —
        no device sync; the wall clock closes at read_class_stats)."""
        sw = self._sweep_.get(cls)
        if sw is None:
            self._sweep_[cls] = sw = [time.perf_counter(), 0]
        sw[1] += 1

    def _emit_step_telemetry(self, cls, stats):
        """Close the class sweep at the read_class_stats sync point:
        step counters, loss/examples-per-second gauges, the JSONL step
        record, device-memory gauges, and (train classes) the
        predicted-vs-measured MFU check.  Never raises — telemetry must
        not kill the training loop it instruments."""
        sw = self._sweep_.pop(cls, None)
        if not sw or not sw[1]:
            return
        # the multi-host heartbeat runs FIRST, outside the fail-soft
        # guard below: sweep open/close is SPMD-lockstep on every host,
        # but the guarded telemetry body can fail on host-LOCAL state
        # (disk full, backend memory stats) — if that skipped the
        # heartbeat's allgather on one host only, every later collective
        # would be off by one and the pod would hang.  Only the
        # collective itself rides this path; its reporting (gauges,
        # desync dump) is exception-guarded inside multihost_check.
        telemetry.health.multihost_check(
            self._step_counter, time.perf_counter() - sw[0],
            registry=telemetry.registry)
        try:
            self._emit_step_telemetry_inner(cls, stats, sw)
        except Exception as e:   # noqa: BLE001 — observe, never abort
            if not self.__dict__.get("_telemetry_error_warned_"):
                self.__dict__["_telemetry_error_warned_"] = True
                self.warning("step telemetry failed (%s: %s) — "
                             "training continues, further telemetry "
                             "errors are silenced", type(e).__name__, e)

    def _emit_step_telemetry_inner(self, cls, stats, sw):
        wall = time.perf_counter() - sw[0]
        steps = sw[1]
        name = CLASS_NAMES[cls]
        examples = int(stats["count"])
        loss_mean = stats["loss"] / max(examples, 1)
        reg = telemetry.registry
        lbl = {"class": name}
        reg.counter("veles_steps_total", "staged steps dispatched",
                    ("class",)).inc(steps, **lbl)
        reg.counter("veles_examples_total", "examples processed",
                    ("class",)).inc(examples, **lbl)
        if wall > 0:
            reg.gauge("veles_examples_per_sec",
                      "examples/s over the last class sweep",
                      ("class",)).set(examples / wall, **lbl)
            reg.histogram("veles_step_wall_seconds",
                          "mean per-step wall time per sweep "
                          "(host dispatch + device, sync-point "
                          "amortized)", ("class",)).observe(
                wall / steps, **lbl)
        reg.gauge("veles_loss", "mean per-example loss of the last "
                  "class sweep", ("class",)).set(loss_mean, **lbl)
        reg.emit("step", steps=steps, examples=examples, wall_s=wall,
                 examples_per_sec=examples / wall if wall > 0 else 0.0,
                 step_ms=wall / steps * 1e3, loss=loss_mean,
                 loss_sum=stats["loss"], n_errors=stats["n_errors"],
                 head_loss=self.head_loss, **lbl)
        # black-box surface: the sweep is the staged loop's one honest
        # sync point, so this is where the flight record learns the
        # step counter and the watchdog learns the run is alive (the
        # spmd heartbeat allgather runs in _emit_step_telemetry, before
        # this fail-soft body)
        telemetry.flight.record(
            "step", step=self._step_counter, steps=steps,
            examples=examples, wall_s=wall, loss=loss_mean, **lbl)
        if wall > 0:
            # bank the sweep throughput in the performance ledger
            # (telemetry.ledger, fail-soft): per-class history the
            # regression sentinel bands — the train-class step_ms /
            # MFU rows ride the MFU check below
            telemetry.ledger.record_value(
                "sweep_examples_per_sec", examples / wall,
                workload="%s/%s" % (self.name, name), unit="ex/s",
                better="higher", source="trainer.sweep", steps=steps)
        telemetry.health.note_progress(step=self._step_counter)
        if self._health_host is not None:
            # sentinel health (services.sentinel), read off the SAME
            # device_get as the class stats — cumulative counters as
            # gauges (the anomaly/rollback counters live in the
            # sentinel unit; these are the raw in-jit probe tallies)
            reg.gauge("veles_sentinel_skipped_updates",
                      "cumulative staged updates zeroed by the in-jit "
                      "sentinel (anomaly skips)").set(
                float(self._health_host.get("skipped", 0.0)))
            reg.gauge("veles_sentinel_policy_skips",
                      "cumulative policy-skipped updates (replay skip "
                      "list / force_skip_steps)").set(
                float(self._health_host.get("policy_skips", 0.0)))
        # the live-array census is the one per-sweep cost that scales
        # with model size (O(arrays x shards) host walk): pay it only
        # when something consumes it — an open --metrics-out sink or a
        # started web-status /metrics scrape surface.  The MFU check
        # stays unconditional: its pricing is computed once and cached,
        # the per-sweep cost is a handful of float ops, and the
        # shortfall warning is a log surface that must work bare.
        if telemetry.collection_enabled():
            if self._mem_watcher is None:
                from veles_tpu.benchmark import Watcher
                self._mem_watcher = Watcher()
            self._mem_watcher.record(reg)
        if cls in self.train_only_classes:
            telemetry.mfu.check_step(self, steps, wall, registry=reg)

    def _zero_stats(self):
        return {"loss": jnp.zeros(()), "n_errors": jnp.zeros(()),
                "count": jnp.zeros(())}

    def reset_epoch_stats(self):
        self.class_stats = [self._replicated(self._zero_stats())
                            for _ in range(3)]

    def read_class_stats(self, cls):
        """Device→host sync — called once per class sweep by Decision.
        The sentinel's health accumulator rides the SAME device_get as
        the class stats: the probe results cost zero extra sync points
        (the PR 3 telemetry budget the numerics-chaos gate pins)."""
        self.flush()
        st, health = jax.device_get((self.class_stats[cls],
                                     self.health))
        self._health_host = health
        stats = {"loss": float(st["loss"]),
                 "n_errors": int(st["n_errors"]),
                 "count": int(st["count"])}
        if self.sentinel is not None and health is not None:
            # strike accounting is CONTROL, not telemetry — it runs
            # outside the fail-soft guard (the ladder acts at the
            # sentinel unit's own slot in the cycle, never mid-read)
            self.sentinel.observe_sweep(cls, stats, health)
        # the sweep's wall clock closes HERE, after the device_get that
        # drains every async dispatch — the only honest step-time sample
        # the staged hot loop offers without adding sync points
        self._emit_step_telemetry(cls, stats)
        return stats

    # ---------------------------------------------------------- inspection
    def lint_staging_spec(self):
        """Staging spec for the jit auditor (veles_tpu.analysis.staging):
        the jitted eval step traced over abstract ShapeDtypeStruct inputs
        — no device compute, no allocation.  None before initialize()
        has built the steps (the graph linter still runs construction-
        time), and None under a mesh (the pjit sharding constraints
        don't trace over bare abstract values)."""
        step = getattr(self, "_eval_step", None)
        if step is None or self.mesh_config is not None \
                or self.loader.carries_data:
            return None

        def abstract(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                               jnp.result_type(a)), tree)

        mb = self.loader.minibatch_size
        args = (abstract(self.params), abstract(self.class_stats[0]),
                abstract(self._data_dev), abstract(self._labels_dev),
                abstract(self._targets_dev),
                jax.ShapeDtypeStruct((mb,), jnp.int32),
                jax.ShapeDtypeStruct((mb,), jnp.float32))
        # the accumulator (argnum 1) is the step's carry: its output
        # avals must match or every scheduler iteration recompiles
        return {"fn": step, "args": args, "carry_argnums": (1,),
                "name": "%s.eval_step" % self.name}

    def lint_numerics_spec(self):
        """Numerics/determinism spec for the VN4xx/VR5xx auditor
        (veles_tpu.analysis.numerics_audit): the REAL jitted train step
        — the one with the grad, the loss reductions, and the per-step
        fold_in — over abstract ``ShapeDtypeStruct`` mirrors.  Under a
        mesh it reuses the sharding spec's mirrors (make_jaxpr accepts
        them unchanged); single-device it mirrors the step's true
        signature.  None before initialize() or for data-carrying
        loaders (their minibatch never lives in the staged state)."""
        step = getattr(self, "_train_step", None)
        if step is None or self.loader.carries_data:
            return None
        loss_fn, _ = losses.get_loss(self.loss)
        suppress = tuple(getattr(loss_fn, "numerics_suppress", ()))
        # the staged step fn is framework code — the user's host calls
        # (VR502's numpy.random scan) live in its callees: the loss
        # evaluator and any layer defined outside veles_tpu
        host_scan = [loss_fn]
        for layer in self.layers:
            mod = type(layer).__module__ or ""
            if not mod.startswith("veles_tpu"):
                host_scan.append(layer.apply)

        #: sentinel-health leaves that are nonnegative by construction
        #: (counters, the EWM variance, the +inf-seeded first-bad-step)
        _health_nonneg = frozenset(
            ("ewma_var", "obs", "first_bad_step", "anomalies",
             "skipped", "policy_skips", "nonfinite_loss",
             "nonfinite_grad", "update_explosion", "loss_spike"))

        def step_leaf_flags(args):
            # vouch for the counters the auditor cannot see: the step
            # arg (argnum 9) increments BEFORE dispatch (_run_step), so
            # it is >= 1 inside the step, the optimizer's step/micro
            # slots (velocity tree) only ever count up from 0 — that is
            # what proves adam's 1 - beta**t bias correction positive —
            # and the sentinel health accumulator (argnum 3) carries
            # nonnegative counters/variance
            flags, idx = {}, 0
            for ai, a in enumerate(args):
                for path, _leaf in \
                        jax.tree_util.tree_flatten_with_path(a)[0]:
                    key = (getattr(path[-1], "key", None)
                           if path else None)
                    if ai == 9:
                        flags[idx] = ("pos", "nonneg")
                    elif key in ("step", "micro"):
                        flags[idx] = ("nonneg",)
                    elif ai == 3 and key in _health_nonneg:
                        flags[idx] = ("nonneg",)
                    idx += 1
            return flags

        if self.mesh_config is not None:
            spec = self.lint_sharding_spec()
            if spec is None:
                return None
            return {"fn": spec["fn"], "args": spec["args"],
                    "suppress": suppress, "host_scan": tuple(host_scan),
                    "input_flags": step_leaf_flags(spec["args"]),
                    "name": "%s.train_step" % self.name}

        def abstract(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                               jnp.result_type(a)), tree)

        mb = self.loader.minibatch_size
        args = (abstract(self.params), abstract(self.velocity),
                abstract(self.class_stats[0]), abstract(self.health),
                abstract(self._data_dev), abstract(self._labels_dev),
                abstract(self._targets_dev),
                jax.ShapeDtypeStruct((mb,), jnp.int32),
                jax.ShapeDtypeStruct((mb,), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.float32),
                jax.ShapeDtypeStruct(self._skip_steps.shape, jnp.int32))
        return {"fn": step, "args": args, "suppress": suppress,
                "host_scan": tuple(host_scan),
                "input_flags": step_leaf_flags(args),
                "name": "%s.train_step" % self.name}

    def lint_sharding_spec(self):
        """Sharding/memory spec for the VS2xx/VM3xx auditor
        (veles_tpu.analysis.sharding_audit): the REAL jitted train step
        plus abstract ``ShapeDtypeStruct`` mirrors of its arguments,
        each carrying the argument's live NamedSharding — the auditor
        lowers and compiles for the mesh without touching data or
        dispatching anything.  None before initialize(), without a mesh
        (nothing to audit), or for data-carrying loaders (the minibatch
        arrives from the host each step, so there is no HBM-resident
        step state beyond the params the staging audit already
        covers)."""
        step = getattr(self, "_train_step", None)
        if step is None or self.mesh_config is None \
                or self.loader.carries_data:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        mc = self.mesh_config
        repl = NamedSharding(mc.mesh, P())
        batch_sh = (NamedSharding(mc.mesh, P(mc.data_axis))
                    if mc.data_axis in mc.mesh.shape else repl)

        memo = {}   # one mirror per PHYSICAL buffer: the autoencoder's
        # targets ARE its data, and VM300 must not count that twice

        def abstract(x):
            if id(x) in memo:
                return memo[id(x)]
            sh = getattr(x, "sharding", None)
            if not isinstance(sh, NamedSharding):
                sh = repl   # uncommitted single-device array: the step
                # receives it replicated over the mesh at dispatch time
            memo[id(x)] = jax.ShapeDtypeStruct(
                tuple(jnp.shape(x)), jnp.result_type(x), sharding=sh)
            return memo[id(x)]

        tree_abs = lambda t: jax.tree_util.tree_map(abstract, t)  # noqa: E731
        mb = self.loader.minibatch_size
        args = (tree_abs(self.params), tree_abs(self.velocity),
                tree_abs(self.class_stats[0]), tree_abs(self.health),
                tree_abs(self._data_dev), tree_abs(self._labels_dev),
                tree_abs(self._targets_dev),
                jax.ShapeDtypeStruct((mb,), jnp.int32,
                                     sharding=batch_sh),
                jax.ShapeDtypeStruct((mb,), jnp.float32,
                                     sharding=batch_sh),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
                jax.ShapeDtypeStruct((), jnp.float32, sharding=repl),
                jax.ShapeDtypeStruct(self._skip_steps.shape, jnp.int32,
                                     sharding=repl))
        # bytes one minibatch moves per step: mb gathered samples (+
        # labels + the f32 valid/int32 index vectors)
        sample_bytes = int(np.prod(self._data_dev.shape[1:])
                           * self._data_dev.dtype.itemsize)
        mb_bytes = mb * (sample_bytes + self._labels_dev.dtype.itemsize
                         + 8)
        return {"fn": step, "args": args,
                "mesh_config": mc,
                "donate_argnums": (0, 1, 2, 3),
                "carry_argnums": (0, 1, 2, 3),
                "params_argnums": (0,), "opt_argnums": (1,),
                "minibatch_bytes": int(mb_bytes),
                "name": "%s.train_step" % self.name}

    def lower_train_sweep(self):
        """The fused k-step train sweep (``steps_per_dispatch`` > 1)
        lowered over abstract mirrors of its live arguments — nothing
        compiles or runs.  ``.as_text()`` is its StableHLO;
        ``.compile()`` gives the partitioned HLO and the program's
        memory analysis, and the first real dispatch then finds that
        executable instead of compiling again (chip_smoke.py)."""
        from jax.sharding import NamedSharding

        def mirror(a):
            # only mesh placements are commitments; what sits on the
            # default device follows the others at dispatch
            sh = getattr(a, "sharding", None)
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=sh if isinstance(sh, NamedSharding) else None)

        k, mb = self.steps_per_dispatch, self.loader.minibatch_size
        args = (self.params, self.velocity, self.class_stats[TRAIN],
                self.health, self._data_dev, self._labels_dev,
                self._targets_dev,
                self._place_stack(np.zeros((k, mb), np.int32)),
                self._place_stack(np.zeros((k, mb), np.float32)),
                jnp.zeros((k,), jnp.int32), jnp.zeros((k,), jnp.float32),
                self._skip_dev)
        return self._sweeps[0].lower(
            *jax.tree_util.tree_map(mirror, args))

    def host_params(self):
        """Full parameter pytree on the host.  Multi-host safe: tensors
        sharded across processes (non-addressable shards) are gathered
        with a process_allgather collective — EVERY process must call
        this together (the snapshotter does; ref only-master-writes,
        snapshotter.py:160)."""
        self.flush()
        return self.host_tree(self.params)

    def host_velocity(self):
        self.flush()
        return self.host_tree(self.velocity)

    @staticmethod
    def host_tree(tree):
        def get(x):
            if isinstance(x, jax.Array) and not x.is_fully_addressable \
                    and not x.is_fully_replicated:
                from jax.experimental import multihost_utils
                return np.asarray(
                    multihost_utils.process_allgather(x, tiled=True))
            return np.asarray(jax.device_get(x))
        return jax.tree_util.tree_map(get, tree)

    def load_params(self, host_params, host_velocity=None):
        # queued steps would otherwise apply to the restored params
        self._pending, self._pending_cls = [], None
        self.params = jax.tree_util.tree_map(jnp.asarray, host_params)
        if host_velocity is not None:
            self.velocity = jax.tree_util.tree_map(jnp.asarray,
                                                   host_velocity)
            # reconcile accumulation state across config changes: a
            # snapshot from a grad_accum=1 run resumes into an
            # accumulating one with fresh (zero) accumulators, and vice
            # versa the stale accumulator is dropped — not a KeyError
            # mid-trace
            if self.grad_accum > 1 and "gacc" not in self.velocity:
                self.velocity["gacc"] = jax.tree_util.tree_map(
                    jnp.zeros_like, self.params)
                self.velocity["micro"] = jnp.zeros((), jnp.int32)
            elif self.grad_accum == 1:
                self.velocity.pop("gacc", None)
                self.velocity.pop("micro", None)
            # abstract (no allocation): only the slot SHAPES matter
            spec = jax.eval_shape(
                lambda: optimizer.init_state(self.params,
                                             hypers=self._hypers))

            def _shapes(t):
                return jax.tree_util.tree_map(lambda a: a.shape, t)

            if any(_shapes(self.velocity.get(s)) != _shapes(spec[s])
                   for s in ("slot1", "slot2")):
                # solver family changed since the snapshot (e.g.
                # adam -> adafactor): slot shapes are incompatible —
                # restart the moments (and the update count their bias
                # correction depends on) rather than crash mid-trace
                self.warning(
                    "restored optimizer state does not match the "
                    "configured solver's slot shapes — reinitializing "
                    "moments and step count")
                fresh = optimizer.init_state(self.params,
                                             hypers=self._hypers)
                for k in ("slot1", "slot2", "step"):
                    self.velocity[k] = fresh[k]
            if self.ema_decay and "ema" not in self.velocity:
                # fresh f32 average seeded from the restored params
                # (jnp.array copies — no aliasing with donated params)
                self.velocity["ema"] = jax.tree_util.tree_map(
                    lambda p: jnp.array(p, jnp.float32), self.params)
            elif not self.ema_decay:
                self.velocity.pop("ema", None)
        if self.mesh_config is not None:
            # re-establish the parallel placement initialize() set up
            from veles_tpu.parallel import sharding
            overrides = getattr(self, "_param_overrides", None)
            self.params = sharding.shard_params(self.params,
                                                self.mesh_config, overrides)
            self.velocity = sharding.shard_params(self.velocity,
                                                  self.mesh_config,
                                                  overrides)

    @property
    def ema_params(self):
        """The Polyak/EMA weight average (gd_defaults["ema_decay"]), or
        None when EMA tracking is off."""
        return self.velocity.get("ema")

    def serve_params(self, use_ema=False):
        """The params a serve/export path should read: the live tree, or
        the EMA average when asked (a loud error beats silently serving
        un-averaged weights the user thought were smoothed)."""
        if not use_ema:
            return self.params
        ema = self.ema_params
        if ema is None:
            raise ValueError(
                "use_ema requested but EMA tracking is off — train with "
                "gd_defaults={'ema_decay': 0.999}")
        return ema

    def forward_fn(self):
        """Jitted serve-time forward (softmax applied for classifiers)."""
        def fwd(params, x):
            out = self._forward(params, x, False, jax.random.key(0))
            if losses.get_loss(self.loss)[1] == "class":
                # every classification loss serves probabilities (the
                # ensemble vote and REST clients rely on it)
                out = jax.nn.softmax(out.astype(jnp.float32), axis=-1)
            return out
        return jax.jit(fwd)
