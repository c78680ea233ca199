#!/usr/bin/env python3
"""Compiles a training cell's fused sweep, or a serving cell's fused
tick, for a DESCRIBED TPU v5e in a sandbox that has none (on-chip-measurement guide, section 2.3) and
prints ``memory_analysis()``.  A compile that passes is not a run.

    JAX_PLATFORMS=cpu python3 benchmarks/compile_check.py \\
        --config benchmarks/configs/<c>.json \\
        --traffic benchmarks/traffic/<t>.json [--n-layer N ...]

With several ``--n-layer`` values it reports the deepest that fits."""

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_for_v5e(jitted, args, topology="v5e:2x2"):
    """Lower ``jitted`` on the shapes of ``args`` placed on one chip of a
    described topology, compile, and return what ``memory_analysis()``
    says."""
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology)
    chip = SingleDeviceSharding(topo.devices[0])

    def mirror(a):
        a = a if hasattr(a, "shape") else np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)

    # the program asks jax.default_backend() whether to interpret its
    # Pallas kernels; steered here, in the script, so that they lower as
    # the Mosaic custom calls the chip would run
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        lowered = jitted.lower(*jax.tree_util.tree_map(mirror, args))
    finally:
        jax.default_backend = real
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    return {
        "mosaic_custom_calls": compiled.as_text().count("tpu_custom_call"),
        "argument_bytes": int(mem.argument_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
    }


def compile_sweep(cfg, traffic):
    """The fused training sweep at a training cell's own shape."""
    import numpy as np
    from benchmarks import build
    from veles_tpu.loader.base import TRAIN

    k, batch = traffic["steps_per_dispatch"], traffic["batch"]
    rows = build.token_rows(cfg, batch * k, traffic["seq"], 0)
    wf = build.build_workflow(cfg, rows, batch, k, traffic["optimizer"],
                              traffic["remat"])
    tr = wf.trainer
    args = (tr.params, tr.velocity, tr.class_stats[TRAIN], tr.health,
            tr._data_dev, tr._labels_dev, tr._targets_dev,
            np.zeros((k, batch), np.int32), np.zeros((k, batch), np.float32),
            np.zeros((k,), np.int32), np.zeros((k,), np.float32),
            tr._skip_dev)
    return compile_for_v5e(tr._sweeps[0], args)


def compile_tick(cfg, tf):
    """The fused serving tick (``PagedContinuousBatcher._tick_body``
    through ``_jit_ticks``) at a closed-loop cell's own shape."""
    import jax.numpy as jnp
    from benchmarks import build
    from veles_tpu.models.generate import (LMGenerator,
                                           PagedContinuousBatcher)

    rows = build.token_rows(cfg, 1, tf["max_len"], 0)
    wf = build.build_workflow(cfg, rows, 1)
    gen = LMGenerator(wf.trainer, max_len=tf["max_len"],
                      cache_dtype=getattr(jnp, tf["cache_dtype"]))
    cb = PagedContinuousBatcher(gen, slots=tf["slots"],
                                block=tf["paged_block"],
                                pool_tokens=tf["pool_tokens"])
    out = compile_for_v5e(cb._jit_ticks(cb._tick_body()),
                          (gen.params, cb._state(), cb._aids))
    return dict(out, fused=cb.fused)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--n-layer", type=int, nargs="*", default=[])
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    with open(args.config) as f:
        cfg = json.load(f)
    with open(args.traffic) as f:
        traffic = json.load(f)
    for depth in args.n_layer or [cfg["n_layer"]]:
        c = dict(cfg, n_layer=depth)
        try:
            out = (compile_sweep if traffic["kind"] == "train"
                   else compile_tick)(c, traffic)
        except Exception as e:      # noqa: BLE001 — the compiler's words
            print(json.dumps({"n_layer": depth, "refused":
                              str(e).splitlines()[0][:400]}), flush=True)
            continue
        print(json.dumps(dict(out, n_layer=depth)), flush=True)
        break


if __name__ == "__main__":
    main()
