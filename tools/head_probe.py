#!/usr/bin/env python3
"""Times the LM head and its loss, forward and backward, on one TPU —
the XLA path (the head's logits, then ``masked_seq_xent``) against the
fused op (``ops.losses.lm_head_xent``, kernel ``veles_head_xent``) at
several tiles — at the two training shapes, and checks that they agree:

    chiprun -- python3 tools/head_probe.py

The tile constants of ``ops/pallas/head_xent.py`` (``_ROW_TILE``,
``_VOCAB_TILE``, ``_ROW_CHUNK``) were chosen from its readings.  Each
line printed is one shape's JSON: medians in ms of twelve timed calls
after two warm ones, and the gaps of the loss, the error count and the
two gradients against the XLA path.  Fails on anything but a TPU."""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from veles_tpu.ops import losses  # noqa: E402
from veles_tpu.ops.pallas import head_xent as hx  # noqa: E402


def timeit(fn, *args, n=12):
    for _ in range(2):
        r = fn(*args)
        jax.block_until_ready(r)
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        r = fn(*args)
        jax.block_until_ready(r)
        ts.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(ts), r


def probe(name, b, t, d, v, tile_list):
    k = jax.random.key(0)
    h = jax.random.normal(k, (b, t, d), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(k, 1), (v, d),
                          jnp.float32) * 0.02
    nxt = jax.random.randint(jax.random.fold_in(k, 2), (b, t - 1), 0, v)
    valid = jnp.ones((b,), jnp.float32)

    def ref(h, w):
        z = jnp.dot(h.astype(jnp.bfloat16), w.T.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
        s = losses.masked_seq_xent(z[:, :-1], nxt, valid)
        return s[0] / s[2], s

    def fused(h, w):
        s = losses.lm_head_xent(h, w, nxt, valid)
        return s[0] / s[2], s

    ms, (rv, rg) = timeit(jax.jit(jax.value_and_grad(
        ref, argnums=(0, 1), has_aux=True)), h, w)
    res = {"xla_fwd_bwd_ms": ms, "loss": float(rv[0]),
           "xla_fwd_ms": timeit(jax.jit(lambda h, w: ref(h, w)[1]),
                                h, w)[0]}
    hc = h.reshape(-1, d).astype(jnp.bfloat16)
    wc = w.astype(jnp.bfloat16)
    lab = jnp.pad(nxt, ((0, 0), (0, 1))).reshape(-1)
    real = hx.head_tiles
    for tiles in tile_list:
        hx.head_tiles = lambda n, v, d, itemsize=2, tiles=tiles: tiles
        losses._head_xent_op.cache_clear()
        try:
            ms, (nv, ng) = timeit(jax.jit(jax.value_and_grad(
                fused, argnums=(0, 1), has_aux=True)), h, w)
            res["%dx%d" % tiles] = {
                "fwd_bwd_ms": ms,
                "fwd_ms": timeit(jax.jit(lambda h, w: fused(h, w)[1]),
                                 h, w)[0],
                "kernel_ms": timeit(jax.jit(hx.head_xent), hc, wc, lab)[0],
                "loss_gap": abs(float(nv[0]) - float(rv[0])),
                "err_gap": float(nv[1][1] - rv[1][1]),
                "grad_gap": [float(jnp.max(jnp.abs(a - c))
                                   / jnp.max(jnp.abs(a)))
                             for a, c in zip(rg, ng)]}
        except Exception as e:      # a tile the compiler refuses
            res["%dx%d" % tiles] = repr(e)[:300]
        finally:
            hx.head_tiles = real
            losses._head_xent_op.cache_clear()
            hx._head_fn.cache_clear()      # _ROW_CHUNK is read in a trace
    print(json.dumps({name: res}), flush=True)


def main():
    if jax.default_backend() != "tpu":
        sys.exit("head_probe: no TPU (%s)" % jax.default_backend())
    print(json.dumps({"device": jax.devices()[0].device_kind}))
    probe("gpt2s", 16, 1024, 768, 50257,
          [(1024, 1024), (1024, 2048), (2048, 1024), (512, 2048)])
    for rc in (8, 64):
        hx._ROW_CHUNK = rc
        probe("gpt2s_rc%d" % rc, 16, 1024, 768, 50257, [(1024, 1024)])
    hx._ROW_CHUNK = 32
    probe("cgpt13", 4, 2048, 2048, 50257, [(1024, 1024), (512, 2048)])


if __name__ == "__main__":
    main()
