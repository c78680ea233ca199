#!/usr/bin/env python3
"""Records the small trace kept in ``benchmarks/tests/data/`` (run once
on the chip, by hand): five bursts of a named jitted matmul under a
``TraceAnnotation`` span, 20 ms of host sleep between bursts under
another — so the busy union, the idle share, the per-name device time
and the attribution of gaps to host spans all have known answers.

    python3 benchmarks/tests/record_trace.py OUT.xplane.pb
    python3 benchmarks/tests/record_trace.py describe SOME.xplane.pb

``describe`` is the by-hand reading of any trace (planes, lines, each
line's heaviest event names): what to look at before writing a reader
against it."""

import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out):
    x = jnp.ones((2048, 2048), jnp.bfloat16)

    @jax.jit
    def bench_matmul(a):
        return (a @ a) * 0.001

    bench_matmul(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    for _ in range(5):
        with jax.profiler.TraceAnnotation("bench.burst"):
            y = x
            for _ in range(4):
                y = bench_matmul(y)
            y.block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    path = glob.glob(d + "/plugins/profile/*/*.xplane.pb")[0]
    shutil.copy(path, out)
    shutil.rmtree(d)


def describe(path, top=40):
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        out.append("PLANE %s" % plane.name)
        for line in plane.lines:
            total, n = {}, 0
            for ev in line.events:
                n += 1
                total[ev.name] = total.get(ev.name, 0.0) + ev.duration_ns
            out.append("  LINE %s (%d events)" % (line.name, n))
            for name, ns in sorted(total.items(),
                                   key=lambda kv: -kv[1])[:top]:
                out.append("    %12.3f ms  %s" % (ns / 1e6, name[:160]))
    return "\n".join(out)


if __name__ == "__main__":
    if sys.argv[1] == "describe":
        print(describe(sys.argv[2]))
    else:
        main(sys.argv[1])
