#!/usr/bin/env python3
"""Reads, on the chip, the two readings a limit is set between
(builder's contract, "How correct is decided", steps 3 to 5): for each
seed the numbers of a sound run of the program against the reference
(the lower reading is their largest), and for the first ``--controls``
seeds the numbers of the control (the reference in int8, in the
program's place) and of each planted fault (the upper reading is their
smallest).  One process, one JSON line per seed (a serving seed is one
whole run of the timed path: server, warm-up, a window of ``--seconds``);
not run by the benchmark's own runs.

    python3 benchmarks/calibrate.py --workload <cell> --seeds 1 2 3 ...
"""

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def calibrate_train(ctx, controls):
    from benchmarks.kinds import train
    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    wf, feed, prog, rows, _ = train.setup(ctx)
    wf.trainer.params = wf.trainer.velocity = None
    del wf, feed
    gc.collect()
    ref = train.reference_norms(cfg, traffic, ctx.seed, rows)
    out = {"program": train.compare(prog, ref)}
    if controls:
        for name, kw in (("control_int8", {"precision": "int8"}),
                         ("fault_half_batch", {"fault": "half_batch"})):
            got = train.reference_norms(cfg, traffic, ctx.seed, rows, **kw)
            out[name] = train.compare(got[:3], ref)
    return out


def calibrate_serve(ctx, controls):
    """One whole run of the timed path for this seed (server, warm-up,
    window at the cell's own load), then the program's reading and, if
    asked, the control's."""
    from benchmarks.kinds import serve_closed
    got = serve_closed.serve(ctx)
    red = serve_closed.reduce_records(
        got["records"], *serve_closed.on_deliveries(
            got["records"], got["t_open"], got["t_close"]))
    return {"program": serve_closed.check(ctx, got, red,
                                          control=controls)}


def main(argv=None, require_tpu=True, root=ROOT):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks import harness, manifest, run
    cell = manifest.Cell(root, manifest.load(root), args.workload)
    device, peaks = harness.find_device(cell.chips, require_tpu)
    from veles_tpu import compile_cache
    compile_cache.enable()
    ctxs = [run.Context(cell, seed, args.seconds, False, device, peaks)
            for seed in args.seeds]
    one = calibrate_train if cell.traffic["kind"] == "train" \
        else calibrate_serve
    readings = ((ctx.seed, one(ctx, i < args.controls))
                for i, ctx in enumerate(ctxs))
    sink = open(args.out, "a") if args.out else None
    t0 = time.perf_counter()
    for seed, got in readings:
        line = json.dumps({"cell": cell.name, "seed": seed,
                           "seconds": time.perf_counter() - t0,
                           "readings": got})
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()
        t0 = time.perf_counter()
    if sink:
        sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
