#!/usr/bin/env python3
"""``calibrate.py`` for the kind ``serve_closed_state``: on the chip,
for each seed one whole run of the timed path (server, warm-up, a
window of ``--seconds`` at the cell's own load) and the program's
reading of ``logit_gap``; for the first ``--controls`` seeds also the
controls' readings over the same sample of answers (``--which``, names
of ``serve_closed_state.CONTROLS``) — the reference with every matmul
operand on a per-tensor int8 grid, with nothing before the last pass
boundary remembered, with the gates held at 1, with a softmax in the
squared product's place.  The limit lies above the program's largest
reading and under every control's smallest.
One process, one JSON line a seed; not run by the benchmark's own runs.

    python3 benchmarks/calibrate_brumby.py --workload <cell> --seeds 1 2
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None, require_tpu=True, root=ROOT):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--which", nargs="+",
                    default=["int8", "state_reset", "gate_off",
                             "softmax_attention"])
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks import harness, manifest, run
    from benchmarks.kinds import serve_closed_state as kind
    cell = manifest.Cell(root, manifest.load(root), args.workload)
    device, peaks = harness.find_device(cell.chips, require_tpu)
    from veles_tpu import compile_cache
    compile_cache.enable()
    sink = open(args.out, "a") if args.out else None
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        ctx = run.Context(cell, seed, args.seconds, False, device, peaks)
        got = kind.serve(ctx)
        red = kind.reduce_records(got["records"], *kind.on_deliveries(
            got["records"], got["t_open"], got["t_close"]))
        numbers, notes = kind.check(
            ctx, got, red,
            controls=args.which if i < args.controls else ())
        line = json.dumps({
            "cell": cell.name, "seed": seed,
            "seconds": time.perf_counter() - t0,
            "out_tokens_per_s": red["out_tokens_per_s"],
            "readings": {"program": numbers, "notes": notes}})
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()
    if sink:
        sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
