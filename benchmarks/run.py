#!/usr/bin/env python3
"""Runs one cell of ``BENCHMARK.json`` once, in one process that holds
the chip:

    python3 benchmarks/run.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

Set-up (build the model on the device from the seed, warm the cell's
own shapes), the measured window, then the comparison with the plain
reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, in a traced run ``breakdown``, and last
``compared`` — each number that decided ``correct`` beside its limit
(also the last lines of standard error).  No TPU, fewer chips than the
cell asks for, or a device without a row in ``benchmarks/peaks.py``:
non-zero exit before any work, no result line."""

import time
T0 = time.perf_counter()

import argparse                    # noqa: E402
import importlib                   # noqa: E402
import importlib.util              # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import sys                         # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Context:
    """What a kind's ``run`` gets."""

    def __init__(self, cell, seed, seconds, trace, device, peaks):
        from benchmarks import harness
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.device, self.peaks = trace, device, peaks
        self.t0 = T0
        self.phases = harness.Phases(T0)


def read_per_layer(cell, collected):
    """Each per-layer metric of the cell through its own reader; a
    reader that finds nothing to read returns None and the metric is
    left out of the line."""
    from benchmarks import manifest
    out = {}
    for name in cell.per_layer:
        path = manifest.reader_path(name)
        spec = importlib.util.spec_from_file_location(
            "benchmarks.readers." + os.path.basename(path)[:-3], path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        value = module.read(collected)
        if value is not None:
            out[name] = {"value": float(value), "unit": cell.units[name]}
    return out


def main(argv=None, require_tpu=True, root=ROOT, out=sys.stdout):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks import check, harness, manifest
    cell = manifest.Cell(root, manifest.load(root), args.workload)
    device, peaks = harness.find_device(cell.chips, require_tpu)
    from veles_tpu import compile_cache
    cache_dir = compile_cache.enable()
    print("[bench] cell=%s seed=%d seconds=%g trace=%d device=%s cache=%s"
          % (cell.name, args.seed, args.seconds, args.trace,
             device["kind"], cache_dir), file=sys.stderr, flush=True)
    kind = importlib.import_module(
        "benchmarks.kinds." + cell.traffic["kind"])
    ctx = Context(cell, args.seed, args.seconds, bool(args.trace), device,
                  peaks)
    ctx.phases.mark("import_and_device")
    res = kind.run(ctx)
    res["notes"]["phases_s"] = ctx.phases.seconds

    correct, compared = check.verdict(res["numbers"], cell.limits["limits"])
    device = dict(device, memory_peak_bytes=res["memory_peak_bytes"])
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"]}
    if args.trace:
        tr = res["collected"]["trace"]
        line["metrics"] = read_per_layer(cell, res["collected"])
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
        res["notes"]["device_seconds_by_opcode"] = tr["opcode_seconds"]
    else:
        line["metrics"] = {
            name: {"value": float(res["end_to_end"][name]),
                   "unit": cell.units[name]} for name in cell.end_to_end}
    line["device"] = device
    line["notes"] = res["notes"]
    line["compared"] = compared
    for name, c in compared.items():
        print("[bench] compared %s value=%r limit=%r"
              % (name, c["value"], c["limit"]), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
