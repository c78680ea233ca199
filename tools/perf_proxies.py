"""CPU-proxy performance metrics for the perf-ledger CI job.

The real numbers live on silicon (bench.py, banked into the process
ledger), but two properties are measurable anywhere and worth guarding
every merge:

* **ratios** — segmented-vs-unsegmented decode-stall behaviour is a
  scheduling property of the engine, not of the chip; the segmented
  run must beat the unsegmented one on a laptop exactly as on a v5e.
* **host-side overheads** — the tuner's launch-time lookup and the
  perf ledger's own append are pure host code on the dispatch path;
  a regression there is a regression everywhere.

Each proxy appends to the target ledger (``--out``, default the
process ledger) through the same ``telemetry.ledger`` plumbing the
real harnesses use, so ``veles-tpu-perf report`` / ``gate`` read CI
runs and silicon runs identically — the keys differ only on the
backend axis.

Usage:  python tools/perf_proxies.py --out /tmp/perf_ledger.jsonl \
            --repeat 4
"""

import argparse
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def tuner_lookup_us(n=2000):
    """Mean launch-time lookup cost (µs) against a warm 64-winner
    cache — hits and misses both ride the dispatch path."""
    from veles_tpu import tuner as tn
    with tempfile.TemporaryDirectory() as d:
        t = tn.KernelTuner(path=os.path.join(d, "winners.json"))
        for i in range(64):
            t.record("flash", "t%d_d64" % (128 << (i % 8)), "float32",
                     {"block_q": 128, "block_k": 128}, 1.0 + i)
        keys = [("flash", "t%d_d64" % (128 << (i % 8)), "float32")
                for i in range(n)]
        t0 = time.perf_counter()
        for kernel, shape, dtype in keys:
            t.lookup(kernel, shape, dtype)
        return (time.perf_counter() - t0) / n * 1e6


def ledger_append_us(n=500):
    """Mean cost (µs) of one ledger append — the price every banked
    step/gate/bench row pays; it must stay negligible next to even a
    sub-millisecond step."""
    from veles_tpu.telemetry import ledger
    with tempfile.TemporaryDirectory() as d:
        book = ledger.PerfLedger(os.path.join(d, "led.jsonl"))
        t0 = time.perf_counter()
        for i in range(n):
            book.append("proxy_overhead_probe", float(i), unit="us",
                        source="perf_proxies", assess=False)
        return (time.perf_counter() - t0) / n * 1e6


def seg_stall_ratio():
    """Segmented-vs-unsegmented p99 decode-stall ratio from one small
    mixed storm (tools/serve_loadtest.run_mixed) — must stay well
    under 1.0 on any box.  Returns (ratio, seg_p99, unseg_p99) or
    None when the storm could not run."""
    from tools import serve_loadtest as lt
    report = lt.run_mixed(prefill_segment=8, long_len=64,
                          stream_new=16, long_new=2, seed=7,
                          streamers=2, long_clients=2, short_len=5,
                          slots=2)
    seg = (report.get("segmented") or {}).get("p99_decode_stall_ms")
    unseg = (report.get("unsegmented") or {}).get(
        "p99_decode_stall_ms")
    if not seg or not unseg:
        return None
    return round(seg / unseg, 3), seg, unseg


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="CPU-proxy perf metrics -> performance ledger "
                    "(telemetry.ledger; docs/perf.md)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="ledger JSONL to append to (default: the "
                         "process ledger)")
    ap.add_argument("--repeat", type=int, default=1,
                    help="measurement rounds — >=4 gives the "
                         "sentinel a band (min_history priors) to judge the last one")
    ap.add_argument("--skip-storm", action="store_true",
                    help="skip the mixed-storm ratio proxy (engine "
                         "spin-up; the host-overhead proxies are "
                         "cheap)")
    args = ap.parse_args(argv)

    from veles_tpu.telemetry import ledger
    book = ledger.PerfLedger(args.out) if args.out else ledger.default()
    rc = 0
    for round_i in range(max(args.repeat, 1)):
        rec = book.append("tuner_lookup_us", tuner_lookup_us(),
                          workload="cpu-proxy", unit="us",
                          better="lower", source="perf_proxies")
        print("tuner_lookup_us: %s" % ((rec or {}).get("value"),))
        rec = book.append("ledger_append_us", ledger_append_us(),
                          workload="cpu-proxy", unit="us",
                          better="lower", source="perf_proxies")
        print("ledger_append_us: %s" % ((rec or {}).get("value"),))
        if not args.skip_storm:
            try:
                got = seg_stall_ratio()
            except Exception as e:  # noqa: BLE001 — proxy best-effort
                print("mixed-storm proxy failed: %s" % e,
                      file=sys.stderr)
                got, rc = None, 1
            if got:
                ratio, seg, unseg = got
                book.append("serve_stall_seg_vs_unseg_x", ratio,
                            workload="cpu-proxy", unit="x",
                            better="lower", source="perf_proxies",
                            seg_p99_ms=seg, unseg_p99_ms=unseg)
                print("serve_stall_seg_vs_unseg_x: %s "
                      "(seg %.3f ms vs unseg %.3f ms)"
                      % (ratio, seg, unseg))
    print("ledger: %s (%d records)"
          % (book.path, len(book.records())))
    return rc


if __name__ == "__main__":
    sys.exit(main())
