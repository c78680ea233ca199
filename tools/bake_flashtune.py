#!/usr/bin/env python3
"""Import a ``bench.py --phase flashtune`` sweep into the tuner cache.

Historically this tool baked ONE global (block_q, block_k) winner into
``veles_tpu_site_config.py``.  The kernel autotuner
(``veles_tpu.tuner``, docs/perf.md "Autotuning") made that the legacy
path: flash resolves its blocks per (kernel, shape-bucket, dtype, mesh)
from the tuner's winner cache at launch time, and ``bench.py --phase
flashtune`` now writes the cache itself.  What remains for this tool is
*import*: take a sweep grid a log captured (possibly from an older
session, possibly fwd-only) and replay its winners into the cache
— per T shape, per kernel (fwd / bwd_dq / bwd_dkv), every entry
re-validated through the VP6xx launch audit before it lands (an
over-VMEM winner from a stale log is refused, not cached).

Input: the flashtune output — either a log holding the
``PHASE_RESULT {...}`` line or a bare JSON grid file.  Grids with the split ``ms_dq``/``ms_dkv`` timings pick each
backward kernel's winner independently; legacy fwd-only/``ms_bwd``
grids fall back to the yoked scoring.

    python tools/bake_flashtune.py flashtune.log
    python tools/bake_flashtune.py --dry-run sweep.json
    python tools/bake_flashtune.py --site-config sweep.json   # legacy

``--site-config`` additionally writes the old
``veles_tpu_site_config.py`` (one global winner, ``--head-dim`` picks
the d128 vs d64 key pair) for setups that pin blocks via config —
config still beats the tuner at launch time.
"""

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TEMPLATE = '''"""Site config — generated by tools/bake_flashtune.py from an
on-hardware flashtune sweep; do not hand-edit the numbers, re-run the
sweep.  Loaded automatically (config.apply_site_config)."""


def update(root):
    root.common.engine.flash.%(key_q)s = %(bq)d
    root.common.engine.flash.%(key_k)s = %(bk)d
    # provenance: %(provenance)s
    # sweep (ms fwd / fwd+bwd): %(summary)s
'''


def load_grid(path):
    text = open(path).read()
    m = re.search(r"PHASE_RESULT (\{.*\})", text)
    if m:
        return json.loads(m.group(1))
    return json.loads(text)


def parse_grid(grid):
    """{(t, bq, bk): result} from the legacy key grammar (``winner_*``
    summary keys are skipped)."""
    by_key = {}
    for key, r in grid.items():
        m = re.match(r"t(\d+)_q(\d+)_k(\d+)$", key)
        if not m:
            continue
        t, bq, bk = map(int, m.groups())
        by_key[(t, bq, bk)] = r
    return by_key


def pick_winner(grid):
    """Legacy global scoring (kept for --site-config): total fwd+bwd ms
    across all T a config survived; a config that failed (VMEM
    overflow) at ANY swept T is out."""
    by_block = {}
    ts = set()
    for (t, bq, bk), r in parse_grid(grid).items():
        ts.add(t)
        by_block.setdefault((bq, bk), {})[t] = r
    best, best_score = None, None
    for blocks, per_t in sorted(by_block.items()):
        if set(per_t) != ts:
            continue          # missing a T = failed there, ineligible
        score = sum(r.get("ms_bwd") or r["ms"] * 3 for r in per_t.values())
        if best_score is None or score < best_score:
            best, best_score = blocks, score
    return best, by_block


def cache_winners(grid, head_dim):
    """Per-(kernel, T) winners for the tuner cache: fwd by ``ms``, the
    backward kernels by their isolated ``ms_dq``/``ms_dkv`` timings
    when the grid has them, else by the yoked ``ms_bwd``."""
    per_t = {}
    for (t, bq, bk), r in parse_grid(grid).items():
        per_t.setdefault(t, []).append(((bq, bk), r))
    out = []
    for t, rows in sorted(per_t.items()):
        for kernel, metric in (("flash.fwd", "ms"),
                               ("flash.bwd_dq", "ms_dq"),
                               ("flash.bwd_dkv", "ms_dkv")):
            scored = [(r.get(metric) if metric in r
                       else r.get("ms_bwd"), blocks)
                      for blocks, r in rows]
            scored = [(ms, blocks) for ms, blocks in scored
                      if ms is not None]
            if not scored:
                continue
            ms, (bq, bk) = min(scored)
            out.append((kernel, t, head_dim,
                        {"block_q": bq, "block_k": bk}, ms))
    return out


def main():
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        epilog="exit codes: 0 = winners imported (or printed with "
               "--dry-run); 1 = nothing importable in the sweep, or "
               "every winner failed the VP6xx audit; 2 = usage error")
    ap.add_argument("sweep", help="flashtune log or JSON grid")
    ap.add_argument("--cache", default=None,
                    help="tuner winner-cache path (default: the "
                    "repo-local .veles_tune/winners.json the launch "
                    "path reads)")
    ap.add_argument("--dtype", default="bfloat16",
                    help="dtype the sweep measured (cache key part)")
    ap.add_argument("--mesh", default=None,
                    help="mesh-topology key to record under (default: "
                    "this process's topology)")
    ap.add_argument("--site-config", action="store_true",
                    help="ALSO bake the legacy one-global-winner "
                    "veles_tpu_site_config.py (config beats the tuner "
                    "at launch time)")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "veles_tpu_site_config.py"),
        help="--site-config output path")
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--head-dim", type=int, default=128,
                    help="head dim of the swept shape (cache key part; "
                    "<= 64 also picks the *_d64 site keys)")
    args = ap.parse_args()

    from veles_tpu import tuner as tn
    from veles_tpu.ops.pallas import flash
    from veles_tpu.tuner import flash_shape_key

    grid = load_grid(args.sweep)
    winners = cache_winners(grid, args.head_dim)
    if not winners:
        sys.exit("no importable (block_q, block_k) results in the "
                 "sweep — nothing to cache")

    tuner = tn.KernelTuner(path=args.cache) if args.cache \
        else tn.get_tuner()
    kern_map = {"flash.fwd": "forward", "flash.bwd_dq": "bwd_dq",
                "flash.bwd_dkv": "bwd_dkv"}
    imported = rejected = 0
    for kernel, t, d, config, ms in winners:
        blocks = (dict(config) if kernel == "flash.fwd" else
                  {"block_q_%s" % kernel[10:]: config["block_q"],
                   "block_k_%s" % kernel[10:]: config["block_k"]})
        launches = flash.audit_launch(
            t, t, d, causal=True, kernels=(kern_map[kernel],), **blocks)
        line = "%s T=%d: block_q=%d block_k=%d  %.3f ms" % (
            kernel, t, config["block_q"], config["block_k"], ms)
        if args.dry_run:
            ok, findings = tuner.audit_candidate(launches)
            print("%s  [%s]" % (line, "ok" if ok else "VP6xx-REJECTED"))
            imported += ok
            rejected += not ok
            continue
        try:
            tuner.record(kernel, flash_shape_key(t, d), args.dtype,
                         config, ms, mesh=args.mesh,
                         source="bake:%s" % os.path.basename(args.sweep),
                         launches=launches)
        except ValueError as e:
            print("REFUSED %s (%s)" % (line, e))
            rejected += 1
            continue
        print("cached  %s" % line)
        imported += 1
    if not args.dry_run and imported:
        print("-> %s" % (args.cache or tn.default_cache_path()))

    if args.site_config:
        winner, by_block = pick_winner(grid)
        if winner is None:
            sys.exit("--site-config: no (block_q, block_k) survived "
                     "every swept T — nothing to bake")
        bq, bk = winner
        summary = "; ".join(
            "T=%d: %.3f/%.3f" % (t, r["ms"], r.get("ms_bwd") or -1)
            for t, r in sorted(by_block[winner].items()))
        small = args.head_dim <= 64
        body = TEMPLATE % {"bq": bq, "bk": bk,
                           "key_q": "block_q_d64" if small else "block_q",
                           "key_k": "block_k_d64" if small else "block_k",
                           "provenance": os.path.basename(args.sweep),
                           "summary": summary}
        print("site winner: block_q=%d block_k=%d  (%s)"
              % (bq, bk, summary))
        if args.dry_run:
            print(body)
        else:
            with open(args.out, "w") as f:
                f.write(body)
            print("baked -> %s" % args.out)
    if not imported:
        sys.exit(1)


if __name__ == "__main__":
    main()
