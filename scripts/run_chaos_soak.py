#!/usr/bin/env python
"""Chaos soak CLI: seeded crash/partition schedule over a mixed
workload, conservation invariants checked at the end.

    python scripts/run_chaos_soak.py --duration 300 --seeds 0,1,2 \
        --out /tmp/chaos.json

Exit code 0 iff zero invariant violations across all seeds. See
docs/crash_chaos.md for the crash-point catalog and the per-class MTTR
definitions this reports.
"""

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--duration", type=float, default=300.0,
                    help="soak length per seed, seconds")
    ap.add_argument("--seeds", default="0",
                    help="comma-separated seeds (one soak per seed)")
    ap.add_argument("--classes", default="worker,replica,raylet,gcs",
                    help="fault classes to inject")
    ap.add_argument("--no-partitions", action="store_true",
                    help="skip metrics-plane partition faults")
    ap.add_argument("--inject-period", type=float, default=8.0,
                    help="mean seconds between injections")
    ap.add_argument("--out", default="",
                    help="report path ('', the default: none is written)")
    args = ap.parse_args(argv)

    from ray_tpu.chaos_soak import run_soak_matrix

    seeds = [int(s) for s in args.seeds.split(",") if s.strip()]
    classes = tuple(c.strip() for c in args.classes.split(",")
                    if c.strip())
    report = run_soak_matrix(
        args.duration, seeds, classes,
        out_path=args.out or None,
        partitions=not args.no_partitions,
        inject_period_s=args.inject_period)
    bad = report["chaos_soak_invariant_violations"]
    for sd, run in report["runs"].items():
        w = run["workloads"]
        print(f"seed {sd}: "
              + ", ".join(f"{n}={s['ok']}/{s['submitted']} ok"
                          f" (+{s['typed_errors']} typed)"
                          for n, s in w.items())
              + f", violations={run['chaos_soak_invariant_violations']}")
        for cls, entry in run["per_class"].items():
            keys = [k for k in entry if k.endswith("_mean_s")]
            stats = ", ".join(f"{k}={entry[k]:.2f}" for k in keys)
            print(f"  {cls}: {entry['injections']} injections"
                  + (f", {stats}" if stats else ""))
    if bad:
        print(f"CHAOS SOAK FAILED: {bad} invariant violations")
        for sd, run in report["runs"].items():
            for v in run["violations"]:
                print(f"  seed {sd}: {json.dumps(v, default=str)}")
        return 1
    print("chaos soak: conservation held (0 violations)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
