"""Benchmark harness entry point — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only table2,...] [--rounds N]

Output: ``name,value,...`` CSV lines on stdout + JSON artifacts under
artifacts/bench/.  Roofline (from dry-run artifacts) is included when
artifacts/dryrun/ exists."""
from __future__ import annotations

import argparse
import os
import time
import traceback
from pathlib import Path

BENCHES = ["table2", "table3", "table4", "table_async",
           "table_sched_backend", "ablations", "roofline"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--rounds", type=int, default=0)
    args = ap.parse_args()
    if args.rounds:
        os.environ["REPRO_BENCH_ROUNDS"] = str(args.rounds)
    only = [s.strip() for s in args.only.split(",") if s.strip()]

    from benchmarks import (ablations, table2_accuracy,
                            table3_scalability, table4_communication,
                            table_async, table_sched_backend)
    jobs = {
        "table2": table2_accuracy.main,
        "table3": table3_scalability.main,
        "table4": table4_communication.main,
        "table_async": table_async.main,
        "table_sched_backend": table_sched_backend.main,
        "ablations": ablations.main,
    }
    if Path("artifacts/dryrun").exists() and any(
            Path("artifacts/dryrun").glob("*.json")):
        from benchmarks import roofline
        jobs["roofline"] = lambda rounds=None: roofline.main()

    rc = 0
    for name in (only or BENCHES):
        fn = jobs.get(name)
        if fn is None:
            continue
        t0 = time.time()
        print(f"### bench:{name}")
        try:
            fn(rounds=args.rounds or None) if name != "roofline" else fn()
            print(f"### bench:{name} done in {time.time()-t0:.1f}s")
        except Exception:
            rc = 1
            print(f"### bench:{name} FAILED")
            traceback.print_exc()
    raise SystemExit(rc)


if __name__ == "__main__":
    main()
