"""Benchmark driver: one module per paper figure/table.

  PYTHONPATH=src python -m benchmarks.run             # quick suite
  PYTHONPATH=src python -m benchmarks.run --full
  PYTHONPATH=src python -m benchmarks.run --only total_time,schedule

Rows print as `k=v` CSV lines; every suite persists its own artifact
through ``common.write_bench_json`` — the single naming authority —
as ``experiments/bench/BENCH_<suite>.json`` (the pre-PR-5 lowercase
``<suite>.json`` dumps are retired). A suite that *errors* still gets a
BENCH file recording the failure, so downstream tooling can glob
``BENCH_*.json`` and see every suite accounted for — and the driver
then exits nonzero.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

from . import (bench_bf16_convergence, bench_collective_traffic,
               bench_dispatch, bench_lowering, bench_memory, bench_oocore,
               bench_preprocess, bench_prof, bench_rank, bench_remap_fusion,
               bench_remap_traffic, bench_reorder, bench_resilience,
               bench_scaling,
               bench_schedule, bench_total_time, roofline)
from . import common
from .common import print_rows, write_bench_json
from repro.runtime.compile_cache import enable_compile_cache

SUITES = {
    "remap_fusion": bench_remap_fusion.run,      # Fig. 2
    "total_time": bench_total_time.run,          # Fig. 3/4 + Table III
    "schedule": bench_schedule.run,              # Fig. 6
    "scaling": bench_scaling.run,                # Fig. 7
    "remap_traffic": bench_remap_traffic.run,    # Fig. 8
    "roofline": roofline.run,                    # Fig. 9 + §Roofline
    "rank": bench_rank.run,                      # Fig. 10
    "memory": bench_memory.run,                  # Fig. 11
    "preprocess": bench_preprocess.run,          # Fig. 12
    "collective_traffic": bench_collective_traffic.run,   # §IV lock-free claim
    "dispatch": bench_dispatch.run,              # repro.tune calibrated auto
    "bf16_convergence": bench_bf16_convergence.run,   # bf16 gathers, fit gap
    "oocore": bench_oocore.run,                  # out-of-core streamed gather
    "reorder": bench_reorder.run,                # locality-ordered streams
    "resilience": bench_resilience.run,          # fault-injection overhead
    "lowering": bench_lowering.run,              # interpret=False Mosaic status
    "prof": bench_prof.run,                      # timed steps + roofline GB/s
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--out", default="experiments/bench")
    args = ap.parse_args()

    names = list(SUITES) if not args.only else args.only.split(",")
    os.makedirs(args.out, exist_ok=True)
    common.BENCH_OUT_DIR = args.out     # BENCH_*.json follow --out
    enable_compile_cache()
    all_rows = []
    errored = []
    for name in names:
        fn = SUITES[name]
        t0 = time.perf_counter()
        try:
            rows = fn(quick=not args.full)
        except Exception as e:                    # noqa: BLE001
            rows = [dict(bench=name, status="error", error=repr(e)[:200])]
            write_bench_json(name, rows)
        if any(r.get("status") == "error" for r in rows):
            errored.append(name)
        dt = time.perf_counter() - t0
        print(f"## {name} ({dt:.1f}s)", flush=True)
        print_rows(rows)
        all_rows.extend(rows)
    print(f"## done: {len(all_rows)} rows -> {args.out}/", flush=True)
    if errored:
        print(f"## errored suites: {','.join(errored)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
