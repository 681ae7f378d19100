"""Readings that set a cell's limits: the program's, and its control's.

    python3 bench/calibrate.py --workload <cell> --seeds 1001-1012 \
        --control-seeds 2001-2003 [--out <file.json>]

In one process, for each seed: make the tensor, prepare it, run one sweep
of the production sweep and take every number the check compares. Then
the same for the control: the program with its own lower-precision path
switched on, ``gather_dtype="bfloat16"`` (fp32 accumulate), in place of
the configured float32 gathers. The sweep is compiled once per side and
reused for every seed, since every seed of a cell has the same shapes.

Prints one line per seed and a summary: the largest reading of the
program (the lower reading) and the smallest of the control (the upper
reading) of each number, with the cell's limit between them.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import harness

CONTROL_GATHER_DTYPE = "bfloat16"


def seed_list(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def side_readings(cell, mesh, seeds, gather_dtype, ref_device, label) -> list:
    """Readings of one sweep per seed, the sweep compiled once."""
    compiled, rt0, rows = None, None, []
    for seed in seeds:
        inputs = harness.make_inputs(cell, seed)
        job = harness.prepare(cell, inputs, mesh, gather_dtype)
        if compiled is None:
            compiled, rungs = harness.compile_sweep(job,
                                                    cell.traffic["backend"])
            rt0 = job.rt
            harness.log(f"{label}: dispatch {json.dumps(rungs, sort_keys=True)}")
        elif job.rt != rt0:
            raise RuntimeError(f"seed {seed} changed the runtime's static "
                               f"shapes: {job.rt} != {rt0}")
        t0 = time.perf_counter()
        window = harness.run_window(compiled, job, 0.0)
        sweep_s = time.perf_counter() - t0
        values = harness.readings(job, window, ref_device)
        ok, _ = harness.judge(values, cell.limits)
        row = {"side": label, "seed": seed, "sweep_s": sweep_s, "ok": ok,
               **values}
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def summary(cell, program: list, control: list) -> dict:
    out = {}
    for key, limit in cell.limits.items():
        lower = max(r[key] for r in program) if program else None
        upper = min(r[key] for r in control) if control else None
        out[key] = {"lower": lower, "upper": upper, "limit": limit}
    return out


def main(argv=None, *, require_tpu: bool = True, root: str = harness.ROOT
         ) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seed_list, required=True)
    ap.add_argument("--control-seeds", type=seed_list, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload, root)
    mesh, devs = harness.open_mesh(cell, root, require_tpu)
    if mesh is None:
        return harness.NO_DEVICE
    program = side_readings(cell, mesh, args.seeds,
                            cell.traffic["gather_dtype"], devs[0], "program")
    control = side_readings(cell, mesh, args.control_seeds,
                            CONTROL_GATHER_DTYPE, devs[0], "control")
    result = {"workload": cell.name, "device": devs[0].device_kind,
              "summary": summary(cell, program, control),
              "program": program, "control": control}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result["summary"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
