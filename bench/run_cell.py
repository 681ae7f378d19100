"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (reported as ``setup_s``) starts with this process: tensor
generation from the seed, FLYCOO preprocessing, the copy to the chip and
the compile of the sweep. Then sweeps run back to back for ``--seconds``
(the sweep running at the deadline finishes and counts), and the first
sweep's factors and fit and the carried stream are compared with a plain
reference. The last line of standard output is one JSON object; with
``--trace 1`` the window is profiled and the per-layer metrics replace
the end-to-end ones. Without a TPU, or with fewer chips than the cell
asks for, it exits nonzero and prints no result.
"""
import time

_T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=_T0))
