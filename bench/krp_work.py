"""Compulsory work of one ``krp_scatter`` call, the scatter kernel of the
program's row-gathering rungs (``pallas_fused``, ``pallas_fused_tiled``).

Counted from the call's output shape in the trace and the tensor's nnz and
R alone: no padding, no layout, no rank rounding. The kernel must read each
nonzero's R-wide Khatri-Rao row and its output row index, add the row into
its output row, and write the output rows once.
"""
from __future__ import annotations

import re

import work

# The HLO instruction a trace names the kernel by, and its output shape:
# ``%krp_scatter.3 = f32[6072,128]{1,0:T(8,128)} custom-call(...)``.
_CALL = re.compile(r"^%krp_scatter(?:\.\d+)? = f32\[(\d+),(\d+)\]")


def output_rows(op_name: str) -> int | None:
    """Output rows of a ``krp_scatter`` call (the mode's rows rounded up to
    a whole output tile), or None where ``op_name`` is no such call."""
    m = _CALL.match(op_name)
    return int(m.group(1)) if m else None


def call_work(rows: int, nnz: int, rank: int) -> tuple[float, float]:
    """(FLOP, bytes) one call cannot avoid: nnz*R adds; nnz*(R+1)*4 bytes
    read (the rows and their int32 output row), rows*R*4 written."""
    return float(nnz * rank), float(nnz * (rank + 1) * 4 + rows * rank * 4)


def least_seconds(rows: int, nnz: int, rank: int, device_kind: str) -> float:
    """Least time the chip could take for one call's compulsory work."""
    p = work.peaks(device_kind)
    flops, nbytes = call_work(rows, nnz, rank)
    return max(flops / p["flops_per_s"], nbytes / p["bytes_per_s"])
