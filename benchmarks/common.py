"""Shared benchmark utilities: timing, CSV rows, standard tensors.

Benchmarks measure on the REAL host CPU — legitimate here because the
paper's target is a CPU (the TPU mapping is validated by the dry-run +
roofline instead). On this 1-core container, *parallel wall-clock speedup*
is not measurable, so distribution-sensitive figures (6, 7) report counted
work-balance metrics (max/mean load = the paper's speedup bound) alongside
wall time, and figure 8 counts exact traffic bytes.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable

import jax
import numpy as np

from repro.core.tensors import frostt_like

BENCH_TENSORS = ("nell-2", "nell-1", "flickr", "delicious", "vast", "enron")

BENCH_OUT_DIR = os.path.join("experiments", "bench")


def write_bench_json(name: str, rows: list[dict],
                     out_dir: str | None = None) -> str:
    """Write machine-readable rows to ``<out_dir>/BENCH_<name>.json``.

    The one shared writer every benchmark uses (no ad-hoc per-module
    writers), so downstream tooling can glob ``BENCH_*.json``.
    ``out_dir=None`` resolves to the module-level ``BENCH_OUT_DIR``,
    which ``benchmarks.run --out`` redirects so row dumps and BENCH
    artifacts land in one place.
    """
    out_dir = BENCH_OUT_DIR if out_dir is None else out_dir
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(rows, f, indent=1, default=str)
    return path


def exchange_sizing(ft, num_workers: int) -> dict:
    """Allocated all_to_all payload bytes for a FLYCOO tensor, both ways.

    ``uniform``: every transition padded to the max capacity (the old
    ``DynasorRuntime.bucket_cap`` / ``uniform_cap=True`` sizing).
    ``per_transition``: each transition sized to its own
    ``remap_capacities`` bound (the tuned default). The single source of
    truth for bench_remap_traffic and bench_dispatch.
    """
    from repro.core.remap import remap_capacities

    caps = remap_capacities(ft)
    elem_bytes = 4 * ft.nmodes + 4          # coords + value
    per_transition = sum(num_workers * num_workers * c * elem_bytes
                         for c in caps)
    uniform = (ft.nmodes * num_workers * num_workers * max(caps)
               * elem_bytes)
    return dict(
        caps=list(map(int, caps)),
        elem_bytes=elem_bytes,
        uniform_bytes=uniform,
        per_transition_bytes=per_transition,
        savings_frac=1.0 - per_transition / max(uniform, 1),
    )


def pr2_static_backend(nmodes: int, rank: int, blk: int,
                       tile_rows: int) -> str:
    """The PR-2 static dispatch rule, reconstructed for baseline rows.

    Before the rank-tiled kernel existed, `select_backend` had exactly
    two MXU rules: fused iff the *full* padded-rank working set fits the
    VMEM budget, else materialize in HBM. bench_rank and bench_dispatch
    both record this historical decision next to the current one — one
    definition here so the two benches can never disagree about what
    \"PR-2 behavior\" was. ``"pallas"`` names PR 2's materializing
    backend, since folded into ``pallas_fused``: a label only, never
    dispatched.
    """
    from repro.kernels.mttkrp import ops as kops

    if rank < kops.MIN_MXU_RANK:
        return "ref"
    if kops.fused_fits_vmem(nmodes, rank, blk, tile_rows):
        return "pallas_fused"
    return "pallas"


def timeit(fn: Callable, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall seconds; blocks on jax outputs."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def bench_tensor(name: str, scale: float = 0.25, seed: int = 0):
    return frostt_like(name, seed=seed, scale=scale)


def row(bench: str, **kv) -> dict:
    return dict(bench=bench, **kv)


def print_rows(rows: list[dict]) -> None:
    for r in rows:
        items = ",".join(f"{k}={v}" for k, v in r.items())
        print(items, flush=True)
