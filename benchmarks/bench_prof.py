"""Profiler suite: timed device steps + achieved-bandwidth roofline.

Unlike the counted suites, every number here is **timed** on this host
(via ``repro.obs.prof.harness`` — fenced steady-state repeats, robust
median) and therefore host-local: the artifact records the environment
fingerprint and noise score next to the numbers, and nothing in CI
diffs them (the noise-aware gate lives in ``python -m repro.obs.prof
gate``; this suite is the figure-style sweep).

Two sections:

  * ``prof_step`` — :func:`repro.kernels.mttkrp.ops.timed_device_step`
    per backend on one microbench grid point: median wall seconds,
    spread, the counted first-order traffic model
    (``ops.step.model_bytes``) and the model-achieved GB/s — the
    roofline coordinate per kernel backend.
  * ``prof_stream`` — one chunked out-of-core mode step per ordering
    policy under an enabled tracer: the ``oocore.mode_step`` span's
    measured time joined with its counted ``self_counters`` bytes by
    ``repro.obs.prof.roofline.bandwidth_rows`` — per-rung achieved GB/s
    exactly as ``python -m repro.obs.prof run`` computes it.

Everything lands in ``BENCH_prof.json`` (host-local, not committed).
"""
from __future__ import annotations

import numpy as np

from repro.obs import counters as _obs
from repro.obs import tracer as _tracer_mod
from repro.obs.prof import bandwidth_rows, env_fingerprint, measure_steady
from repro.tune.microbench import GridPoint, make_case

from .common import row, write_bench_json

_POINT = GridPoint(nmodes=3, rank=32, blk=32, tile_rows=8, density=1.0)
# One member per residency family — quick enough under interpret mode.
_BACKENDS = ("ref", "pallas_fused_gather", "pallas_fused")


def _step_rows(quick: bool) -> list[dict]:
    from repro.kernels.mttkrp import ops as kops

    backends = [b for b in _BACKENDS if b in kops.BACKENDS]
    repeats = 3 if quick else 5
    idx, val, valid, factors, rows_cap = make_case(_POINT, seed=0)
    model_b = kops.step_traffic_bytes(
        cap=int(idx.shape[0]), nmodes=_POINT.nmodes, rank=_POINT.rank,
        rows_cap=rows_cap)
    fp = env_fingerprint()
    out = []
    for backend in backends:
        with _obs.use_registry(), _tracer_mod.use_tracer() as tracer:
            stats = measure_steady(
                lambda: kops.timed_device_step(
                    idx, val, valid, factors, mode=0, rows_cap=rows_cap,
                    row_offset=0, blk=_POINT.blk, tile_rows=_POINT.tile_rows,
                    backend=backend),
                warmup=1, repeats=repeats, block=None)  # wrapper self-fences
        out.append(row(
            "prof_step", backend=backend, nmodes=_POINT.nmodes,
            rank=_POINT.rank, blk=_POINT.blk, tile_rows=_POINT.tile_rows,
            median_s=round(stats.median_s, 6),
            mad_frac=round(stats.mad_frac, 4),
            rejected=stats.rejected,
            model_bytes=model_b,
            model_gbps=round(model_b / max(stats.median_s, 1e-12) / 1e9, 4),
            spans=len(tracer.records),
            devices=fp.get("devices"),
        ))
    return out


def _stream_rows(quick: bool) -> list[dict]:
    import jax.numpy as jnp

    from repro.oocore.executor import mttkrp_out_of_core

    shape = (20000, 40, 9000, 30)
    blk, tile_rows, rank = 32, 8, 128 if quick else 256
    from repro.core.tensors import random_sparse_tensor
    t = random_sparse_tensor(shape, 600, seed=3, distribution="powerlaw")
    rng = np.random.default_rng(0)
    factors = [jnp.asarray(rng.standard_normal((d, rank)), jnp.float32)
               for d in shape]
    mode = 1
    order = np.argsort(t.indices[:, mode], kind="stable")
    idx = t.indices[order].astype(np.int32)
    val = t.values[order].astype(np.float32)
    valid = np.ones(len(val), bool)
    rows_cap = -(-shape[mode] // tile_rows) * tile_rows
    out = []
    for ordering in ("none", "tile", "morton"):
        with _obs.use_registry(), _tracer_mod.use_tracer() as tracer:
            mttkrp_out_of_core(
                idx, val, valid, factors, mode=mode, rows_cap=rows_cap,
                blk=blk, tile_rows=tile_rows, max_chunk_bytes=2000,
                ordering=ordering)
            rows = bandwidth_rows(tracer.records)
        for r in rows:
            out.append(row(
                "prof_stream", span=r["span"], backend=r["backend"],
                rung=r["rung"], ordering=ordering, calls=r["calls"],
                time_s=round(r["time_s"], 6),
                moved_bytes=r["moved_bytes"], basis=r["basis"],
                achieved_gbps=round(r["achieved_gbps"], 4),
            ))
    return out


def run(quick: bool = True) -> list[dict]:
    rows = _step_rows(quick) + _stream_rows(quick)
    write_bench_json("prof", rows)
    return rows
