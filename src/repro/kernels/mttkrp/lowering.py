"""Mosaic lowering validation: compile every backend on a CPU-only host.

The compiled-path honesty harness. A Pallas kernel that only ever runs
in the interpreter can silently accumulate Mosaic
incompatibilities — ops without a TPU lowering rule (``gather``),
BlockSpec shapes that violate sublane/lane tiling, dtype/layout
mistakes — and nothing notices until a real TPU job dies. This module
catches that class of rot with **no TPU attached**: jax's AOT path

    jax.jit(f).trace(*abstract_args).lower(lowering_platforms=("tpu",))

runs the full StableHLO + Mosaic kernel compilation pipeline on any
host (only *execution* needs the device — see
:mod:`repro.runtime.execution` for that half of the story), so CI can
assert that every backend in :data:`repro.kernels.mttkrp.ops.BACKENDS`
compiles, per representative geometry, on every commit.

What is validated per (backend, geometry): ``ops.mttkrp_device_step`` —
the real dispatch entry (layout build + kernel), not a test double —
lowered whole with ``interpret=False``; for Pallas backends the result
must contain a ``tpu_custom_call`` (the serialized Mosaic module), for
``ref`` it must simply lower (plain XLA).

Compiled-geometry constraint (Mosaic, not this harness): the kernels'
rank-1 ``(blk,)`` scalar-stream blocks require ``blk % 1024 == 0``
(:data:`MOSAIC_BLK_MULTIPLE`); the interpreter accepts any ``blk``.
Geometries here respect it — see :func:`compiled_geometry_ok`.

Lowering is not compiling: a kernel that lowers may still be refused by
the TPU compiler (tiling, VMEM, SMEM). ``tests/test_tpu_compile.py``
compiles the main path at real widths for a described v5e.

Entry points: :func:`lower_backend` (one check), :func:`run` (a grid →
``LoweringResult`` rows, the payload of ``BENCH_lowering.json``), and
``python -m repro.kernels.mttkrp.lowering`` (the CI ``lowering-smoke``
step; ``--full`` for the slow grid).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from . import kernel as _kernel
from . import ops as _ops

__all__ = [
    "MOSAIC_BLK_MULTIPLE",
    "Geometry",
    "LoweringResult",
    "SMOKE_GEOMETRIES",
    "FULL_GEOMETRIES",
    "compiled_geometry_ok",
    "device_step_args",
    "lower_backend",
    "run",
    "main",
]

# Mosaic requires rank-1 block shapes — the kernels' (blk,) value/row
# streams — to match XLA's 1024-element tiling of rank-1 arrays (or be
# the whole array dimension, which the blocked layout never is).
# Execution-mode geometry constraint only: the interpreter accepts any
# blk. kernel.py owns the rule and raises on a compiled call that breaks it.
MOSAIC_BLK_MULTIPLE = _kernel.COMPILED_BLK_MULTIPLE


@dataclasses.dataclass(frozen=True)
class Geometry:
    """One lowering-validation configuration.

    ``factor_rows`` is the row count of every non-output-mode factor —
    it sizes the resident gather operands and (after padding to
    ``FACTOR_ROW_TILE``) the stream backend's per-mode window,
    ``min(blk, ceil(rows/128))`` tiles. ``num_tiles`` output row tiles
    give ``rows_cap = num_tiles * tile_rows``; ``nnz_cap`` is the
    unaligned stream length (the layout build pads it).
    """

    nmodes: int
    rank: int
    blk: int
    tile_rows: int
    factor_rows: int = 64
    num_tiles: int = 4
    nnz_cap: int = 256

    @property
    def rows_cap(self) -> int:
        return self.num_tiles * self.tile_rows

    @property
    def window_tiles(self) -> int:
        """The stream backend's per-mode window at this geometry."""
        from ...oocore import planner as _planner

        frow = _kernel.FACTOR_ROW_TILE
        padded = self.factor_rows + (-self.factor_rows) % frow
        return _planner.stream_window_tiles(self.blk, padded)

    def label(self) -> str:
        return (f"N{self.nmodes}_R{self.rank}_blk{self.blk}"
                f"_t{self.tile_rows}_rows{self.factor_rows}")


# The CI smoke grid: one small-everything point, a higher-order point,
# and a multi-slab + multi-tile-window point — ≥ 3 geometries per
# backend, each exercising a distinct BlockSpec regime (whole-rank vs
# rank-slab factors, window width 1 vs >1), all < a second to lower.
SMOKE_GEOMETRIES = (
    Geometry(nmodes=3, rank=128, blk=1024, tile_rows=8, factor_rows=64),
    Geometry(nmodes=4, rank=128, blk=1024, tile_rows=128, factor_rows=96),
    Geometry(nmodes=3, rank=256, blk=1024, tile_rows=8, factor_rows=300),
)

# The slow full grid: adds a 5-mode point, a non-128-multiple rank (the
# pad_rank path), wide blocks, and a many-tile stream window.
FULL_GEOMETRIES = SMOKE_GEOMETRIES + (
    Geometry(nmodes=5, rank=128, blk=1024, tile_rows=16, factor_rows=64),
    Geometry(nmodes=3, rank=200, blk=1024, tile_rows=8, factor_rows=64),
    Geometry(nmodes=3, rank=512, blk=2048, tile_rows=128, factor_rows=700),
    Geometry(nmodes=4, rank=256, blk=1024, tile_rows=32, factor_rows=1000,
             num_tiles=8, nnz_cap=1024),
)


@dataclasses.dataclass(frozen=True)
class LoweringResult:
    """Outcome of one (backend, geometry) lowering attempt."""

    backend: str
    geometry: Geometry
    ok: bool
    mosaic: bool            # StableHLO contains a tpu_custom_call
    seconds: float
    error: str = ""

    def row(self) -> dict:
        """Flat dict for ``BENCH_lowering.json`` / the CLI report."""
        g = self.geometry
        return dict(
            backend=self.backend, nmodes=g.nmodes, rank=g.rank, blk=g.blk,
            tile_rows=g.tile_rows, factor_rows=g.factor_rows,
            window_tiles=g.window_tiles, lowered_ok=self.ok,
            mosaic=self.mosaic, seconds=round(self.seconds, 4),
            error=self.error,
        )


def compiled_geometry_ok(geom: Geometry) -> tuple[bool, str]:
    """Is this geometry expressible on the compiled path at all?

    Returns ``(ok, reason)``. The only compiled-vs-interpret geometry
    restriction the kernels carry is the rank-1 block-shape rule on
    ``blk``: XLA tiles a rank-1 int32/fp32 array in 1024 elements and
    Mosaic refuses a ``(blk,)`` block that does not match. Rank,
    tile_rows and windows are padded/tiled into Mosaic-legal shapes by
    construction; what fits VMEM and SMEM is the residency planner's
    question, not a geometry rule.
    """
    if geom.blk % MOSAIC_BLK_MULTIPLE != 0:
        return False, (f"blk={geom.blk} is not a multiple of "
                       f"{MOSAIC_BLK_MULTIPLE}: Mosaic rejects rank-1 "
                       "(blk,) scalar-stream blocks that do not match "
                       "XLA's 1024-element tiling")
    return True, ""


def device_step_args(geom: Geometry, *, mode: int = 0):
    """Abstract (ShapeDtypeStruct) operands for ``mttkrp_device_step``.

    No data is materialized — lowering is shape/dtype-driven, which is
    what lets the full grid stay cheap on CPU.
    """
    cap = geom.nnz_cap
    idx = jax.ShapeDtypeStruct((cap, geom.nmodes), jnp.int32)
    val = jax.ShapeDtypeStruct((cap,), jnp.float32)
    valid = jax.ShapeDtypeStruct((cap,), jnp.bool_)
    factors = [
        jax.ShapeDtypeStruct(
            (geom.rows_cap if w == mode else geom.factor_rows, geom.rank),
            jnp.float32)
        for w in range(geom.nmodes)
    ]
    row_offset = jax.ShapeDtypeStruct((), jnp.int32)
    return idx, val, valid, factors, row_offset


def lower_backend(backend: str, geom: Geometry, *,
                  platform: str = "tpu") -> LoweringResult:
    """Lower one backend at one geometry with ``interpret=False``.

    Uses the AOT trace-then-lower path so the Mosaic pipeline runs even
    when jax's default backend is CPU. Never raises: failures come back
    as ``ok=False`` with the exception message, so a grid sweep reports
    every broken backend instead of stopping at the first.
    """
    idx, val, valid, factors, row_offset = device_step_args(geom)
    t0 = time.perf_counter()
    try:
        lowered = _ops.mttkrp_device_step.trace(
            idx, val, valid, factors, mode=0, rows_cap=geom.rows_cap,
            row_offset=row_offset, blk=geom.blk, tile_rows=geom.tile_rows,
            interpret=False, backend=backend,
        ).lower(lowering_platforms=(platform,))
        text = lowered.as_text()
    except Exception as e:  # noqa: BLE001 — every failure is a result row
        return LoweringResult(
            backend=backend, geometry=geom, ok=False, mosaic=False,
            seconds=time.perf_counter() - t0,
            error=f"{type(e).__name__}: {e}")
    seconds = time.perf_counter() - t0
    mosaic = "tpu_custom_call" in text
    # ref is plain XLA — no Mosaic module expected. Every Pallas backend
    # must actually have produced one, or the "lowering" proved nothing.
    ok = bool(text) and (mosaic or backend == "ref")
    err = "" if ok else "lowered without a tpu_custom_call (Mosaic module)"
    return LoweringResult(backend=backend, geometry=geom, ok=ok,
                          mosaic=mosaic, seconds=seconds, error=err)


def run(geometries=SMOKE_GEOMETRIES, backends=_ops.BACKENDS,
        *, platform: str = "tpu") -> list[LoweringResult]:
    """Lower every backend at every geometry; returns all results."""
    return [lower_backend(b, g, platform=platform)
            for b in backends for g in geometries]


def main(argv=None) -> int:
    """CLI for the CI ``lowering-smoke`` step: 0 iff everything lowers."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.kernels.mttkrp.lowering", description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="the full geometry grid (slow) instead of smoke")
    args = ap.parse_args(argv)
    geometries = FULL_GEOMETRIES if args.full else SMOKE_GEOMETRIES
    results = run(geometries)
    failures = [r for r in results if not r.ok]
    for r in results:
        status = "ok  " if r.ok else "FAIL"
        print(f"{status} {r.backend:28s} {r.geometry.label():32s} "
              f"{r.seconds:6.2f}s"
              + (f"  {r.error}" if r.error else ""))
    n = len(results)
    print(f"lowering {'smoke' if not args.full else 'full'}: "
          f"{n - len(failures)}/{n} (backend, geometry) points lower to "
          f"Mosaic with interpret=False")
    return 1 if failures else 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
