"""repro.tune — measured autotuning for the Dynasor MTTKRP runtime.

The ``auto`` dispatch in ``kernels.mttkrp.ops`` and the exchange sizing
in ``core.distributed`` were originally driven by static models (a VMEM
working-set estimate and a worst-case bucket capacity). This package
replaces both with *measurements*:

  * :mod:`repro.tune.microbench` — times every backend (the
    ``kernels.mttkrp.ops.BACKENDS`` family — fused, rank-tiled fused,
    bf16-gather fused, the in-kernel-gather trio, ref —
    plus ``segsum``) over a grid of ``(nmodes, rank, blk, tile_rows,
    density)`` on the current host;
  * :mod:`repro.tune.table` — the versioned JSON calibration table
    those timings are saved into (``experiments/tune/``), with a
    registry that falls back deterministically to the static model when
    no table exists;
  * :mod:`repro.tune.model` — a cost model that interpolates the table
    to unseen configurations and plans per-mode
    ``(backend, blk, tile_rows)`` for ``DynasorRuntime``;
  * :mod:`repro.tune.cli` — ``python -m repro.tune calibrate|show|check``.

Tuning workflow
---------------

1. **Calibrate once per host** (writes ``experiments/tune/*.json``)::

       python -m repro.tune calibrate --quick     # or --full
       python -m repro.tune show                  # inspect the table
       python -m repro.tune check                 # dispatch == measured argmin

2. **Decompose with a tuned runtime** — the table steers the backend
   per mode, the tile shapes, and (independently of the table) each
   remap exchange is sized to its own transition::

       from repro.core import distributed as dist
       from repro.core.cpals import cp_als_distributed
       from repro.tune.table import find_table

       table = find_table()                       # None -> static model
       rt, packed = dist.prepare_runtime(ft, rank=32, table=table)
       res = cp_als_distributed(ft, 32, mesh, backend="auto", table=table)

3. **Single calls** — pass the table straight to the dispatch::

       from repro.kernels.mttkrp import ops as kops
       kops.select_backend("auto", nmodes=4, rank=64, table=table)

With ``table=None`` every decision is bit-identical to the static
model, so untuned hosts behave exactly as before calibration.
"""
from .microbench import (BACKENDS, GridPoint, calibrate, default_grid,
                         stub_measure)
from .model import CostModel, compare_dispatch, plan_modes
from .table import (AUTO_BACKENDS, COMPAT_SCHEMA_VERSIONS, OPS_BACKENDS,
                    SCHEMA_VERSION, CalibrationEntry, CalibrationTable,
                    SchemaVersionError, aggregate_timings,
                    default_table_path, find_table, key_factor_rows,
                    load_table, measured_best)

__all__ = [
    "BACKENDS",
    "OPS_BACKENDS",
    "AUTO_BACKENDS",
    "COMPAT_SCHEMA_VERSIONS",
    "GridPoint",
    "calibrate",
    "default_grid",
    "stub_measure",
    "key_factor_rows",
    "CostModel",
    "compare_dispatch",
    "plan_modes",
    "SCHEMA_VERSION",
    "CalibrationEntry",
    "CalibrationTable",
    "SchemaVersionError",
    "aggregate_timings",
    "measured_best",
    "default_table_path",
    "find_table",
    "load_table",
]
