"""Paper §IV claim, measured on compiled programs: Dynasor's owner-computes
layout eliminates the dense intermediate-value reduction.

We compile BOTH distributed spMTTKRP programs for 8 workers and parse the
collective ops out of the optimized HLO:

* baseline (nonzero-parallel, ALTO/HiCOO traffic): every mode all-reduces a
  dense (I_pad × R) partial from every worker — the "intermediate values"
  the paper talks about;
* Dynasor: owned output rows are all-gathered once (each row moves once),
  plus the capacity-padded all_to_all of the dynamic remap.

Reported with ring-cost weights (all-reduce moves ≈2× its payload on a
ring; gather/scatter/a2a ≈1×). Runs in a subprocess so the 8-device XLA
flag never leaks into the bench process.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import row, write_bench_json

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax
from jax.sharding import Mesh
from repro.core import distributed as dist
from repro.core.flycoo import build_flycoo
from repro.core.tensors import frostt_like
from repro.launch.hlo_analysis import collective_bytes

out = {}
for name in %TENSORS%:
    t = frostt_like(name, scale=0.25)
    ft = build_flycoo(t, 8)
    rt, (idx, val, mask) = dist.prepare_runtime(ft, rank=%RANK%)
    mesh = Mesh(np.array(jax.devices()), (dist.AXIS,))
    factors = dist.init_factors(ft, rt, seed=0)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    res = {}
    for label, maker, pack in (
        ("dynasor", lambda: dist.make_spmttkrp_all_modes(rt, mesh), (idx, val, mask)),
        ("baseline_allreduce", lambda: dist.make_baseline_all_modes(rt, mesh),
         dist.even_split_pack(ft, rt)),
    ):
        import jax.numpy as jnp
        fn = maker()
        compiled = jax.jit(fn).lower(
            *[sds(np.asarray(x)) for x in pack],
            *[sds(np.asarray(f)) for f in factors]).compile()
        cb = collective_bytes(compiled.as_text())
        kinds = cb["bytes_by_kind"]
        weighted = sum(v * (2.0 if k == "all-reduce" else 1.0)
                       for k, v in kinds.items())
        res[label] = {"by_kind": kinds, "weighted_bytes": weighted}
    out[name] = res
print("JSON" + json.dumps(out))
"""


def run(quick: bool = True, rank: int = 64):
    tensors = ["nell-2", "flickr"] if quick else [
        "nell-2", "nell-1", "flickr", "delicious", "vast"]
    script = _SCRIPT.replace("%TENSORS%", repr(tensors)).replace(
        "%RANK%", str(rank))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    # The child only compiles for virtual CPU devices; the parent may hold
    # the accelerator, which a second process must not try to take.
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=1800)
    rows = []
    for line in proc.stdout.splitlines():
        if line.startswith("JSON"):
            data = json.loads(line[4:])
            for tensor, res in data.items():
                dyn = res["dynasor"]["weighted_bytes"]
                base = res["baseline_allreduce"]["weighted_bytes"]
                rows.append(row(
                    "collective_traffic", tensor=tensor, rank=rank,
                    dynasor_MB=round(dyn / 1e6, 2),
                    baseline_MB=round(base / 1e6, 2),
                    traffic_ratio=round(base / max(dyn, 1), 2),
                    dynasor_kinds=str(res["dynasor"]["by_kind"]),
                    baseline_kinds=str(
                        res["baseline_allreduce"]["by_kind"])))
    if not rows:
        rows = [row("collective_traffic", status="error",
                    stderr=proc.stderr[-300:])]
    write_bench_json("collective_traffic", rows)
    return rows
