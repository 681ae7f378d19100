"""Chip smoke test: distributed CP-ALS on nell-2 at its published widths.

Drives the system's main path once, in one process, on a TPU:
``cp_als_distributed(ft, 16, mesh, backend="auto")`` on a uniform
synthetic tensor with nell-2's published shape (12,100 x 9,200 x 28,800),
three sweeps, and checks it against plain-XLA references on the chip:

  (a) one all-modes MTTKRP through ``make_spmttkrp_all_modes(...,
      backend="auto")``, un-permuted, against ``core.mttkrp.mttkrp`` on the
      same natural-space factors: relative Frobenius error <= 1e-4 in every
      mode, and no nonzero dropped by the remap;
  (b) the fit trace of ``backend="auto"`` against ``backend="segsum"``
      (same FLYCOO tensor, same seed, so the same initial factors): within
      1e-3 in every sweep.

Usage (from the repository root)::

  python chip_smoke.py              # one chip: the whole smoke
  python chip_smoke.py --chips 4    # the 4-device mesh path and its checks
  JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --nnz 20000

Only the nonzero count is cut (see ``NNZ``); shape, distribution and
rank are never cut.
The last line of stdout is ``{"ok": true, "device": {...}}``. Without a
TPU the script exits nonzero and prints no result; ``--rehearse`` runs
the same phases on whatever JAX finds (Pallas interpreted off-TPU) and
ends with a line that is not a result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import distributed as dist  # noqa: E402
from repro.core.cpals import cp_als_distributed  # noqa: E402
from repro.core.flycoo import build_flycoo  # noqa: E402
from repro.core.mttkrp import mttkrp  # noqa: E402
from repro.core.tensors import FROSTT_PROFILES, random_sparse_tensor  # noqa: E402
from repro.kernels.mttkrp import ops as kops  # noqa: E402
from repro.obs import counters as obs  # noqa: E402
from repro.oocore import planner  # noqa: E402
from repro.runtime import execution  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

TENSOR = "nell-2"
SHAPE = FROSTT_PROFILES[TENSOR]["shape"]
PUBLISHED_NNZ = FROSTT_PROFILES[TENSOR]["nnz"]
RANK = 16            # the suites' rank (benchmarks/bench_total_time.py)
SWEEPS = 3
SEED = 0
# The nonzero count is the one cut, and HBM does not force it: by
# compiled.memory_analysis() for a described TPU v5e, the whole jitted
# sweep (make_als_sweep) at nell-2's 76.9M nonzeros needs 11.25 GB on one
# chip and 2.72 GB per device on the 4-device mesh (16 GB each). Time
# does: the resident one-hot gather takes about 2.3 us per nonzero per
# sweep on one v5e chip, so the checks and both fit traces at 76.9M would
# run about 1100 s, and 12M keeps a run near 6 minutes. On four chips the
# cut is what a run that holds four chips costs; 16M is 4M per chip.
NNZ = {1: 12_000_000, 4: 16_000_000}
CUT_REASON = {1: "run time; 76.9M fits one chip's HBM",
              4: "cost of a four-chip run; 76.9M fits four chips' HBM"}
MTTKRP_RTOL = 1e-4
FIT_ATOL = 1e-3
REF_CHUNK = 1 << 21  # nonzeros per reference MTTKRP call (bounds its memory)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_s = [0.0]


def _on_duration(event, duration, **_kwargs):
    if event == _COMPILE_EVENT:
        _compile_s[0] += duration


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, flush=True)


def dispatch_report(ft, rt) -> list[str]:
    """Each mode's ``auto`` resolution, as the ``dispatch.backend`` counter
    records it for the arguments the mode step passes."""
    backends = []
    for n in range(rt.nmodes):
        factor_rows = tuple(rt.i_pad[w] for w in range(rt.nmodes) if w != n)
        num_blocks = kops.n_pad_for(rt.nnz_cap, rt.rows_cap[n], rt.blk,
                                    rt.tile_rows) // rt.blk
        with obs.use_registry() as reg:
            backend = kops.select_backend(
                "auto", nmodes=rt.nmodes, rank=rt.rank, blk=rt.blk,
                tile_rows=rt.tile_rows, factor_rows=factor_rows,
                num_blocks=num_blocks)
        (key,) = [k for k in reg.snapshot() if k.startswith("dispatch.")]
        plan = planner.plan_residency(
            nmodes=rt.nmodes, rank=rt.rank, blk=rt.blk,
            tile_rows=rt.tile_rows, factor_rows=factor_rows,
            num_blocks=num_blocks)
        log(f"mode {n}: backend={backend} ({key}; planner VMEM "
            f"{plan.vmem_bytes} B <= budget {plan.vmem_budget} B; "
            f"factor rows {factor_rows}, blk={rt.blk}, "
            f"tile_rows={rt.tile_rows}, blocks={num_blocks})")
        backends.append(backend)
    return backends


def reference_mttkrp(t, nat_factors, mode: int, device):
    """Plain-XLA MTTKRP on one device, summed over nonzero chunks."""
    out = jnp.zeros((SHAPE[mode], RANK), jnp.float32, device=device)
    facs = [jax.device_put(f, device) for f in nat_factors]
    for lo in range(0, t.nnz, REF_CHUNK):
        idx = jax.device_put(t.indices[lo:lo + REF_CHUNK].astype(np.int32),
                             device)
        val = jax.device_put(t.values[lo:lo + REF_CHUNK], device)
        out = out + mttkrp(idx, val, facs, mode, SHAPE[mode])
    return np.asarray(out)


def check_mttkrp(t, ft, mesh, chips: int) -> None:
    """Comparison (a): one all-modes MTTKRP, auto vs plain XLA."""
    rt, (idx, val, mask) = dist.prepare_runtime(ft, RANK)
    log(f"runtime: blk={rt.blk} tile_rows={rt.tile_rows} "
        f"nnz_cap/worker={rt.nnz_cap} rows_cap={rt.rows_cap}")
    backends = dispatch_report(ft, rt)
    if execution.compiles() and not all(b.startswith("pallas")
                                        for b in backends):
        fail(f"auto resolved to a non-Pallas backend: {backends}")
    factors = dist.init_factors(ft, rt, seed=SEED)
    sharded = NamedSharding(mesh, P(dist.AXIS))
    replicated = NamedSharding(mesh, P())
    args = [jax.device_put(a, sharded) for a in (idx, val, mask)]
    args += [jax.device_put(f, replicated) for f in factors]
    for name, a in zip(("idx", "val", "mask"), args):
        devs = sorted(s.device.id for s in a.addressable_shards)
        log(f"{name} shards on devices {devs}, shard shape "
            f"{a.addressable_shards[0].data.shape}")
        if len(set(devs)) != chips:
            fail(f"{name} sits on {len(set(devs))} devices, not {chips}")
    fn = dist.make_spmttkrp_all_modes(rt, mesh, backend="auto")
    with obs.use_registry() as reg:
        t0 = time.perf_counter()
        compiled = fn.lower(*args).compile()
        log(f"all-modes MTTKRP compile: {time.perf_counter() - t0:.1f} s")
    traced = {k: v for k, v in reg.snapshot().items()
              if k.startswith("dispatch.")}
    log(f"dispatch.backend counters while tracing: {traced}")
    t0 = time.perf_counter()
    outs, (idx2, _, _), diags = compiled(*args)
    jax.block_until_ready(outs)
    log(f"all-modes MTTKRP run (informational): "
        f"{time.perf_counter() - t0:.3f} s")
    devs = sorted(s.device.id for s in idx2.addressable_shards)
    log(f"remapped idx shards on devices {devs}")
    if len(set(devs)) != chips:
        fail(f"remapped stream sits on {len(set(devs))} devices, not {chips}")
    dropped = int(diags["dropped"])
    log(f"dropped={dropped}")
    if dropped:
        fail(f"the remap dropped {dropped} nonzeros")
    nat = [dist.unpermute_factor(ft, rt, n, factors[n])
           for n in range(rt.nmodes)]
    ref_device = jax.devices()[0]
    for n in range(rt.nmodes):
        got = dist.unpermute_factor(ft, rt, n, np.asarray(outs[n]))
        ref = reference_mttkrp(t, nat, n, ref_device)
        err = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
        log(f"mode {n}: MTTKRP rel Frobenius error {err:.3e} "
            f"(bound {MTTKRP_RTOL:g})")
        if not err <= MTTKRP_RTOL:
            fail(f"mode {n} MTTKRP error {err:.3e} > {MTTKRP_RTOL:g}")


def check_fits(ft, mesh) -> None:
    """Comparison (b): fit trace, backend auto vs segsum."""
    fits, factors = {}, {}
    for backend in ("auto", "segsum"):
        compile0 = _compile_s[0]
        t0 = time.perf_counter()
        res = cp_als_distributed(ft, RANK, mesh, iters=SWEEPS, seed=SEED,
                                 tol=0.0, backend=backend, table=None)
        wall = time.perf_counter() - t0
        comp = _compile_s[0] - compile0
        fits[backend] = res.fits
        factors[backend] = [f * res.lam for f in res.factors]
        log(f"cp_als_distributed backend={backend}: fits {res.fits}")
        log(f"  {SWEEPS} sweeps {wall:.1f} s wall, of which XLA/Mosaic "
            f"compile {comp:.1f} s; sweep time (informational) "
            f"{(wall - comp) / SWEEPS:.3f} s")
        if len(res.fits) != SWEEPS or not np.all(np.isfinite(res.fits)):
            fail(f"backend={backend}: fits {res.fits}")
    gap = float(np.max(np.abs(np.subtract(fits["auto"], fits["segsum"]))))
    log(f"fit gap auto vs segsum: {gap:.3e} (bound {FIT_ATOL:g})")
    if not gap <= FIT_ATOL:
        fail(f"fit gap {gap:.3e} > {FIT_ATOL:g}")
    # A uniform random tensor has almost no low-rank structure, so both
    # fits sit near zero; the λ-scaled factors show more of the agreement.
    diffs = [float(np.linalg.norm(a - s) / np.linalg.norm(s))
             for a, s in zip(factors["auto"], factors["segsum"])]
    log("factor rel difference auto vs segsum per mode (informational): "
        + ", ".join(f"{d:.3e}" for d in diffs))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--nnz", type=int, default=None,
                    help="override the nonzero count (rehearsals)")
    ap.add_argument("--rehearse", action="store_true",
                    help="run off-TPU too (Pallas interpreted); prints no "
                         "result")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse:
        if dev.platform != "tpu":
            print(f"no TPU: jax.devices()[0].platform is {dev.platform!r}",
                  file=sys.stderr)
            return 2
        execution.set_execution_mode("compiled")
    if len(devices) < args.chips:
        print(f"need {args.chips} devices, jax finds {len(devices)}",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"execution_mode={execution.get_execution_mode()}")

    nnz = args.nnz if args.nnz is not None else NNZ[args.chips]
    log(f"tensor: {TENSOR} shape={SHAPE} uniform, R={RANK}, "
        f"{SWEEPS} sweeps, seed={SEED}, {args.chips} chip(s)")
    if nnz < PUBLISHED_NNZ:
        log(f"reduced: nnz {PUBLISHED_NNZ / 1e6:.1f}M -> {nnz} "
            f"({CUT_REASON[args.chips]})")
    t0 = time.perf_counter()
    t = random_sparse_tensor(SHAPE, nnz, seed=SEED)
    ft = build_flycoo(t, num_workers=args.chips)
    log(f"set-up: {t.nnz} nonzeros generated and FLYCOO-built in "
        f"{time.perf_counter() - t0:.1f} s (host)")
    mesh = Mesh(np.array(devices[:args.chips]), (dist.AXIS,))

    check_mttkrp(t, ft, mesh, args.chips)
    check_fits(ft, mesh)

    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               if d.memory_stats() else 0 for d in devices[:args.chips])
    log(f"peak_bytes_in_use: {peak}")
    if args.rehearse:
        log("rehearsal passed (not a chip run)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
