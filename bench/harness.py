"""One run of one benchmark cell: set-up, the measured window, the check.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything a
cell needs is found by name: its configuration file (the entry of
``configs`` it names), its job under ``bench/traffic/<traffic>.json``,
its limits under ``bench/limits/<workload>.json`` and each per-layer
metric's reader under ``bench/metrics/<name>.py``. Adding a cell adds
files and entries; no code changes.

The window drives the production sweep: the jitted function that
``repro.core.cpals.make_als_sweep(rt, mesh, backend=...)`` returns, looped
as ``cp_als_distributed`` loops it, carrying the remapped stream, the
factors and lambda from one sweep to the next and fencing each sweep by
reading its fit. Set-up compiles it ahead of time, from JAX's persistent
compilation cache after a cell's first run.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

import devtrace
import reference
import tensorgen

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
NO_DEVICE = 3   # exit code: no TPU, or fewer chips than the cell needs
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """Find a cell and everything it names, by name."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[workload]
    (cfg,) = [c for c in spec["configs"] if c["name"] == w["config"]]
    bench = os.path.join(root, "bench")

    def _json(*parts):
        with open(os.path.join(*parts)) as f:
            return json.load(f)

    return Cell(
        name=workload, chips=int(w["chips"]),
        config=_json(root, cfg["file"]),
        traffic=_json(bench, "traffic", w["traffic"] + ".json"),
        limits=_json(bench, "limits", workload + ".json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)])


class CompileCounter:
    """Counts XLA compiles and persistent-cache hits as JAX reports them."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1


@dataclasses.dataclass
class Job:
    """A prepared decomposition: the program's state on the chip."""

    inputs: tensorgen.Inputs
    ft: object
    rt: object
    mesh: object
    stream: tuple            # (idx, val, mask) on the mesh
    factors: list            # replicated, permuted row space
    lam: object
    x_norm_sq: object
    flags: tuple             # sweep0 = True, False
    preprocess_s: float


def make_inputs(cell: Cell, seed: int) -> tensorgen.Inputs:
    c = cell.config
    return tensorgen.make_inputs(
        c["shape"], int(c["nnz"]), int(cell.traffic["rank"]), seed=seed,
        pattern_seed=c.get("pattern_seed"), intervals=c.get("intervals"))


def slot_map(ft, rt, mode: int) -> np.ndarray:
    """Runtime row slot of each natural row of ``mode``."""
    mp = ft.modes[mode]
    return (mp.row_perm // mp.rows_cap) * rt.rows_cap[mode] \
        + mp.row_perm % mp.rows_cap


def prepare(cell: Cell, inputs: tensorgen.Inputs, mesh,
            gather_dtype: str) -> Job:
    """FLYCOO build, runtime metadata and the copy to the chip."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import distributed as dist
    from repro.core.flycoo import build_flycoo
    from repro.core.tensors import SparseTensor

    sharded = NamedSharding(mesh, P(dist.AXIS))
    replicated = NamedSharding(mesh, P())
    rank = int(cell.traffic["rank"])
    t = SparseTensor(inputs.coords, inputs.values, inputs.shape)
    t0 = time.perf_counter()
    ft = build_flycoo(t, num_workers=cell.chips)
    rt, stream = dist.prepare_runtime(ft, rank, gather_dtype=gather_dtype)
    stream = tuple(jax.device_put(a, sharded) for a in stream)
    jax.block_until_ready(stream)
    preprocess_s = time.perf_counter() - t0
    factors = []
    for n, nat in enumerate(inputs.factors):
        perm = np.zeros((rt.i_pad[n], rank), np.float32)
        perm[slot_map(ft, rt, n)] = nat
        factors.append(jax.device_put(perm, replicated))
    x_norm_sq = np.broadcast_to(
        np.float32(np.sum(inputs.values.astype(np.float64) ** 2)),
        (cell.chips,)).copy()
    return Job(inputs, ft, rt, mesh, stream, factors,
               jax.device_put(np.ones(rank, np.float32), replicated),
               jax.device_put(x_norm_sq, sharded),
               (jax.device_put(np.bool_(True), replicated),
                jax.device_put(np.bool_(False), replicated)),
               preprocess_s)


def compile_sweep(job: Job, backend: str):
    """AOT-compile the production sweep; returns it and the rungs chosen."""
    from repro.core.cpals import make_als_sweep
    from repro.obs import counters as obs

    sweep = make_als_sweep(job.rt, job.mesh, backend=backend)
    with obs.use_registry() as reg:
        compiled = sweep.lower(*job.stream, job.x_norm_sq, *job.factors,
                               job.lam, job.flags[0]).compile()
    rungs = {k: v for k, v in reg.snapshot().items()
             if k.startswith("dispatch.")}
    return compiled, rungs


@dataclasses.dataclass
class Window:
    sweeps: int
    seconds: float           # start of the window to the end of the last sweep
    sweep_times: list
    fits: list
    first: tuple             # (factors, lam) after the first sweep, on host
    stream: tuple            # the stream after the last sweep, on chip


def run_window(compiled, job: Job, seconds: float) -> Window:
    """Sweeps back to back; none starts after ``seconds``; one always runs.

    The window takes the stream, factors and lambda off ``job``, and keeps
    the first sweep's factors for the check on the host, so that a sweep's
    inputs are freed once it has returned their successors: the device
    holds the same arrays whether one sweep or many fit in ``seconds``,
    and ``peak_hbm_gb`` reads the program's peak alone.
    """
    import jax

    (idx, val, mask), factors, lam = job.stream, job.factors, job.lam
    job.stream = job.factors = job.lam = None
    times, fits, first = [], [], None
    with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
        t0 = time.perf_counter()
        while True:
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation(devtrace.SWEEP_SPAN):
                (idx, val, mask), factors, lam, fit = compiled(
                    idx, val, mask, job.x_norm_sq, *factors, lam,
                    job.flags[0 if first is None else 1])
                fits.append(float(fit))   # the fence
            t1 = time.perf_counter()
            times.append(t1 - ts)
            if first is None:
                first = jax.device_get((factors, lam))
            if t1 - t0 >= seconds:
                break
    return Window(len(times), t1 - t0, times, fits, first, (idx, val, mask))


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# -- the comparison that decides ``correct`` ---------------------------------

def stream_mismatch(job: Job, stream) -> int:
    """Nonzeros the carried stream holds other than the tensor's own.

    The stream's valid entries are mapped back to natural coordinates and
    compared, as a set of (coordinate, value bits) pairs, with the tensor
    the benchmark made: 0 means every nonzero is there exactly once with
    its value; otherwise the count of pairs that differ.
    """
    ft, rt, inp = job.ft, job.rt, job.inputs
    idx, val, mask = (np.asarray(a) for a in stream)
    mask = mask.reshape(-1)
    idx = idx.reshape(-1, idx.shape[-1])[mask]
    val = val.reshape(-1)[mask]
    nat = np.empty(idx.shape, np.int64)
    for n in range(len(inp.shape)):
        mp = ft.modes[n]
        s = idx[:, n].astype(np.int64)
        local = s % rt.rows_cap[n]
        ok = local < mp.rows_cap
        flat = np.where(ok, (s // rt.rows_cap[n]) * mp.rows_cap + local, 0)
        nat[:, n] = np.where(ok, mp.row_unperm[flat], -1)
    bad = int(np.sum(np.any((nat < 0) | (nat >= np.array(inp.shape)), axis=1)))
    nat = np.clip(nat, 0, np.array(inp.shape) - 1)

    def pairs(coords, values):
        key = np.ravel_multi_index(tuple(coords.T), inp.shape)
        bits = values.astype(np.float32).view(np.int32)
        order = np.lexsort((bits, key))
        return key[order], bits[order]

    got, want = pairs(nat, val), pairs(inp.coords.astype(np.int64),
                                       inp.values)
    if got[0].size != want[0].size:
        return bad + abs(int(got[0].size) - int(want[0].size)) + 1
    return bad + int(np.sum((got[0] != want[0]) | (got[1] != want[1])))


def readings(job: Job, window: Window, ref_device) -> dict:
    """Each number read, from what the timed sweeps produced.

    ``factor_err`` is the largest relative Frobenius gap, over the modes,
    between the first sweep's factors and the reference's, both with unit
    columns as the sweep leaves them; ``lam_err`` is the relative gap of
    lambda, the last mode's column norms, and ``fit_gap`` that of the fit.
    """
    ft, rt, inp = job.ft, job.rt, job.inputs
    factors, lam = window.first
    got = [np.asarray(f, np.float64)[slot_map(ft, rt, n)]
           for n, f in enumerate(factors)]
    mismatch = stream_mismatch(job, window.stream)
    ref_f, ref_lam, ref_fit = reference.first_sweep(
        inp.coords, inp.values, inp.shape, inp.factors, ref_device)
    err = max(float(np.linalg.norm(g - r) / np.linalg.norm(r))
              for g, r in zip(got, ref_f))
    lam = np.asarray(lam, np.float64)
    lam_err = float(np.linalg.norm(lam - ref_lam) / np.linalg.norm(ref_lam))
    return {"factor_err": err, "lam_err": lam_err,
            "fit_gap": float(abs(window.fits[0] - ref_fit)),
            "stream_mismatch": float(mismatch)}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    checks = {k: {"value": values[k], "limit": float(limits[k])}
              for k in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


# -- per-layer metrics --------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Context:
    """What a per-layer metric's reader reads."""

    trace: devtrace.Trace
    sweeps: int              # sweeps inside the traced window
    window_s: float          # host clock, traced window
    nnz: int
    shape: tuple
    rank: int
    chips: int
    device_kind: str


def load_reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(cell: Cell, ctx: Context) -> dict:
    """Each reader's number; one that finds nothing to read is left out,
    and on a trace with device operations that is said on standard error."""
    out = {}
    for m in cell.per_layer:
        value = load_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
        elif any(ctx.trace.devices.values()):
            log(f"ERROR: per-layer metric {m['name']} matched no operation "
                f"of a device trace that holds "
                f"{sum(map(len, ctx.trace.devices.values()))}; it is left "
                f"out of the result")
    return out


# -- the run ------------------------------------------------------------------

def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def open_mesh(cell: Cell, root: str = ROOT, require_tpu: bool = True):
    """The cell's chips as a one-axis mesh, and the devices; ``(None,
    None)`` without a TPU or with fewer chips than the cell asks for."""
    sys.path.insert(0, os.path.join(root, "src"))
    import jax
    from jax.sharding import Mesh

    from repro.core import distributed as dist
    from repro.runtime import execution
    from repro.runtime.compile_cache import enable_compile_cache

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        log(f"no TPU: JAX finds {devices[0].platform!r} devices")
        return None, None
    if len(devices) < cell.chips:
        log(f"{cell.name} needs {cell.chips} chips, JAX finds {len(devices)}")
        return None, None
    if devices[0].platform == "tpu":
        execution.set_execution_mode("compiled")
    log(f"compile cache: {enable_compile_cache()}")
    devs = devices[:cell.chips]
    return Mesh(np.array(devs), (dist.AXIS,)), devs


def main(argv=None, *, require_tpu: bool = True, t_start: float | None = None,
         root: str = ROOT) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    cell = load_cell(args.workload, root)
    mesh, devs = open_mesh(cell, root, require_tpu)
    if mesh is None:
        return NO_DEVICE
    counter = CompileCounter()
    traffic = cell.traffic

    inputs = make_inputs(cell, args.seed)
    job = prepare(cell, inputs, mesh, traffic["gather_dtype"])
    compiled, rungs = compile_sweep(job, traffic["backend"])
    setup_s = time.perf_counter() - t_start
    log(f"dispatch: {json.dumps(rungs, sort_keys=True)}")
    log(f"runtime: nnz_cap={job.rt.nnz_cap} rows_cap={job.rt.rows_cap} "
        f"bucket_caps={job.rt.bucket_caps} blk={job.rt.blk}")
    log(f"set-up {setup_s:.3f} s (preprocess {job.preprocess_s:.3f} s), "
        f"compiles {counter.compiles} ({counter.compile_s:.1f} s), "
        f"cache hits {counter.cache_hits}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    compiles0 = counter.compiles
    try:
        if trace_dir:
            with devtrace.capture(trace_dir):
                window = run_window(compiled, job, args.seconds)
        else:
            window = run_window(compiled, job, args.seconds)
        in_window = counter.compiles - compiles0
        peak = peak_bytes(devs)
        log(f"window: {window.sweeps} sweeps in {window.seconds:.4f} s, "
            f"first ones {[round(s, 4) for s in window.sweep_times[:8]]}, "
            f"fits {window.fits[:8]}, compiles inside {in_window}")
        dev = devs[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devs), "memory_peak_bytes": peak}
        result = {}
        if trace_dir:
            trace = devtrace.load(devtrace.find_xplane(trace_dir))
            c = cell.config
            ctx = Context(trace, window.sweeps, window.seconds, int(c["nnz"]),
                          tuple(c["shape"]), int(traffic["rank"]), cell.chips,
                          dev.device_kind)
            metrics = per_layer(cell, ctx)
            device.update(busy_s=devtrace.busy_s(trace),
                          window_s=trace.window_s)
            result["breakdown"] = devtrace.breakdown(trace)
        else:
            e2e = {"sweep_s": window.seconds / window.sweeps,
                   "preprocess_s": job.preprocess_s,
                   "peak_hbm_gb": peak / 1e9, "setup_s": setup_s}
            units = {m["name"]: m["unit"] for m in cell.end_to_end}
            metrics = {k: {"value": e2e[k], "unit": u}
                       for k, u in units.items()}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    del compiled
    values = readings(job, window, devs[0])
    ok, checks = judge(values, cell.limits)
    for k in sorted(set(values) - set(checks)):
        log(f"reading {k}: {values[k]!r} (not compared)")
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    line = {"correct": ok, "attempted": window.sweeps,
            "failed": 0 if ok else window.sweeps, "metrics": metrics,
            "device": device, **result, "checks": checks}
    print(json.dumps(line), flush=True)
    return 0
