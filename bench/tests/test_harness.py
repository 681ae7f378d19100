"""The harness is driven by data, and refuses to run without a chip."""
import json
import os
import subprocess
import sys

from conftest import BENCH, ROOT, last_json, run_tiny, tiny_root


def test_new_cell_runs_without_code_edits(tmp_path):
    root = tiny_root(tmp_path)
    for dirpath, _, files in os.walk(root / "bench"):
        for name in files:
            rel = os.path.relpath(os.path.join(dirpath, name), root / "bench")
            if rel in ("configs/tiny.json", "traffic/tiny-job.json",
                       "limits/tiny.json"):
                continue
            with open(os.path.join(dirpath, name), "rb") as a, \
                    open(os.path.join(BENCH, rel), "rb") as b:
                assert a.read() == b.read(), rel
    proc = run_tiny(root, ["--workload", "tiny", "--seed", "2147483659",
                           "--seconds", "1", "--trace", "0"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_json(proc.stdout)
    assert line["correct"] is True, proc.stderr[-3000:]
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"sweep_s", "preprocess_s", "peak_hbm_gb",
                                    "setup_s"}
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert "dispatch.backend" in proc.stderr


def test_traced_run_reports_per_layer_metrics(tmp_path):
    root = tiny_root(tmp_path)
    proc = run_tiny(root, ["--workload", "tiny", "--seed", "11",
                           "--seconds", "0.5", "--trace", "1"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_json(proc.stdout)
    assert line["correct"] is True
    # The CPU has no TPU planes: only the metric that needs none remains.
    assert set(line["metrics"]) == {"sweep_roofline"}
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run_cell.py", "--workload", "nell2-r16",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert last_json(proc.stdout) is None
    assert "no TPU" in proc.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    import shutil
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cmd = [sys.executable] + spec["command"][1:] + [
        "--workload", "nell2-r16", "--seed", "1", "--seconds", "1",
        "--trace", "0"]
    proc = subprocess.run(cmd, cwd=tmp_path, capture_output=True, text=True,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          timeout=300)
    assert proc.returncode != 0
    assert last_json(proc.stdout) is None


def test_window_keeps_no_device_array_from_before_it_or_its_first_sweep(
        tmp_path):
    """Three sweeps, each taking one second on a stand-in clock. As the
    third starts, the stream, factors and lambda from before the window
    and the first sweep's outputs are gone, and the arrays with a
    dimension (stream, factors, lambda, norm) hold no more bytes than as
    the first started: ``peak_hbm_gb`` does not grow with the number of
    sweeps in the window. Scalars are left out: the previous sweep's fit
    is named until the next sweep returns."""
    code = """
import json, types, weakref
import jax, numpy as np
cell = harness.load_cell('tiny')
mesh, devs = harness.open_mesh(cell, '.', require_tpu=False)
job = harness.prepare(cell, harness.make_inputs(cell, 3000000019), mesh,
                      cell.traffic['gather_dtype'])
compiled, _ = harness.compile_sweep(job, cell.traffic['backend'])
held = [weakref.ref(a) for a in (*job.stream, *job.factors, job.lam)]
clock = [0.0]
harness.time = types.SimpleNamespace(perf_counter=lambda: clock[0])
live, still_held = [], []
def sweep(*args):
    live.append(sum(a.nbytes for a in jax.live_arrays() if a.ndim))
    if len(live) == 3:
        still_held.append(sum(r() is not None for r in held))
    out = compiled(*args)
    if len(live) == 1:
        held.extend(weakref.ref(a) for a in jax.tree.leaves(out[:3]))
    clock[0] += 1.0
    return out
window = harness.run_window(sweep, job, 3.0)
values = harness.readings(job, window, devs[0])
print('RESULT', json.dumps({
    'sweeps': window.sweeps, 'live': live, 'still_held': still_held,
    'job_emptied': [x is None for x in (job.stream, job.factors, job.lam)],
    'first_on_host': all(isinstance(a, np.ndarray)
                         for a in jax.tree.leaves(window.first)),
    'stream_mismatch': values['stream_mismatch'],
    'correct': harness.judge(values, cell.limits)[0]}))
sys.exit(0)
"""
    proc = run_tiny(tiny_root(tmp_path), [], prelude=code)
    assert proc.returncode == 0, proc.stderr[-3000:]
    (got,) = [json.loads(ln.split(" ", 1)[1])
              for ln in proc.stdout.splitlines() if ln.startswith("RESULT")]
    assert got["sweeps"] == 3
    assert got["still_held"] == [0]
    first_bytes, _, third_bytes = got["live"]
    assert third_bytes <= first_bytes, got["live"]
    assert got["job_emptied"] == [True, True, True]
    assert got["first_on_host"] is True
    assert got["stream_mismatch"] == 0
    assert got["correct"] is True
