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
