"""Put the benchmark's modules and the program on the path.

Run from the repository root:  python -m pytest bench/tests
"""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (BENCH, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)


import json  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402

TINY_CONFIG = {"name": "tiny", "shape": [61, 47, 83], "nnz": 4000,
               "pattern_seed": 5}
TINY_JOB = {"name": "tiny-job", "rank": 16, "backend": "auto",
            "gather_dtype": "float32"}


def tiny_root(tmp, chips=1, limits_of="nell2-r16"):
    """A copy of the benchmark with one more cell, ``tiny``, added the way
    a later change adds one: a configuration file, a job file, a limits
    file and entries in BENCHMARK.json. No file that is there changes."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    spec = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny", "config": "tiny",
                              "traffic": "tiny-job", "chips": chips,
                              "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (root / "bench/configs/tiny.json").write_text(json.dumps(TINY_CONFIG))
    (root / "bench/traffic/tiny-job.json").write_text(json.dumps(TINY_JOB))
    shutil.copy(os.path.join(BENCH, "limits", limits_of + ".json"),
                root / "bench/limits/tiny.json")
    return root


def run_tiny(root, argv, devices=1, prelude=""):
    """``harness.main`` in a child on the CPU, the chip check skipped."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    code = ("import sys; sys.path[:0] = ['bench', 'src']\n"
            "import harness, work\n"
            "work.PEAKS.setdefault('cpu', work.PEAKS['TPU v5 lite'])\n"
            + prelude +
            f"\nsys.exit(harness.main({argv!r}, require_tpu=False))\n")
    return subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)


def last_json(stdout):
    lines = [ln for ln in stdout.splitlines() if ln.startswith("{")]
    return json.loads(lines[-1]) if lines else None
