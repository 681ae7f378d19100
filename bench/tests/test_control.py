"""The check fails its control and every fault the timed path can have.

At a size a test run holds: the ``tiny`` cell (3 modes, 4,000 nonzeros,
R=16) under the limits of ``nell2-r16``, on the CPU with the Pallas
kernels interpreted. The control is the program's own lower-precision
path, bf16 factor-row gathers; the faults are planted in the program
underneath a whole run of the harness.
"""
import pytest

from conftest import last_json, run_tiny, tiny_root

ARGS = ["--workload", "tiny", "--seed", "424242", "--seconds", "0.2",
        "--trace", "0"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("control"))


def test_program_passes_and_control_fails(root):
    code = """
import calibrate, json
cell = harness.load_cell('tiny')
mesh, devs = harness.open_mesh(cell, '.', require_tpu=False)
for side, dtype in (('program', 'float32'), ('control', 'bfloat16')):
    rows = calibrate.side_readings(cell, mesh, [5, 6, 7], dtype, devs[0], side)
    print('RESULT', side, json.dumps([r['ok'] for r in rows]))
sys.exit(0)
"""
    proc = run_tiny(root, ARGS, prelude=code)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = {ln.split()[1]: ln.split(" ", 2)[2]
           for ln in proc.stdout.splitlines() if ln.startswith("RESULT")}
    assert got == {"program": "[true, true, true]",
                   "control": "[false, false, false]"}, proc.stdout


FAULTS = {
    # The sweep hands its state back unchanged.
    "state_unchanged": """
import jax, jax.numpy as jnp
from repro.core import cpals
def frozen(rt, mesh, backend='auto'):
    return jax.jit(lambda i, v, m, x, *rest: (
        (i, v, m), list(rest[:-2]), rest[-2], jnp.float32(0)))
cpals.make_als_sweep = frozen
""",
    # Half of the nonzeros left out of every mode's MTTKRP.
    "half_left_out": """
import jax.numpy as jnp
from repro.core import distributed as dist
inner = dist.device_mttkrp
def half(idx, val, mask, *a, **k):
    keep = jnp.arange(mask.shape[0]) < mask.shape[0] // 2
    return inner(idx, val, mask & keep, *a, **k)
dist.device_mttkrp = half
""",
    # One MTTKRP answer altered where the kernel produces it.
    "answer_altered": """
from repro.core import distributed as dist
inner = dist.device_mttkrp
def altered(*a, **k):
    return inner(*a, **k).at[0, 0].add(1.0)
dist.device_mttkrp = altered
""",
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(root, fault):
    proc = run_tiny(root, ARGS, prelude=FAULTS[fault])
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last_json(proc.stdout)["correct"] is False


def test_exchange_left_out_is_not_correct(tmp_path):
    """On four devices, the remap's all-to-all replaced by a no-op."""
    root = tiny_root(tmp_path, chips=4, limits_of="nell2-r16")
    prelude = """
from repro.core import remap
remap.exchange = lambda buckets, mask, axis: (buckets, mask)
"""
    sound = run_tiny(root, ARGS, devices=4)
    assert sound.returncode == 0, sound.stderr[-3000:]
    assert last_json(sound.stdout)["correct"] is True
    proc = run_tiny(root, ARGS, devices=4, prelude=prelude)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_json(proc.stdout)
    assert line["correct"] is False
    assert line["checks"]["stream_mismatch"]["value"] > 0
