"""A 5-mode tensor shaped like FROSTT's vast-2015-mc1-5d, cut down.

The full tensor is 165,427 × 11,374 × 2 × 100 × 89. Here mode 0 and 1 are
cut a hundredfold, and modes 2–4 keep their published widths: a 2-row
mode (whose 2×R factor makes every other mode's normal matrix a
Hadamard product with a rank-2 gram), and 100- and 89-row modes that
each fill one 128-row output tile. One sweep of the production sweep
(``make_als_sweep``) runs in the Pallas interpreter with ``pallas_fused``
forced on every mode, and again with ``auto``; its factors and lambda are
compared with the benchmark's plain reference (``bench/reference.py``,
float64 host algebra around a ``highest``-precision MTTKRP).

Tolerance: the program computes in fp32, so a correct sweep lands within
a few fp32 roundings of the reference, amplified by the solve: the
readings here are 1.0e-6 (factors) and 1.8e-7 (lambda). The program's
own bf16-gather path rounds every gathered factor entry to 8 bits (four
gathers a nonzero) and reads 3.2e-3 and 2.9e-3. ``TOL`` = 1e-4 sits
more than a decade from each, and ``test_bf16_gather_fails_the_tolerance``
shows the second side.
"""
import importlib.util
import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import distributed as dist
from repro.core.cpals import make_als_sweep
from repro.core.flycoo import build_flycoo
from repro.core.tensors import random_sparse_tensor
from repro.kernels.mttkrp import ops as kops
from repro.obs import counters as obs

SHAPE = (1654, 114, 2, 100, 89)
NNZ = 20_000
RANK = 16
TILE_ROWS = 128
TOL = 1e-4

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference", os.path.join(_ROOT, "bench", "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def case():
    t = random_sparse_tensor(SHAPE, NNZ, seed=16)
    rng = np.random.default_rng(16)
    nat = [rng.standard_normal((d, RANK)).astype(np.float32) for d in SHAPE]
    ref_f, ref_lam, _ = _reference().first_sweep(
        t.indices, t.values, SHAPE, nat)
    return t, nat, ref_f, ref_lam


def _slots(ft, rt, n):
    mp = ft.modes[n]
    return (mp.row_perm // mp.rows_cap) * rt.rows_cap[n] \
        + mp.row_perm % mp.rows_cap


def _first_sweep(case, backend, gather_dtype="float32"):
    """One production sweep; natural-row factors, lambda, dispatch."""
    t, nat, _, _ = case
    ft = build_flycoo(t, num_workers=1)
    rt, stream = dist.prepare_runtime(ft, RANK, tile_rows=TILE_ROWS,
                                      gather_dtype=gather_dtype)
    factors = []
    for n, f in enumerate(nat):
        perm = np.zeros((rt.i_pad[n], RANK), np.float32)
        perm[_slots(ft, rt, n)] = f
        factors.append(perm)
    x_norm_sq = np.array([np.sum(t.values.astype(np.float64) ** 2)],
                         np.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), (dist.AXIS,))
    with obs.use_registry() as reg:
        _, out, lam, _ = make_als_sweep(rt, mesh, backend=backend)(
            *stream, x_norm_sq, *factors, np.ones(RANK, np.float32),
            np.bool_(True))
    rungs = {k: v for k, v in reg.snapshot().items()
             if k.startswith("dispatch.backend")}
    got = [np.asarray(f, np.float64)[_slots(ft, rt, n)]
           for n, f in enumerate(out)]
    return got, np.asarray(lam, np.float64), rungs


def _errors(got, lam, case):
    _, _, ref_f, ref_lam = case
    factor_err = max(np.linalg.norm(g - r) / np.linalg.norm(r)
                     for g, r in zip(got, ref_f))
    lam_err = np.linalg.norm(lam - ref_lam) / np.linalg.norm(ref_lam)
    return factor_err, lam_err


@pytest.mark.parametrize("backend", ["pallas_fused", "auto"])
def test_first_sweep_matches_reference(case, backend):
    got, lam, rungs = _first_sweep(case, backend)
    factor_err, lam_err = _errors(got, lam, case)
    assert factor_err < TOL, (backend, factor_err)
    assert lam_err < TOL, (backend, lam_err)
    if backend == "pallas_fused":
        assert rungs == {
            "dispatch.backend{backend=pallas_fused,source=explicit}": 5}


def test_bf16_gather_fails_the_tolerance(case):
    """The tolerance is tight enough to refuse the bf16-gather path."""
    got, lam, _ = _first_sweep(case, "pallas_fused",
                               gather_dtype="bfloat16")
    factor_err, _ = _errors(got, lam, case)
    assert factor_err > 10 * TOL, factor_err


@pytest.mark.parametrize("mode", [2, 4], ids=["2-row", "89-row"])
def test_one_tile_output_mode(case, mode):
    """Every nonzero writes into the one output tile of a 2- or 89-row
    mode; the mode step agrees with a float64 MTTKRP."""
    t, nat, _, _ = case
    order = np.argsort(t.indices[:, mode], kind="stable")
    idx = t.indices[order].astype(np.int32)
    val = t.values[order].astype(np.float32)
    rows_cap = TILE_ROWS
    out = kops.mttkrp_device_step(
        idx, val, np.ones(len(val), bool), [f for f in nat], mode=mode,
        rows_cap=rows_cap, row_offset=0, blk=512, tile_rows=TILE_ROWS,
        interpret=True, backend="pallas_fused")
    want = np.zeros((rows_cap, RANK))
    prod = val[:, None].astype(np.float64)
    for w, f in enumerate(nat):
        if w != mode:
            prod = prod * f.astype(np.float64)[idx[:, w]]
    np.add.at(want, idx[:, mode], prod)
    err = np.abs(np.asarray(out, np.float64) - want).max() / np.abs(want).max()
    assert err < 1e-5, err
    assert not np.any(np.asarray(out)[SHAPE[mode]:])
