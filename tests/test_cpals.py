"""CP-ALS (paper Alg. 1): convergence + exact recovery of low-rank truth."""
import itertools

import numpy as np
import pytest

from repro.core.cpals import cp_als
from repro.core.tensors import SparseTensor, random_sparse_tensor


def dense_lowrank_coo(shape, rank, seed=0):
    rng = np.random.default_rng(seed)
    facs = [rng.standard_normal((d, rank)) for d in shape]
    dense = np.einsum("ir,jr,kr->ijk", *facs)
    idx = np.array(list(itertools.product(*[range(d) for d in shape])),
                   dtype=np.int32)
    return SparseTensor(idx, dense.reshape(-1).astype(np.float32),
                        shape), dense


@pytest.mark.slow
def test_exact_recovery_rank4():
    t, dense = dense_lowrank_coo((16, 12, 10), 4, seed=0)
    res = cp_als(t, rank=4, iters=40, seed=1)
    assert res.fit > 0.999, res.fits
    rec = np.einsum("r,ir,jr,kr->ijk", res.lam, *res.factors)
    rel = np.linalg.norm(rec - dense) / np.linalg.norm(dense)
    assert rel < 1e-2


def test_fit_nondecreasing_after_warmup():
    t, _ = dense_lowrank_coo((12, 10, 8), 3, seed=2)
    res = cp_als(t, rank=3, iters=20, seed=3, tol=0.0)
    fits = np.array(res.fits)
    assert np.all(np.diff(fits[1:]) > -1e-3), fits


def test_fit_bounded_and_finite_on_random_tensor():
    t = random_sparse_tensor((30, 20, 10), 500, seed=4)
    res = cp_als(t, rank=8, iters=8, seed=5)
    assert np.isfinite(res.fit)
    assert res.fit <= 1.0 + 1e-6
    for n, f in enumerate(res.factors):
        assert f.shape == (t.shape[n], 8)
        assert np.all(np.isfinite(f))


def test_four_mode_tensor():
    t = random_sparse_tensor((8, 7, 6, 5), 300, seed=6)
    res = cp_als(t, rank=4, iters=5, seed=7)
    assert np.isfinite(res.fit)
    assert len(res.factors) == 4


def test_cp_als_distributed_refuses_worker_mesh_mismatch():
    # An 8-worker FLYCOO tensor on a 1-device mesh would run with the
    # wrong owner layout; the driver must refuse it up front.
    import jax
    from jax.sharding import Mesh

    from repro.core import distributed as dist
    from repro.core.cpals import cp_als_distributed
    from repro.core.flycoo import build_flycoo

    t = random_sparse_tensor((20, 16, 12), 200, seed=0)
    ft = build_flycoo(t, 8, m_bounds=(2, 8), g_bounds=(8, 64))
    mesh = Mesh(np.array(jax.devices()[:1]), (dist.AXIS,))
    with pytest.raises(ValueError, match="8 workers"):
        cp_als_distributed(ft, 4, mesh, iters=1)
