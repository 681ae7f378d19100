"""The generator: exact nnz, and the same static shapes for every seed."""
import numpy as np
import pytest

import tensorgen
from repro.core import distributed as dist
from repro.core.flycoo import build_flycoo, choose_partition_params
from repro.core.remap import remap_capacities
from repro.core.tensors import SparseTensor

SHAPE, NNZ = (61, 47, 83, 9), 6000


def test_pattern_is_distinct_and_exact():
    coords = tensorgen.pattern(SHAPE, NNZ, np.random.default_rng(5))
    assert coords.shape == (NNZ, 4)
    assert np.unique(np.ravel_multi_index(tuple(coords.T), SHAPE)).size == NNZ
    assert (coords >= 0).all() and (coords < np.array(SHAPE)).all()


def test_interval_permutation_keeps_intervals():
    perm = tensorgen.interval_permutation(1000, 64, np.random.default_rng(1))
    assert sorted(perm) == list(range(1000))
    assert (perm // 64 == np.arange(1000) // 64).all()
    assert (perm != np.arange(1000)).any()


@pytest.mark.parametrize("workers", [1, 4])
def test_seeds_differ_but_shapes_do_not(workers):
    """One worker: fresh coordinates per seed. Four: a fixed pattern
    relabelled inside the given intervals."""
    kw = {} if workers == 1 else dict(
        pattern_seed=5, intervals=choose_partition_params(SHAPE, NNZ, 4).m)
    runs = [tensorgen.make_inputs(SHAPE, NNZ, 16, seed=s, **kw)
            for s in (1, 2**31 + 7)]
    a, b = runs
    assert not np.array_equal(a.coords, b.coords)
    assert not np.array_equal(a.values, b.values)
    assert not np.array_equal(a.factors[0], b.factors[0])
    fts = [build_flycoo(SparseTensor(r.coords, r.values, r.shape),
                        num_workers=workers) for r in runs]
    assert fts[0].nnz_cap == fts[1].nnz_cap
    assert [mp.rows_cap for mp in fts[0].modes] == \
        [mp.rows_cap for mp in fts[1].modes]
    assert remap_capacities(fts[0]) == remap_capacities(fts[1])
    rts = [dist.prepare_runtime(ft, 16)[0] for ft in fts]
    assert rts[0] == rts[1]


def test_negative_and_large_seeds_are_accepted():
    for seed in (-3, 2**40 + 1):
        inp = tensorgen.make_inputs((5, 6, 7), 20, 2, seed=seed)
        assert inp.coords.shape == (20, 3)
