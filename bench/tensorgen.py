"""Seeded synthetic stand-ins for the FROSTT tensors, and the initial factors.

The benchmark makes every input itself from ``--seed``; the program under
test only receives them, and nothing here reads the program.

By default a run's seed draws a fresh tensor: ``nnz`` distinct uniform
coordinates and standard-normal values. On one chip every static shape of
the compiled sweep follows from (shape, nnz) alone, so every seed runs the
same program on different data.

Across chips the per-worker capacities depend on where the nonzeros fall,
so a configuration for several chips names ``intervals``: the widths of
its super-shard intervals, one per mode. Its coordinates then come from a
fixed *pattern* (drawn once from the file's ``pattern_seed``), and a run's
seed relabels each mode's rows by a random permutation that keeps every
row inside its interval, and draws fresh values. Relabelling inside an
interval keeps each interval's nonzero count, and with it the per-worker
loads and exchange sizes, while the coordinates, the values and the
factors differ from seed to seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# Stream ids of numpy's SeedSequence: one per independent draw from a seed.
_VALUES, _FACTORS, _RELABEL, _PATTERN = 1, 2, 3, 4


@dataclasses.dataclass(frozen=True)
class Inputs:
    """One run's tensor (natural index space) and initial factors."""

    coords: np.ndarray          # (nnz, N) int32, distinct
    values: np.ndarray          # (nnz,) float32, no zeros
    shape: tuple[int, ...]
    factors: list[np.ndarray]   # (I_n, R) float32 each


def _rng(seed: int, stream: int):
    """Independent generator ``stream`` of a run's seed (any whole number)."""
    return np.random.default_rng([seed % 2**64, stream])


def pattern(shape, nnz: int, rng) -> np.ndarray:
    """``nnz`` distinct uniform coordinates, sorted by flat index."""
    shape = tuple(int(d) for d in shape)
    if nnz > np.prod(shape, dtype=np.float64):
        raise ValueError(f"nnz={nnz} exceeds the capacity of shape {shape}")
    keys = np.zeros(0, np.int64)
    while keys.size < nnz:
        want = nnz - keys.size
        draw = np.stack([rng.integers(0, d, size=want + 64, dtype=np.int64)
                         for d in shape])
        fresh = np.ravel_multi_index(tuple(draw), shape)
        fresh = np.setdiff1d(np.unique(fresh), keys, assume_unique=True)
        if fresh.size > want:
            fresh = np.delete(fresh, rng.choice(fresh.size, fresh.size - want,
                                                replace=False))
        keys = np.union1d(keys, fresh)
    coords = np.stack(np.unravel_index(keys, shape), axis=1)
    return coords.astype(np.int32)


def interval_permutation(dim: int, m: int, rng) -> np.ndarray:
    """Random bijection of ``range(dim)`` with ``perm[r] // m == r // m``."""
    order = np.lexsort((rng.random(dim), np.arange(dim) // m))
    perm = np.empty(dim, np.int64)
    perm[order] = np.arange(dim)
    return perm


def make_inputs(shape, nnz: int, rank: int, *, seed: int,
                pattern_seed: int | None = None,
                intervals=None) -> Inputs:
    """The tensor and initial factors of one run.

    Without ``intervals`` the coordinates are drawn from ``seed``. With
    them, from ``pattern_seed``, and ``seed`` relabels rows inside each
    mode's intervals of ``intervals[n]`` rows.
    """
    shape = tuple(int(d) for d in shape)
    if intervals is None:
        coords = pattern(shape, nnz, _rng(seed, _PATTERN))
    else:
        coords = pattern(shape, nnz, np.random.default_rng(pattern_seed))
        relabel = _rng(seed, _RELABEL)
        for n, (dim, m) in enumerate(zip(shape, intervals)):
            perm = interval_permutation(dim, int(m), relabel)
            coords[:, n] = perm[coords[:, n]]
    values = _rng(seed, _VALUES).standard_normal(
        nnz, dtype=np.float32)
    values[values == 0] = 1.0
    frng = _rng(seed, _FACTORS)
    factors = [frng.standard_normal((dim, rank), dtype=np.float32)
               for dim in shape]
    return Inputs(coords, values, shape, factors)
