"""Single-device spMTTKRP engines (paper §II-C elementwise computation).

Three tiers, each validated against the previous:
  1. :func:`mttkrp_elementwise_ref` — literal per-nonzero loop (paper Fig. 1 /
     Eq. 4). numpy, tests only.
  2. :func:`mttkrp` — vectorized JAX engine: gather input factor rows,
     Hadamard-product them, scale by the value, ``segment_sum`` into the
     output rows. This is the pure-jnp oracle for the Pallas kernel.
  3. ``repro.kernels.mttkrp.ops.mttkrp_blocked`` — the Pallas TPU kernel
     (shard = VMEM block; scatter = one-hot MXU matmul).
  4. :func:`mttkrp_fused` — single-device convenience over the N-mode fused
     Pallas path (``ops.mttkrp_device_step``): sorts the stream by output
     row and dispatches through the backend matrix (``auto`` by default).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.mttkrp import ops as _kops

__all__ = [
    "mttkrp_elementwise_ref",
    "hadamard_rows",
    "mttkrp",
    "mttkrp_sorted",
    "mttkrp_fused",
]


def mttkrp_elementwise_ref(indices, values, factors, mode, out_rows=None):
    """Literal Alg. 2 inner loop in numpy (lines 13-25). Tests only."""
    indices = np.asarray(indices)
    values = np.asarray(values)
    nmodes = indices.shape[1]
    rank = factors[0].shape[1]
    out_rows = out_rows if out_rows is not None else factors[mode].shape[0]
    out = np.zeros((out_rows, rank), dtype=np.float64)
    for i in range(len(values)):
        ell = np.ones(rank, dtype=np.float64)
        for w in range(nmodes):
            if w == mode:
                continue
            ell *= np.asarray(factors[w])[indices[i, w]].astype(np.float64)
        out[indices[i, mode]] += float(values[i]) * ell
    return out


def hadamard_rows(indices, values, factors, mode):
    """``value · ⊙_{w≠mode} Y_w[c_w]`` for every nonzero → ``(nnz, R)``.

    This is the gather + Hadamard stage (Alg. 2 lines 19-23); the remaining
    segment-reduction is the scatter stage handled either by
    ``jax.ops.segment_sum`` or by the Pallas kernel.
    """
    nmodes = indices.shape[1]
    ell = values[:, None].astype(factors[0].dtype)
    for w in range(nmodes):
        if w == mode:
            continue
        ell = ell * jnp.take(factors[w], indices[:, w], axis=0)
    return ell


@functools.partial(jax.jit, static_argnames=("mode", "out_rows"))
def mttkrp(indices, values, factors, mode: int, out_rows: int):
    """Vectorized spMTTKRP for one mode (unsorted nonzeros)."""
    ell = hadamard_rows(indices, values, factors, mode)
    return jax.ops.segment_sum(ell, indices[:, mode], num_segments=out_rows)


@functools.partial(
    jax.jit, static_argnames=("mode", "out_rows", "indices_sorted")
)
def mttkrp_sorted(indices, values, factors, mode: int, out_rows: int,
                  indices_sorted: bool = True):
    """spMTTKRP for nonzeros pre-sorted by output row (FLYCOO layout).

    Sortedness lets XLA use the monotonic segment-sum path; it is also the
    precondition for the Pallas blocked kernel.
    """
    ell = hadamard_rows(indices, values, factors, mode)
    return jax.ops.segment_sum(
        ell, indices[:, mode], num_segments=out_rows,
        indices_are_sorted=indices_sorted,
    )


@functools.partial(
    jax.jit,
    static_argnames=("mode", "out_rows", "blk", "tile_rows", "backend",
                     "interpret", "gather_dtype"),
)
def mttkrp_fused(indices, values, factors, mode: int, out_rows: int, *,
                 blk: int = 512, tile_rows: int = 128,
                 backend: str = "auto", interpret: bool | None = None,
                 gather_dtype: str = "float32"):
    """Single-device spMTTKRP through the fused N-mode Pallas path.

    Sorts the nonzero stream by output row (the FLYCOO precondition), pads
    the output to a whole number of row tiles, and dispatches through
    ``ops.mttkrp_device_step``'s backend matrix (``docs/kernels.md``) —
    ``auto`` picks the gather family, fused, rank-tiled fused or ref
    from mode count, rank padding and VMEM budget. ``gather_dtype=
    "bfloat16"`` makes the fused family gather bf16 factor rows
    (fp32 accumulate).
    """
    order = jnp.argsort(indices[:, mode], stable=True)
    idx = jnp.take(indices, order, axis=0).astype(jnp.int32)
    val = jnp.take(values, order)
    valid = jnp.ones(val.shape, bool)
    rows_cap = -(-out_rows // tile_rows) * tile_rows
    out = _kops.mttkrp_device_step(
        idx, val, valid, list(factors), mode=mode, rows_cap=rows_cap,
        row_offset=0, blk=blk, tile_rows=tile_rows, interpret=interpret,
        backend=backend, gather_dtype=gather_dtype,
    )
    return out[:out_rows]
