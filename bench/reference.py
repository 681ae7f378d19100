"""Plain reference for one CP-ALS sweep, in natural index space.

Independent of the code under test: it imports nothing from ``src/`` and
takes nothing the program made. Each mode's MTTKRP is a gather, a
product and a scatter-add in ``jax.numpy`` on one device, summed over
fixed-size chunks of nonzeros so that it fits beside nothing else; the
grams, the solve, the normalisation and the fit are float64 on the host.

The algorithm is the repository's CP-ALS (paper Alg. 1) for the first
sweep: for each mode n in turn, A_n <- M_n (V + 1e-9 I)^-1 with
V = Hadamard product of the other modes' grams, columns divided by their
2-norms (the norms become lambda), and the fit
1 - ||X - X_hat|| / ||X|| from the sparse-CP identity with the last
mode's pre-solve MTTKRP.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1 << 21   # nonzeros per MTTKRP chunk
RIDGE = 1e-9


@functools.partial(jax.jit, static_argnames=("mode", "rows", "chunk"))
def _mttkrp(coords, values, factors, *, mode: int, rows: int, chunk: int):
    """sum_k values[k] * prod_{w != mode} A_w[coords[k, w]] into row
    coords[k, mode], over ``coords.shape[0] // chunk`` chunks."""
    rank = factors[0].shape[1]

    def body(c, acc):
        lo = c * chunk
        idx = jax.lax.dynamic_slice_in_dim(coords, lo, chunk, axis=0)
        val = jax.lax.dynamic_slice_in_dim(values, lo, chunk, axis=0)
        prod = val[:, None]
        for w, f in enumerate(factors):
            if w != mode:
                prod = prod * jnp.take(f, idx[:, w], axis=0)
        return acc + jax.ops.segment_sum(prod, idx[:, mode],
                                         num_segments=rows)

    return jax.lax.fori_loop(0, coords.shape[0] // chunk, body,
                             jnp.zeros((rows, rank), jnp.float32))


def _padded(coords, values, chunk):
    pad = -coords.shape[0] % chunk
    return (np.concatenate([coords, np.zeros((pad, coords.shape[1]),
                                             coords.dtype)]),
            np.concatenate([values, np.zeros(pad, values.dtype)]))


def first_sweep(coords, values, shape, factors, device=None,
                chunk: int = CHUNK):
    """One sweep from ``factors`` -> (factors', lambda, fit), float64.

    The last mode's factor is returned as normalised; multiply by lambda
    for the model's scale.
    """
    device = device or jax.devices()[0]
    chunk = min(chunk, max(1, coords.shape[0]))
    c_pad, v_pad = _padded(np.asarray(coords, np.int32),
                           np.asarray(values, np.float32), chunk)
    d_coords = jax.device_put(c_pad, device)
    d_values = jax.device_put(v_pad, device)
    factors = [np.asarray(f, np.float64) for f in factors]
    grams = [f.T @ f for f in factors]
    rank = factors[0].shape[1]
    lam = np.ones(rank)
    m_last = None
    for n, rows in enumerate(shape):
        with jax.default_matmul_precision("highest"):
            dev_f = [jax.device_put(f.astype(np.float32), device)
                     for f in factors]
            m = np.asarray(_mttkrp(d_coords, d_values, dev_f, mode=n,
                                   rows=int(rows), chunk=chunk), np.float64)
        v = np.ones((rank, rank))
        for w, g in enumerate(grams):
            if w != n:
                v = v * g
        a = np.linalg.solve(v + RIDGE * np.eye(rank), m.T).T
        norms = np.linalg.norm(a, axis=0)
        norms = np.where(norms == 0, 1.0, norms)
        factors[n] = a / norms
        grams[n] = factors[n].T @ factors[n]
        lam = norms
        m_last = m
    x_norm_sq = float(np.sum(np.asarray(values, np.float64) ** 2))
    g = np.ones((rank, rank))
    for gram in grams:
        g = g * gram
    model_sq = float(lam @ g @ lam)
    inner = float(np.einsum("ir,ir,r->", m_last, factors[-1], lam))
    resid_sq = max(x_norm_sq - 2.0 * inner + model_sq, 0.0)
    fit = 1.0 - np.sqrt(resid_sq) / np.sqrt(x_norm_sq)
    return factors, lam, fit
