"""CP-ALS (paper Alg. 1) on top of the Dynasor spMTTKRP engines.

Two drivers, one algorithm:

* :func:`cp_als` — single-device JAX reference (segment-sum MTTKRP). The
  correctness oracle and the laptop-scale path.
* :func:`cp_als_distributed` — the production path: owner-computes Dynasor
  MTTKRP under ``shard_map`` with dynamic tensor remapping between modes.
  Factors live in FLYCOO-permuted row space for the whole decomposition
  (grams, column norms and the fit are permutation-invariant) and are
  un-permuted once at the end.

Fit = 1 - ||X - X̂||_F / ||X||_F, computed with the standard sparse-CP
identity (SPLATT):  ||X̂||² = 1λᵀ(⊛_w Gramᵂ)λ1   and
<X, X̂> = Σ_r λ_r Σ_i M_last[i,r]·A_last[i,r]  where ``M_last`` is the final
mode's (pre-solve) MTTKRP output — no dense reconstruction ever happens.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..obs import counters as _obs
from ..obs import scopes as _scopes
from ..obs import tracer as _tracer
from ..resilience import checkpoint as _ckpt
from ..resilience import numerics as _numerics
from ..resilience import policy as _rpolicy
from . import distributed as dist
from .flycoo import FlycooTensor
from .mttkrp import mttkrp as mttkrp_jax

__all__ = ["CPResult", "cp_als", "cp_als_distributed", "fit_from_parts",
           "make_instrumented_mode_fns"]


@dataclasses.dataclass
class CPResult:
    """Decomposition [[λ; A_0 … A_{N-1}]] + convergence trace."""

    factors: list[np.ndarray]   # natural row space, (I_n, R) each
    lam: np.ndarray             # (R,) column weights
    fits: list[float]           # fit after each ALS sweep
    iters: int

    @property
    def fit(self) -> float:
        return self.fits[-1] if self.fits else float("nan")


def _normalize_columns(A, sweep0: bool):
    """Column-normalize; first sweep uses 2-norm, later sweeps max-norm
    (standard CP-ALS practice — keeps λ from oscillating)."""
    if sweep0:
        norms = jnp.linalg.norm(A, axis=0)
    else:
        norms = jnp.maximum(jnp.max(jnp.abs(A), axis=0), 1.0)
    norms = jnp.where(norms == 0, 1.0, norms)
    return A / norms, norms


def _solve_v_guarded(grams, mode: int, M, ridge: float = 1e-9):
    """A_n ← M_n · V⁺ with V = ⊛_{w≠n} G_w — guarded; returns (A, level).

    The solve runs through :func:`repro.resilience.numerics.guarded_solve`
    (non-finite/ill-conditioned gram → escalated ridge → lstsq); ``level``
    indexes ``GUARD_LEVELS`` so host-side drivers can count escalations
    (``resilience.solve.guards``). Level 0 is bit-identical to the
    historical plain ``solve(V + ridge·I)``.
    """
    R = M.shape[1]
    V = jnp.ones((R, R), M.dtype)
    for w, G in enumerate(grams):
        if w != mode:
            V = V * G
    return _numerics.guarded_solve(V, M, ridge=ridge)


def _solve_v(grams, mode: int, M, ridge: float = 1e-9):
    """A_n ← M_n · V⁺ with V = ⊛_{w≠n} G_w (Hadamard of grams)."""
    X, _level = _solve_v_guarded(grams, mode, M, ridge=ridge)
    return X


def fit_from_parts(x_norm_sq, lam, grams, M_last, A_last):
    """Sparse-CP fit from the identity above (no reconstruction)."""
    R = lam.shape[0]
    G = jnp.ones((R, R), M_last.dtype)
    for g in grams:
        G = G * g
    model_norm_sq = jnp.einsum("r,rs,s->", lam, G, lam)
    inner = jnp.einsum("ir,ir,r->", M_last, A_last, lam)
    resid_sq = jnp.maximum(x_norm_sq - 2.0 * inner + model_norm_sq, 0.0)
    return 1.0 - jnp.sqrt(resid_sq) / jnp.sqrt(x_norm_sq)


# ---------------------------------------------------------------------------
# Single-device reference driver
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("shape", "sweep0"))
def _sweep_jax(indices, values, factors, lam, shape: tuple[int, ...],
               sweep0: bool):
    factors = list(factors)
    grams = [f.T @ f for f in factors]
    M = None
    for n in range(len(shape)):
        M = mttkrp_jax(indices, values, factors, n, shape[n])
        A = _solve_v(grams, n, M)
        A, norms = _normalize_columns(A, sweep0)
        factors[n] = A
        grams[n] = A.T @ A
        lam = norms
    x_norm_sq = jnp.sum(values.astype(jnp.float32) ** 2)
    fit = fit_from_parts(x_norm_sq, lam, grams, M, factors[-1])
    return factors, lam, fit


def cp_als(tensor, rank: int, *, iters: int = 10, seed: int = 0,
           tol: float = 1e-5, tracer=None,
           checkpoint_dir: str | None = None,
           checkpoint_every: int = 1) -> CPResult:
    """Single-device CP-ALS (paper Alg. 1) — the correctness oracle.

    ``tracer`` (default: the process tracer, normally the no-op) records
    one ``sweep`` span per ALS sweep; the whole sweep is a single jitted
    call here, so there is no per-mode breakdown — use
    :func:`cp_als_distributed` for the full span taxonomy.

    ``checkpoint_dir`` turns on resumable sweeps: every
    ``checkpoint_every``-th completed sweep is persisted atomically
    (factors, λ, fit trace, sweep index) through the
    ``repro.checkpoint`` manager, and a rerun pointed at the same
    directory restores the newest complete checkpoint and continues —
    a killed job resumes warm instead of restarting, converging to the
    same decomposition (pinned by ``tests/test_resilience.py``).
    """
    tracer = _tracer.get_tracer() if tracer is None else tracer
    rng = np.random.default_rng(seed)
    factors = [jnp.asarray(rng.standard_normal((d, rank)), jnp.float32)
               for d in tensor.shape]
    lam = jnp.ones((rank,), jnp.float32)
    idx = jnp.asarray(tensor.indices, jnp.int32)
    val = jnp.asarray(tensor.values, jnp.float32)
    fits: list[float] = []
    start_it = 0
    mgr = _ckpt.make_manager(checkpoint_dir)
    if mgr is not None:
        template = _ckpt.make_state(
            [np.asarray(f) for f in factors], np.asarray(lam), fits,
            sweep=0, rank=rank, backend="jax")
        state, _step = _ckpt.restore_state(mgr, template)
        if state is not None:
            factors = [jnp.asarray(f) for f in state["factors"]]
            lam = jnp.asarray(state["lam"])
            fits = [float(x) for x in state["fits"]]
            start_it = int(state["sweep"]) + 1
    for it in range(start_it, iters):
        t0 = time.perf_counter()
        with tracer.span("sweep", sweep=it, driver="single"):
            factors, lam, fit = _sweep_jax(idx, val, tuple(factors), lam,
                                           tuple(tensor.shape), it == 0)
            fit = float(fit)   # blocks: the sweep is fully resolved here
        _obs.add("cpals.sweep_s", time.perf_counter() - t0, driver="single")
        _obs.add("cpals.sweeps", driver="single")
        fits.append(fit)
        if mgr is not None and (it + 1) % checkpoint_every == 0:
            _ckpt.save_state(mgr, _ckpt.make_state(
                [np.asarray(f) for f in factors], np.asarray(lam), fits,
                sweep=it, rank=rank, backend="jax"))
        if it > 0 and abs(fits[-1] - fits[-2]) < tol:
            break
    return CPResult([np.asarray(f) for f in factors], np.asarray(lam),
                    fits, len(fits))


# ---------------------------------------------------------------------------
# Distributed Dynasor driver
# ---------------------------------------------------------------------------

def make_als_sweep(rt: dist.DynasorRuntime, mesh: Mesh, *,
                   backend: str = "segsum") -> Callable:
    """One full distributed ALS sweep (all modes, with dynamic remapping).

    ``backend`` is the per-device MTTKRP engine: ``segsum`` (plain XLA),
    ``ref``, ``pallas_fused`` (Khatri-Rao rows formed in XLA, scattered
    by ``krp_scatter`` — works for any tensor order), or
    ``auto`` (dispatch on mode count / rank padding / VMEM budget; see
    ``kernels.mttkrp.ops.select_backend``).

    Returned jitted fn:
      ``(idx, val, mask, factors, lam, sweep0) ->
        (idx', val', mask', factors', lam', fit_parts)``
    Factors are replicated ``(i_pad_n, R)`` arrays in permuted row space.
    The MTTKRP → solve → normalize → remap chain per mode follows Alg. 1/2;
    the solve happens on owned rows only (owner-computes extends to the
    least-squares update), then an all_gather re-replicates the factor.

    Each phase runs under a ``jax.named_scope`` of :mod:`repro.obs.scopes`
    (``als_mode{n}/mttkrp|solve|remap``, then ``fit``), so a device
    trace of the sweep can be split by phase.
    """

    def inner(idx, val, mask, x_norm_sq, *factors_lam):
        idx, val, mask = idx[0], val[0], mask[0]
        x_norm_sq = x_norm_sq[0]
        *factors, lam, sweep0 = factors_lam
        factors = list(factors)
        grams = [f.T @ f for f in factors]   # padding rows are 0 → exact
        M_last_local = A_last_local = None
        for n in range(rt.nmodes):
            with jax.named_scope(_scopes.mode_scope(n)):
                with jax.named_scope(_scopes.MTTKRP):
                    local_M = dist.device_mttkrp(idx, val, mask, factors, n,
                                                 rt, backend)
                with jax.named_scope(_scopes.SOLVE):
                    A_local = _solve_v(grams, n, local_M)
                    # Column norms need the full matrix: psum of local sums.
                    sq = jax.lax.psum(jnp.sum(A_local ** 2, axis=0),
                                      dist.AXIS)
                    mx = jax.lax.pmax(jnp.max(jnp.abs(A_local), axis=0),
                                      dist.AXIS)
                    norms = jnp.where(sweep0, jnp.sqrt(sq),
                                      jnp.maximum(mx, 1.0))
                    norms = jnp.where(norms == 0, 1.0, norms)
                    A_local = A_local / norms
                    lam = norms
                    full = jax.lax.all_gather(A_local, dist.AXIS, axis=0,
                                              tiled=True)
                    factors[n] = full
                    grams[n] = full.T @ full
                if n == rt.nmodes - 1:
                    M_last_local, A_last_local = local_M, A_local
                with jax.named_scope(_scopes.REMAP):
                    idx, val, mask, _ = dist.device_remap(
                        idx, val, mask, (n + 1) % rt.nmodes, rt)
        with jax.named_scope(_scopes.FIT):
            # fit parts: <X, X̂> = Σ_r λ_r Σ_i M[i,r]·Â[i,r], owned rows
            # psummed.
            inner_term = jax.lax.psum(
                jnp.einsum("ir,ir,r->", M_last_local, A_last_local, lam),
                dist.AXIS)
            R = lam.shape[0]
            G = jnp.ones((R, R), jnp.float32)
            for g in grams:
                G = G * g
            model_norm_sq = jnp.einsum("r,rs,s->", lam, G, lam)
            resid_sq = jnp.maximum(
                x_norm_sq - 2.0 * inner_term + model_norm_sq, 0.0)
            fit = 1.0 - jnp.sqrt(resid_sq) / jnp.sqrt(x_norm_sq)
        return ((idx[None], val[None], mask[None]),
                factors, lam, fit)

    from jax.sharding import PartitionSpec as P
    spec_t, spec_r = P(dist.AXIS), P()
    shmapped = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(spec_t, spec_t, spec_t, spec_t)
        + (spec_r,) * (rt.nmodes + 2),
        out_specs=((spec_t, spec_t, spec_t), [spec_r] * rt.nmodes, spec_r,
                   spec_r),
        check_vma=False,
    )
    return jax.jit(shmapped)


def make_instrumented_mode_fns(rt: dist.DynasorRuntime, mesh: Mesh, *,
                               backend: str = "segsum"):
    """Per-mode jitted pieces for the *instrumented* stepped driver.

    The production sweep (:func:`make_als_sweep`) is one jitted
    ``shard_map`` call over all modes — nothing inside it can carry a
    span boundary. When a tracer is enabled the driver instead steps
    through per-mode jitted pieces so mttkrp/solve/remap get real
    wall-time spans:

      * ``mttkrp_fns[n](idx, val, mask, *factors)`` → the **full**
        pre-solve MTTKRP ``(i_pad_n, R)``: each worker computes its
        owned rows exactly as in the fused sweep and ``out_specs=
        P(AXIS)`` concatenates them (owner-computes rows are contiguous
        per worker, so the concatenation *is* the factor row space);
      * ``remap_fns[n](idx, val, mask)`` → the ``n → n+1`` dynamic
        remap, same per-transition capacities as the fused sweep.

    The solve/normalize happens host-side on the full matrices — row-wise
    identical to the fused sweep's owned-rows solve (``_solve_v`` acts
    per row; a psum of local column sums equals the global sum) — so the
    stepped driver converges like the production one while every phase
    is observable. Counted metrics (dispatch, planner, remap bytes) are
    identical by construction: the same ``device_mttkrp`` path traces
    once per mode either way.
    """
    from jax.sharding import PartitionSpec as P
    spec_t, spec_r = P(dist.AXIS), P()
    mttkrp_fns, remap_fns = [], []
    for n in range(rt.nmodes):
        def mttkrp_inner(idx, val, mask, *factors, _n=n):
            idx, val, mask = idx[0], val[0], mask[0]
            return dist.device_mttkrp(idx, val, mask, list(factors), _n,
                                      rt, backend)
        mttkrp_fns.append(jax.jit(jax.shard_map(
            mttkrp_inner, mesh=mesh,
            in_specs=(spec_t, spec_t, spec_t) + (spec_r,) * rt.nmodes,
            out_specs=spec_t, check_vma=False)))

        def remap_inner(idx, val, mask, _n=n):
            idx, val, mask = idx[0], val[0], mask[0]
            idx, val, mask, _ = dist.device_remap(
                idx, val, mask, (_n + 1) % rt.nmodes, rt)
            return idx[None], val[None], mask[None]
        remap_fns.append(jax.jit(jax.shard_map(
            remap_inner, mesh=mesh,
            in_specs=(spec_t, spec_t, spec_t),
            out_specs=(spec_t, spec_t, spec_t), check_vma=False)))
    return mttkrp_fns, remap_fns


def _ckpt_state(rt, backend, factors, lam, fits, sweep, idx, val, mask):
    """Assemble one distributed-sweep checkpoint (stream included)."""
    return _ckpt.make_state(
        [np.asarray(f) for f in factors], np.asarray(lam), fits,
        sweep=sweep, rank=rt.rank, ordering=rt.ordering, backend=backend,
        stream=(np.asarray(idx), np.asarray(val), np.asarray(mask)))


def _cp_als_distributed_traced(ft, rank, mesh, rt, idx, val, mask, *,
                               iters, seed, tol, backend, tracer,
                               mgr=None, checkpoint_every: int = 1
                               ) -> CPResult:
    """Stepped Dynasor CP-ALS under an enabled tracer or resilience policy.

    Per-mode jitted pieces (see :func:`make_instrumented_mode_fns`) give
    every phase a real host-side call boundary — which is also what the
    resilience layer needs: an active :func:`repro.resilience.use_policy`
    scope makes the kernel dispatch walk the degradation ladder at trace
    time, the remap call here gets host-level bounded retry, and every
    solve escalation is counted. Checkpoints (``mgr``) persist the
    factors *and* the remapped nonzero stream, so a resumed job
    continues from the exact post-sweep state.
    """
    factors = [jnp.asarray(f) for f in dist.init_factors(ft, rt, seed=seed)]
    lam = jnp.ones((rank,), jnp.float32)
    mttkrp_fns, remap_fns = make_instrumented_mode_fns(rt, mesh,
                                                       backend=backend)
    x_norm_sq = jnp.float32(np.sum(ft.tensor.values.astype(np.float64) ** 2))
    fits: list[float] = []
    start_it = 0
    if mgr is not None:
        state, _step = _ckpt.restore_state(
            mgr, _ckpt_state(rt, backend, factors, lam, fits, 0,
                             idx, val, mask))
        if state is not None:
            factors = [jnp.asarray(f) for f in state["factors"]]
            lam = jnp.asarray(state["lam"])
            fits = [float(x) for x in state["fits"]]
            idx = jnp.asarray(state["stream_idx"])
            val = jnp.asarray(state["stream_val"])
            mask = jnp.asarray(state["stream_mask"])
            start_it = int(state["sweep"]) + 1
    pol = _rpolicy.get_policy()
    grams = [f.T @ f for f in factors]
    for it in range(start_it, iters):
        t_sweep = time.perf_counter()
        with tracer.span("sweep", sweep=it, driver="distributed"):
            M = A = None
            for n in range(rt.nmodes):
                with tracer.span("mode", mode=n):
                    t0 = time.perf_counter()
                    with tracer.span("mttkrp", backend=backend):
                        def _mttkrp(n=n, idx=idx, val=val, mask=mask,
                                    factors=tuple(factors)):
                            return jax.block_until_ready(
                                mttkrp_fns[n](idx, val, mask, *factors))
                        M = (_mttkrp() if pol is None
                             else pol.run("ops.kernel", _mttkrp))
                        # Layout-pin: the eager solve/normalize below must
                        # compute identically whether M arrived sharded
                        # (mid-run) or from restored host factors (resume)
                        # — reduction order follows layout, and resume
                        # exactness is part of the checkpoint contract.
                        M = jnp.asarray(np.asarray(M))
                    _obs.add("cpals.phase_s", time.perf_counter() - t0,
                             phase="mttkrp", mode=n)
                    t0 = time.perf_counter()
                    with tracer.span("solve"):
                        A, level = _solve_v_guarded(grams, n, M)
                        A, norms = _normalize_columns(A, it == 0)
                        A = jax.block_until_ready(A)
                        level = int(level)
                        if level:
                            _obs.add("resilience.solve.guards",
                                     level=_numerics.GUARD_LEVELS[level],
                                     mode=n)
                    _obs.add("cpals.phase_s", time.perf_counter() - t0,
                             phase="solve", mode=n)
                    factors[n] = A
                    grams[n] = A.T @ A
                    lam = norms
                    t0 = time.perf_counter()
                    with tracer.span("remap", transition=n):
                        def _remap(n=n, idx=idx, val=val, mask=mask):
                            return jax.block_until_ready(
                                remap_fns[n](idx, val, mask))
                        idx, val, mask = (
                            _remap() if pol is None
                            else pol.run("distributed.remap", _remap))
                    _obs.add("cpals.phase_s", time.perf_counter() - t0,
                             phase="remap", mode=n)
            fit = float(fit_from_parts(x_norm_sq, lam, grams, M, A))
        _obs.add("cpals.sweep_s", time.perf_counter() - t_sweep,
                 driver="distributed")
        _obs.add("cpals.sweeps", driver="distributed")
        fits.append(fit)
        if mgr is not None and (it + 1) % checkpoint_every == 0:
            _ckpt.save_state(mgr, _ckpt_state(rt, backend, factors, lam,
                                              fits, it, idx, val, mask))
        if it > 0 and abs(fits[-1] - fits[-2]) < tol:
            break
    nat = [dist.unpermute_factor(ft, rt, n, np.asarray(f))
           for n, f in enumerate(factors)]
    return CPResult(nat, np.asarray(lam), fits, len(fits))


def cp_als_distributed(ft: FlycooTensor, rank: int, mesh: Mesh, *,
                       iters: int = 10, seed: int = 0, tol: float = 1e-5,
                       backend: str = "segsum",
                       tile_rows: int = 8, table=None,
                       gather_dtype: str = "float32",
                       ordering: str | None = None,
                       tracer=None,
                       checkpoint_dir: str | None = None,
                       checkpoint_every: int = 1,
                       resilience: "_rpolicy.RetryPolicy | None" = None
                       ) -> CPResult:
    """Distributed CP-ALS: FLYCOO layout + Dynasor sweeps on ``mesh``.

    Works for tensors of any order: with ``backend="pallas_fused"`` (or
    ``"auto"``) every mode of a 3-/4-/5-mode decomposition runs the fused
    N-mode Pallas kernel end-to-end. ``table`` (a ``repro.tune``
    calibration table) gives every mode a tuned
    ``(backend, blk, tile_rows)`` plan, followed when ``backend="auto"``.
    ``gather_dtype="bfloat16"`` opts the whole decomposition into bf16
    factor-row gathers on every fused-family mode step (fp32
    accumulate); the end-to-end fit impact is measured by
    ``benchmarks/bench_bf16_convergence.py``.

    ``ordering`` (:data:`repro.reorder.ORDERINGS`; ``None`` inherits
    ``ft.ordering``) turns on locality-aware nonzero ordering for every
    fused-family mode step — same fit up to fp32 accumulation order
    (property-tested in ``tests/test_reorder.py``).

    ``tracer`` defaults to the process tracer (``repro.obs``), normally
    the no-op — the production path below stays untouched. An *enabled*
    tracer switches to the stepped driver
    (:func:`make_instrumented_mode_fns`): per-mode jitted pieces with
    nested ``sweep → mode → mttkrp|solve|remap`` spans and identical
    counted metrics.

    ``checkpoint_dir`` turns on resumable sweeps (atomic per-sweep
    checkpoints holding factors, λ, fit trace, sweep index *and* the
    remapped nonzero stream — a resumed job continues from the exact
    post-sweep state; see ``repro.resilience.checkpoint``).
    ``resilience`` (a ``repro.resilience.RetryPolicy``) turns on
    graceful degradation: the run switches to the stepped driver and
    every kernel dispatch / remap / chunk launch gets bounded retry and
    a recorded walk down the residency ladder — every fallback counted
    in the ``resilience.*`` namespace, never a silent wrong answer.
    """
    if ft.params.num_workers != mesh.shape[dist.AXIS]:
        raise ValueError(
            f"tensor was built for {ft.params.num_workers} workers but the "
            f"mesh has {mesh.shape[dist.AXIS]} devices on {dist.AXIS!r}: "
            "build_flycoo(t, num_workers=<mesh size>)")
    tracer = _tracer.get_tracer() if tracer is None else tracer
    rt, (idx, val, mask) = dist.prepare_runtime(ft, rank,
                                                tile_rows=tile_rows,
                                                table=table,
                                                gather_dtype=gather_dtype,
                                                ordering=ordering)
    mgr = _ckpt.make_manager(checkpoint_dir)
    if tracer.enabled or resilience is not None or mgr is not None:
        # The stepped driver is the resilient one: per-phase host call
        # boundaries are where retry/degradation/checkpointing attach.
        if resilience is None:
            return _cp_als_distributed_traced(
                ft, rank, mesh, rt, idx, val, mask, iters=iters, seed=seed,
                tol=tol, backend=backend, tracer=tracer, mgr=mgr,
                checkpoint_every=checkpoint_every)
        with _rpolicy.use_policy(resilience):
            return _cp_als_distributed_traced(
                ft, rank, mesh, rt, idx, val, mask, iters=iters, seed=seed,
                tol=tol, backend=backend, tracer=tracer, mgr=mgr,
                checkpoint_every=checkpoint_every)
    factors = [jnp.asarray(f) for f in dist.init_factors(ft, rt, seed=seed)]
    lam = jnp.ones((rank,), jnp.float32)
    sweep = make_als_sweep(rt, mesh, backend=backend)
    x_norm_sq = np.broadcast_to(
        np.float32(np.sum(ft.tensor.values.astype(np.float64) ** 2)),
        (rt.num_workers,)).copy()
    fits: list[float] = []
    for it in range(iters):
        t0 = time.perf_counter()
        (idx, val, mask), factors, lam, fit = sweep(
            idx, val, mask, x_norm_sq, *factors, lam,
            jnp.asarray(it == 0))
        fit = float(fit)   # blocks on the whole fused sweep
        _obs.add("cpals.sweep_s", time.perf_counter() - t0,
                 driver="distributed")
        _obs.add("cpals.sweeps", driver="distributed")
        fits.append(fit)
        if it > 0 and abs(fits[-1] - fits[-2]) < tol:
            break
    nat = [dist.unpermute_factor(ft, rt, n, np.asarray(f))
           for n, f in enumerate(factors)]
    return CPResult(nat, np.asarray(lam), fits, len(fits))
