"""Distributed spMTTKRP: owner-computes + dynamic remapping via shard_map.

This is Alg. 2 (Dynasor) on a JAX device mesh:

  * the ``workers`` mesh axis plays the CPU-thread role; every device owns
    the output rows of the super-shards LPT-assigned to it (baked into the
    FLYCOO row permutation, see ``core.flycoo``);
  * the per-device mode step is gather → Hadamard → segment-scatter
    (``ref``/``segsum`` backends) or the Pallas blocked kernel
    (``pallas_fused`` Khatri-Rao rows scattered by ``krp_scatter`` /
    ``pallas_fused_tiled`` rank-slabbed / ``pallas_fused_gather`` and
    its tiled composition, which gather the factor rows *inside* the
    kernel / ``pallas_fused_gather_stream``, the out-of-core variant
    that keeps the factors HBM-resident behind a streamed VMEM tile
    window / the bf16-gather variants / ``auto`` dispatch — decision
    matrix in ``docs/kernels.md``);
  * **owner-computes means the output factor needs no psum** — only an
    all_gather to re-replicate it for later modes (on CPU this was "write
    once to shared DRAM");
  * while mode ``n`` computes, the tensor is re-bucketed for mode ``n+1``
    with a capacity-padded all_to_all (``core.remap``) — the dynamic memory
    layout that keeps storage at ``2·|T|``.

Also implemented, as the paper's comparison targets:

  * :func:`make_spmttkrp_all_modes` with ``remap=False`` — Fig. 9 "Case 2":
    tensor stays in mode-0 order; non-owner modes must produce dense
    partial outputs and all-reduce them;
  * :func:`make_baseline_all_modes` — ALTO/HiCOO-style nonzero-parallel
    execution: every mode all-reduces a dense ``(I_n, R)`` partial — the
    intermediate-value traffic Dynasor eliminates.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from . import remap as remap_lib
from .flycoo import FlycooTensor, pack_mode
from ..kernels.mttkrp import ops as kops
from ..obs import counters as _obs
from ..obs import tracer as _tracer
from ..resilience import faults as _faults
from ..runtime import execution as _execution

__all__ = [
    "AXIS",
    "DynasorRuntime",
    "ModePlan",
    "prepare_runtime",
    "init_factors",
    "make_spmttkrp_all_modes",
    "make_baseline_all_modes",
    "unpermute_factor",
]

AXIS = "workers"


class ModePlan(NamedTuple):
    """Tuned per-mode kernel configuration (from ``repro.tune``)."""

    backend: str                # segsum | ref | any kernels.mttkrp backend
    blk: int                    # Pallas nonzero block for this mode
    tile_rows: int              # Pallas output row tile for this mode
    # Rank slabs the fused kernel iterates for this mode: padded_rank /
    # RANK_SLAB when backend is one of the rank-slabbed kernels
    # (pallas_fused_tiled / pallas_fused_gather_tiled /
    # pallas_fused_gather_stream, which always slabs), else 1 (the
    # whole padded rank is one resident slab). Pure metadata for traffic
    # accounting / benches — the kernel derives its own grid from shapes.
    rank_slabs: int = 1
    # Out-of-core stream-window widths per *input* mode (the
    # repro.oocore planner's FACTOR_ROW_TILE-tile counts) when backend
    # is pallas_fused_gather_stream, else (). Metadata like rank_slabs:
    # the kernel derives its real windows from the factor shapes.
    window_tiles: tuple = ()
    # repro.reorder.ORDERINGS locality policy the mode step applies
    # in-jit (build_block_layout order_keys). Unlike rank_slabs /
    # window_tiles this is *not* metadata — it changes the aligned
    # stream the kernel sees.
    ordering: str = "none"


@dataclasses.dataclass(frozen=True)
class DynasorRuntime:
    """Static metadata threaded through the jitted distributed functions."""

    num_workers: int
    nmodes: int
    rank: int
    rows_cap: tuple[int, ...]   # owned output rows per worker, per mode
    i_pad: tuple[int, ...]      # num_workers * rows_cap, per mode
    nnz_cap: int                # per-worker nonzero capacity
    bucket_cap: int             # all_to_all per-(src,dst) capacity (max)
    shape: tuple[int, ...]      # natural tensor shape
    blk: int = 512              # Pallas nonzero block (FLYCOO shard g)
    tile_rows: int = 128        # Pallas output row tile
    # Per-transition all_to_all capacities (remap_capacities order: entry n
    # bounds the mode n -> n+1 exchange). None = uniform bucket_cap for
    # every transition (the pre-tuning behavior / `uniform_cap` hatch).
    bucket_caps: tuple[int, ...] | None = None
    # Tuned (backend, blk, tile_rows) per mode from a calibration table.
    # None = untuned: every mode uses (blk, tile_rows) above and the
    # caller's backend string.
    mode_plans: tuple[ModePlan, ...] | None = None
    # Dtype the fused kernels gather factor rows in ("float32" |
    # "bfloat16"). bf16 halves gather-operand VMEM/HBM traffic and
    # accumulates at fp32 (≈(N−1)·2⁻⁸ rel. error); it is threaded here — never
    # chosen by ``auto`` — so the whole decomposition opts in explicitly.
    gather_dtype: str = "float32"
    # repro.reorder.ORDERINGS locality policy threaded to every mode
    # step (untuned runtimes; tuned runtimes carry it per ModePlan).
    ordering: str = "none"

    def __post_init__(self):
        # Validate at construction: non-fused mode steps never read this,
        # so a typo ("bf16") would otherwise run fp32 silently or raise
        # mid-decomposition only once a fused backend is reached.
        if self.gather_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"unknown gather_dtype {self.gather_dtype!r}: expected "
                "'float32' or 'bfloat16'")
        from ..reorder import validate_ordering  # deferred: reorder→kernels
        validate_ordering(self.ordering)

    @property
    def payload_width(self) -> int:
        return self.nmodes + 1  # coords + value

    def bucket_cap_for(self, from_mode: int) -> int:
        """Exchange capacity of the ``from_mode -> from_mode+1`` remap."""
        if self.bucket_caps is None:
            return self.bucket_cap
        return self.bucket_caps[from_mode]

    def plan_for(self, mode: int, backend: str = "auto") -> ModePlan:
        """Resolve the kernel configuration for ``mode``.

        Tuned runtimes always use the plan's (blk, tile_rows) — rows_cap
        was rounded to the plan's tile — and substitute the plan's
        backend only when the caller asked for ``auto``.
        ``rank_slabs`` and the out-of-core ``window_tiles`` are
        re-derived from the *resolved* backend so an explicit override
        never carries stale residency metadata (and an explicit tiled
        or streaming backend on an untuned runtime gets the real slab /
        window counts — the runtime knows every mode's ``i_pad``); for
        an unresolved ``auto`` they stay trivial — only the ops-level
        dispatch knows what auto becomes.
        """
        if self.mode_plans is not None:
            p = self.mode_plans[mode]
            if backend != "auto":
                p = p._replace(backend=backend)
        else:
            p = ModePlan(backend, self.blk, self.tile_rows,
                         ordering=self.ordering)
        slabs = 1
        if p.backend in ("pallas_fused_tiled", "pallas_fused_gather_tiled",
                         kops.STREAM_BACKEND):
            slabs = kops.padded_rank(self.rank) // kops.MXU_RANK_MULTIPLE
        window = ()
        if p.backend == kops.STREAM_BACKEND:
            from ..oocore.planner import stream_window_tiles
            window = tuple(stream_window_tiles(p.blk, self.i_pad[w])
                           for w in range(self.nmodes) if w != mode)
        return p._replace(rank_slabs=slabs, window_tiles=window)


def prepare_runtime(
    ft: FlycooTensor, rank: int, *, blk: int | None = None,
    tile_rows: int = 8, uniform_cap: bool = False, table=None,
    gather_dtype: str = "float32", ordering: str | None = None,
) -> tuple[DynasorRuntime, tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Build runtime metadata + the initial mode-0 packed layout (H_0).

    Args:
      uniform_cap: escape hatch — size every remap exchange to the max
        transition capacity (the pre-tuning behavior) instead of each
        transition's own ``remap_capacities`` bound.
      table: optional calibration table / cost model from ``repro.tune``;
        when given, each mode gets a tuned ``(backend, blk, tile_rows)``
        plan (``rows_cap`` rounds to the tuned tile) and ``backend="auto"``
        callers follow it. ``None`` keeps the static configuration.
      gather_dtype: ``"float32"`` (default) or ``"bfloat16"`` — threaded
        to every fused-kernel mode step (see ``DynasorRuntime``).
      ordering: :data:`repro.reorder.ORDERINGS` locality policy threaded
        to every mode step (in-jit re-ranking — the order survives the
        dynamic remapping between modes). ``None`` (default) inherits
        ``ft.ordering``, so a tensor built with
        ``build_flycoo(..., ordering=...)`` keeps its policy end to end.

    Its wall time is counted once per call as ``runtime.prepare_s``, and
    is a ``runtime.prepare`` span under an enabled tracer (:mod:`repro.obs`).
    """
    t0 = time.perf_counter()
    with _tracer.get_tracer().span("runtime.prepare"):
        ordering = ft.ordering if ordering is None else ordering
        D = ft.params.num_workers
        plans = None
        if table is not None:
            from ..tune.model import plan_modes  # deferred: tune imports core
            plans = plan_modes(table, ft, rank, ordering=ordering)
        tiles = (
            tuple(p.tile_rows for p in plans) if plans is not None
            else (tile_rows,) * ft.nmodes
        )
        rows_cap = tuple(
            int(-(-mp.rows_cap // t) * t)                    # round to tile
            for mp, t in zip(ft.modes, tiles)
        )
        i_pad = tuple(D * rc for rc in rows_cap)
        if blk is None:
            blk = kops.runtime_blk(ft.params.g, compiled=_execution.compiles())
        caps = remap_lib.remap_capacities(ft)
        # Count the per-transition all_to_all allocation here, once per
        # runtime build — every driver (CP-ALS, benches, serving) that
        # constructs a runtime gets its collective traffic into the obs
        # registry without bench-side re-derivation.
        _obs.record_remap_exchange(caps, D, ft.nmodes, uniform_cap=uniform_cap)
        rt = DynasorRuntime(
            num_workers=D, nmodes=ft.nmodes, rank=rank, rows_cap=rows_cap,
            i_pad=i_pad, nnz_cap=ft.nnz_cap,
            bucket_cap=max(caps), shape=ft.tensor.shape,
            blk=blk, tile_rows=tile_rows,
            bucket_caps=None if uniform_cap else tuple(caps),
            mode_plans=plans, gather_dtype=gather_dtype, ordering=ordering,
        )
        # pack_mode used flycoo rows_cap; re-pad indices to the tile-rounded
        # layout.
        idx, val, mask = pack_mode(ft, 0)
        idx = _repad_indices(ft, idx, rows_cap)
    _obs.add("runtime.prepare_s", time.perf_counter() - t0)
    return rt, (idx, val, mask)


def _repad_indices(ft: FlycooTensor, idx: np.ndarray,
                   rows_cap: Sequence[int]) -> np.ndarray:
    """Map device-major slots from flycoo rows_cap to tile-rounded rows_cap."""
    out = idx.copy()
    for n, mp in enumerate(ft.modes):
        old, new = mp.rows_cap, rows_cap[n]
        if old == new:
            continue
        dev = idx[..., n] // old
        out[..., n] = dev * new + idx[..., n] % old
    return out


def permuted_factor_init(ft: FlycooTensor, mode: int, rank: int,
                         rows_cap: int, seed: int) -> np.ndarray:
    """Random factor in permuted row space; padding rows exactly zero."""
    rng = np.random.default_rng(seed * 1000 + mode)
    D = ft.params.num_workers
    nat = rng.standard_normal((ft.tensor.shape[mode], rank)).astype(np.float32)
    out = np.zeros((D * rows_cap, rank), np.float32)
    mp = ft.modes[mode]
    # natural row r lives at permuted slot row_perm[r] (re-padded to rows_cap)
    slot = (mp.row_perm // mp.rows_cap) * rows_cap + mp.row_perm % mp.rows_cap
    out[slot] = nat
    return out


def init_factors(ft: FlycooTensor, rt: DynasorRuntime, seed: int = 0):
    return [
        permuted_factor_init(ft, n, rt.rank, rt.rows_cap[n], seed)
        for n in range(rt.nmodes)
    ]


def unpermute_factor(ft: FlycooTensor, rt: DynasorRuntime, mode: int,
                     factor: np.ndarray) -> np.ndarray:
    """Permuted (i_pad, R) → natural (I_n, R)."""
    mp = ft.modes[mode]
    slot = (mp.row_perm // mp.rows_cap) * rt.rows_cap[mode] \
        + mp.row_perm % mp.rows_cap
    return np.asarray(factor)[slot]


# ---------------------------------------------------------------------------
# shard_map-inner primitives
# ---------------------------------------------------------------------------

def _pack_payload(idx, val):
    """Coordinates + value as one int32 row per nonzero.

    The value travels as its fp32 bit pattern. The reverse (coordinates
    as fp32 bits) would turn small indices into denormals, which the TPU
    flushes to zero when the exchange buffers are written.
    """
    bits = jax.lax.bitcast_convert_type(val.astype(jnp.float32), jnp.int32)
    return jnp.concatenate([idx.astype(jnp.int32), bits[:, None]], axis=1)


def _unpack_payload(payload, nmodes):
    val = jax.lax.bitcast_convert_type(payload[:, nmodes], jnp.float32)
    return payload[:, :nmodes], val


def device_mttkrp(idx, val, mask, factors, mode: int, rt: DynasorRuntime,
                  backend: str):
    """Owner-computes local MTTKRP for ``mode`` → (rows_cap, R) f32.

    A tuned runtime (``rt.mode_plans``) supplies this mode's
    ``(backend, blk, tile_rows)``; the plan's backend applies when the
    caller passes ``auto``, and may be ``segsum``.
    """
    if backend != "segsum" and backend != "auto" \
            and backend not in kops.BACKENDS:
        raise ValueError(
            f"unknown MTTKRP backend {backend!r}: expected 'segsum', "
            f"'auto' or one of {kops.BACKENDS}")
    plan = rt.plan_for(mode, backend)
    backend = plan.backend
    dev = jax.lax.axis_index(AXIS)
    rows_cap = rt.rows_cap[mode]
    if backend != "segsum":
        # interpret/compiled comes from the repro.runtime.execution
        # policy (the default), never a per-call hardcode.
        return kops.mttkrp_device_step(
            idx, val, mask, factors, mode=mode, rows_cap=rows_cap,
            row_offset=dev * rows_cap, blk=plan.blk,
            tile_rows=plan.tile_rows, backend=backend,
            gather_dtype=rt.gather_dtype, ordering=plan.ordering,
        )
    # segsum: plain XLA segment-sum path (dry-run / TPU-lowerable default).
    local_row = jnp.where(mask, idx[:, mode] - dev * rows_cap, 0)
    ell = jnp.where(mask, val, 0.0)[:, None].astype(factors[0].dtype)
    for w in range(rt.nmodes):
        if w != mode:
            ell = ell * jnp.take(factors[w], idx[:, w], axis=0)
    return jax.ops.segment_sum(
        ell.astype(jnp.float32), local_row, num_segments=rows_cap,
        indices_are_sorted=True,
    )


def device_remap(idx, val, mask, next_mode: int, rt: DynasorRuntime):
    """Dynamic tensor remapping: re-bucket owned nonzeros for ``next_mode``.

    The exchange is sized to *this transition's* capacity
    (``rt.bucket_cap_for``) — each all_to_all allocates only the padding
    its own (src, dst) bound requires, not the global max.

    Returns ``(idx', val', mask', dropped)`` — the new owner-sorted layout.
    """
    # Registered failure boundary (repro.resilience): the all_to_all is
    # the one collective of the sweep — an interconnect hiccup lands
    # here. Fires at trace time under jit; the stepped driver retries
    # the whole remap call.
    _faults.fault_site("distributed.remap")
    D = rt.num_workers
    cap = rt.bucket_cap_for((next_mode - 1) % rt.nmodes)
    dest = jnp.where(
        mask, (idx[:, next_mode] // rt.rows_cap[next_mode]).astype(jnp.int32), D
    )
    payload = _pack_payload(idx, val)
    buckets, bmask, dropped = remap_lib.bucket_by_destination(
        dest, payload, D, cap
    )
    recv, recv_mask = remap_lib.exchange(buckets, bmask, AXIS)
    flat = recv.reshape(D * cap, rt.payload_width)
    fmask = recv_mask.reshape(D * cap)
    ridx, _ = _unpack_payload(flat, rt.nmodes)
    key = ridx[:, next_mode]  # permuted slot == sort by local row
    out, omask = remap_lib.compact_sorted(flat, fmask, key, rt.nnz_cap)
    oidx, oval = _unpack_payload(out, rt.nmodes)
    oval = jnp.where(omask, oval, 0.0)
    # Padding entries point at row 0 (in-bounds gather, zero value: harmless).
    oidx = jnp.where(omask[:, None], oidx, 0)
    return oidx, oval, omask, dropped


def _dense_partial_mttkrp(idx, val, mask, factors, mode: int,
                          rt: DynasorRuntime):
    """Non-owner path: dense (i_pad, R) partial + all-reduce (baseline)."""
    ell = jnp.where(mask, val, 0.0)[:, None].astype(factors[0].dtype)
    for w in range(rt.nmodes):
        if w != mode:
            ell = ell * jnp.take(factors[w], idx[:, w], axis=0)
    partial = jax.ops.segment_sum(
        ell.astype(jnp.float32), jnp.where(mask, idx[:, mode], 0),
        num_segments=rt.i_pad[mode],
    )
    return jax.lax.psum(partial, AXIS)


# ---------------------------------------------------------------------------
# Top-level jitted builders
# ---------------------------------------------------------------------------

def make_spmttkrp_all_modes(
    rt: DynasorRuntime, mesh: Mesh, *, backend: str = "segsum",
    remap: bool = True,
) -> Callable:
    """spMTTKRP along all modes (the paper's headline benchmark op).

    Returns a jitted fn ``(idx, val, mask, factors) ->
    (mttkrp_outs, (idx', val', mask'), diagnostics)`` where ``mttkrp_outs``
    is a list of replicated ``(i_pad_n, R)`` MTTKRP results (pre-solve) and
    the primed tensors are the remapped layout (back at mode 0 after a full
    cycle).

    ``remap=False`` is Fig. 9 "Case 2": the layout stays in mode-0 order; for
    modes ≥ 1 each device computes a dense partial over *all* rows and
    all-reduces it (the intermediate-value traffic Dynasor avoids).
    """

    def inner(idx, val, mask, *factors):
        # shard_map blocks keep a leading (1, ...) device axis — drop it.
        idx, val, mask = idx[0], val[0], mask[0]
        factors = list(factors)
        outs = []
        diags = {"dropped": jnp.zeros((), jnp.int32)}
        for n in range(rt.nmodes):
            owner_ok = remap or n == 0
            if owner_ok:
                local = device_mttkrp(idx, val, mask, factors, n, rt, backend)
                full = jax.lax.all_gather(local, AXIS, axis=0, tiled=True)
            else:
                full = _dense_partial_mttkrp(idx, val, mask, factors, n, rt)
            outs.append(full)
            if remap:
                nxt = (n + 1) % rt.nmodes
                idx, val, mask, dropped = device_remap(idx, val, mask, nxt, rt)
                diags["dropped"] = diags["dropped"] + dropped.astype(jnp.int32)
        return outs, (idx[None], val[None], mask[None]), diags

    spec_t = P(AXIS)
    spec_r = P()
    shmapped = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(spec_t, spec_t, spec_t) + (spec_r,) * rt.nmodes,
        out_specs=([spec_r] * rt.nmodes, (spec_t, spec_t, spec_t),
                   {"dropped": spec_r}),
        check_vma=False,
    )
    return jax.jit(shmapped)


def make_baseline_all_modes(rt: DynasorRuntime, mesh: Mesh) -> Callable:
    """ALTO/HiCOO-style nonzero-parallel baseline.

    Tensor split evenly by nonzero count (no ownership structure); every
    mode produces a dense ``(i_pad_n, R)`` partial per device and all-reduces
    it. Same outputs as Dynasor; different (much larger) collective traffic.
    """

    def inner(idx, val, mask, *factors):
        idx, val, mask = idx[0], val[0], mask[0]
        factors = list(factors)
        outs = [
            _dense_partial_mttkrp(idx, val, mask, factors, n, rt)
            for n in range(rt.nmodes)
        ]
        return outs

    spec_t = P(AXIS)
    spec_r = P()
    shmapped = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(spec_t, spec_t, spec_t) + (spec_r,) * rt.nmodes,
        out_specs=[spec_r] * rt.nmodes,
        check_vma=False,
    )
    return jax.jit(shmapped)


def even_split_pack(ft: FlycooTensor, rt: DynasorRuntime):
    """Nonzero-parallel layout for the baseline: even chunks, natural order.

    Indices are still in permuted row space so baseline outputs are directly
    comparable with Dynasor outputs.
    """
    D = rt.num_workers
    nnz = ft.nnz
    cap = -(-nnz // D)
    idx = np.zeros((D, cap, ft.nmodes), np.int32)
    val = np.zeros((D, cap), np.float32)
    mask = np.zeros((D, cap), bool)
    perm_idx = _repad_indices(ft, ft.perm_indices.astype(np.int32), rt.rows_cap)
    for d in range(D):
        lo, hi = d * cap, min(nnz, (d + 1) * cap)
        k = hi - lo
        if k <= 0:
            continue
        idx[d, :k] = perm_idx[lo:hi]
        val[d, :k] = ft.tensor.values[lo:hi]
        mask[d, :k] = True
    return idx, val, mask
