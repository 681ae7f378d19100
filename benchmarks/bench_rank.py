"""Paper Fig. 10: total execution time vs. factor-matrix rank R — plus the
N-mode kernel comparisons.

spMTTKRP is memory-bound; traffic ∝ R ⇒ time ≈ linear in R. We measure the
Dynasor sorted-stream engine across R ∈ {16 … 256} and fit the linearity.

Rows are written to ``experiments/bench/BENCH_rank.json``.

The ``gather_in_kernel`` section is the PR-4 tentpole record: on the
4-mode ``enron`` tensor, ``pallas_fused_gather`` (factor matrices resident in
VMEM, gather performed inside the kernel on an int32 index stream) vs.
``pallas_fused`` (gathered factor rows materialized in HBM by the
caller). The counted per-nonzero operand stream drops from
``(N−1)·R̂·4`` B of rows to ``(N−1)·4`` B of indices — a factor R̂ —
and each row records both terms plus the end-to-end ``auto`` decision
with and without factor-size knowledge.

The third section (``rank_tiled_largeR``) is the rank-cliff record: a
5-mode tensor at FLYCOO-shard-sized blocks (``blk=2048``), swept across
R ≥ 1024. At this block size the PR-2 static dispatch abandons the
fused win from R = 2048 up (the full-rank working set crosses the
64 MiB budget between the R=1024 row, which still fits, and the R=2048
row — both are recorded so the crossing is visible in the data). The
rank-tiled kernel (``pallas_fused_tiled``) keeps the fused traffic
saving at every rank (``rank_slabs`` × slab passes), and the
bf16-gather variant halves the gather bytes on top; each row records
the timed backends and the ``auto`` decision next to the PR-2 decision.
"""
from __future__ import annotations

import numpy as np

from repro.core.flycoo import build_flycoo
from repro.core.mttkrp import mttkrp_fused
from repro.core.tensors import random_sparse_tensor
from repro.kernels.mttkrp import ops as kops

from .bench_total_time import _dynasor_all_modes
from .common import (bench_tensor, pr2_static_backend, row, timeit,
                     write_bench_json)


def run(quick: bool = True, scale: float = 1.0):
    rows = []
    tensors = ("nell-2", "flickr") if quick else (
        "nell-2", "nell-1", "flickr", "delicious", "vast")
    ranks = (16, 32, 64, 128, 256)
    for name in tensors:
        t = bench_tensor(name, scale=scale)
        ft = build_flycoo(t, num_workers=8)
        times = []
        for rank in ranks:
            fn = _dynasor_all_modes(ft, rank)
            tt = timeit(fn, iters=3)
            times.append(tt)
            rows.append(row("rank_fig10", tensor=name, rank=rank,
                            seconds=round(tt, 5)))
        # linearity: correlation of time vs rank
        r = float(np.corrcoef(ranks, times)[0, 1])
        rows.append(row("rank_fig10", tensor=name, rank="linearity_r",
                        seconds=round(r, 4)))

    # --- gather-in-kernel on the 4-mode tensor -----------------------------
    t4 = bench_tensor("enron", scale=0.25 if quick else 1.0)
    gather_rows = _gather_in_kernel_rows(t4, quick)
    rows.extend(gather_rows)

    # --- rank-tiled + bf16 at R >= 1024 (the removed VMEM cliff) ----------
    large_rows = _large_rank_rows(quick)
    rows.extend(large_rows)
    # The suite's full row set is the artifact (run.py no longer writes
    # side-channel dumps): fig-10 linearity rows included.
    write_bench_json("rank", rows)
    return rows


def _gather_in_kernel_rows(t4, quick: bool) -> list[dict]:
    """PR-4 tentpole: per-nonzero HBM operand bytes, gather vs fused.

    The fused path materializes every gathered factor row in HBM —
    ``(N−1)·R̂·4`` B written and re-read per nonzero before the kernel
    ever runs. The gather family streams ``(N−1)·4`` B of int32 indices
    instead and holds the replicated factors in VMEM. Wall times are
    interpret-mode emulation; the counted bytes are the record.
    """
    import jax.numpy as jnp
    rng = np.random.default_rng(2)
    nmodes = t4.nmodes
    idx = jnp.asarray(t4.indices.astype(np.int32))
    val = jnp.asarray(t4.values.astype(np.float32))
    out = []
    for rank in ((32, 128) if quick else (32, 64, 128, 256)):
        factors = [jnp.asarray(rng.standard_normal((d, rank)), jnp.float32)
                   for d in t4.shape]

        def make(backend):
            def run():
                return [mttkrp_fused(idx, val, factors, n, t4.shape[n],
                                     blk=512, tile_rows=128, backend=backend)
                        for n in range(nmodes)]
            return run

        t_gather = timeit(make("pallas_fused_gather"), warmup=1, iters=2)
        t_fused = timeit(make("pallas_fused"), warmup=1, iters=2)
        rpad = kops.padded_rank(rank)
        fused_operand_B = (nmodes - 1) * rpad * 4     # materialized rows
        index_stream_B = (nmodes - 1) * 4             # int32 indices
        factor_rows = sum(t4.shape) - min(t4.shape)   # worst mode resident
        auto_fr = kops.select_backend(
            "auto", nmodes=nmodes, rank=rank, blk=512, tile_rows=128,
            factor_rows=factor_rows)
        auto_no_fr = kops.select_backend(
            "auto", nmodes=nmodes, rank=rank, blk=512, tile_rows=128)
        out.append(row(
            "gather_in_kernel", tensor="enron", nmodes=nmodes, nnz=t4.nnz,
            rank=rank, rank_padded=rpad,
            gather_interp_s=round(t_gather, 5),
            fused_interp_s=round(t_fused, 5),
            fused_operand_B_per_nnz=fused_operand_B,
            gather_index_stream_B_per_nnz=index_stream_B,
            operand_traffic_ratio=round(fused_operand_B / index_stream_B, 1),
            operand_traffic_saved_MB=round(
                t4.nnz * nmodes * (fused_operand_B - index_stream_B) / 1e6,
                3),
            auto_with_factor_rows=auto_fr,
            auto_without_factor_rows=auto_no_fr,
            note="times are interpret-mode emulation; traffic is counted"))
    return out


def _large_rank_rows(quick: bool) -> list[dict]:
    """5-mode, shard-sized blocks, R from 1024 up: fused wins past the
    old cliff. Wall times are interpret-mode emulation (CPU container);
    the dispatch decisions and counted traffic are the record."""
    import jax.numpy as jnp

    shape = (256, 48, 32, 24, 16)
    nmodes = len(shape)
    blk, tile_rows = 2048, 128          # FLYCOO g-sized nonzero block
    t5 = random_sparse_tensor(shape, 1500 if quick else 4000, seed=0)
    idx = jnp.asarray(t5.indices.astype(np.int32))
    val = jnp.asarray(t5.values.astype(np.float32))
    rng = np.random.default_rng(1)
    out = []
    for rank in ((1024, 2048) if quick else (1024, 2048, 4096)):
        factors = [jnp.asarray(rng.standard_normal((d, rank)), jnp.float32)
                   for d in shape]

        def make(backend, gather_dtype="float32"):
            return lambda: mttkrp_fused(
                idx, val, factors, 0, shape[0], blk=blk,
                tile_rows=tile_rows, backend=backend,
                gather_dtype=gather_dtype)

        t_tiled = timeit(make("pallas_fused_tiled"), warmup=1, iters=2)
        t_bf16 = timeit(make("pallas_fused_tiled", "bfloat16"),
                        warmup=1, iters=2)
        auto = kops.select_backend("auto", nmodes=nmodes, rank=rank,
                                   blk=blk, tile_rows=tile_rows)
        pr2 = pr2_static_backend(nmodes, rank, blk, tile_rows)
        slabs = kops.padded_rank(rank) // kops.MXU_RANK_MULTIPLE
        contrib_saved = t5.nnz * 2 * rank * 4       # write+read never paid
        bf16_saved = t5.nnz * (nmodes - 1) * rank * 2   # gathers at 2B not 4B
        out.append(row(
            "rank_tiled_largeR", tensor="synth5", nmodes=nmodes,
            nnz=t5.nnz, rank=rank, blk=blk, tile_rows=tile_rows,
            rank_slabs=slabs,
            fused_tiled_interp_s=round(t_tiled, 5),
            bf16_tiled_interp_s=round(t_bf16, 5),
            auto_backend=auto, pr2_auto_backend=pr2,
            contrib_traffic_saved_MB=round(contrib_saved / 1e6, 3),
            bf16_gather_saved_MB=round(bf16_saved / 1e6, 3),
            note="times are interpret-mode emulation; traffic is counted"))
    return out
