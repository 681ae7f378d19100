"""End-to-end distributed CP decomposition (the paper's headline workload).

Runs Dynasor's owner-computes spMTTKRP with dynamic tensor remapping under
``shard_map`` on 8 (forced host) devices, decomposes a dense low-rank
tensor exactly, and compares against the nonzero-parallel + all-reduce
baseline (the ALTO/HiCOO traffic pattern).

  PYTHONPATH=src python examples/cp_decompose_distributed.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import itertools
import time

import numpy as np
import jax
from jax.sharding import Mesh

from repro.core import distributed as dist
from repro.core.cpals import cp_als_distributed
from repro.core.flycoo import build_flycoo
from repro.core.tensors import SparseTensor, frostt_like
from repro.runtime.compile_cache import enable_compile_cache


def main():
    D = len(jax.devices())
    print(f"=== distributed Dynasor CP-ALS ({D} workers) ===")
    mesh = Mesh(np.array(jax.devices()), (dist.AXIS,))

    # exact recovery of a dense rank-4 tensor
    rng = np.random.default_rng(0)
    shape, R = (32, 24, 16), 4
    facs = [rng.standard_normal((d, R)) for d in shape]
    dense = np.einsum("ir,jr,kr->ijk", *facs)
    idx = np.array(list(itertools.product(*map(range, shape))), np.int32)
    t = SparseTensor(idx, dense.reshape(-1).astype(np.float32), shape)
    ft = build_flycoo(t, D, m_bounds=(2, 8), g_bounds=(8, 64))
    res = cp_als_distributed(ft, R, mesh, iters=20, seed=1)
    rec = np.einsum("r,ir,jr,kr->ijk", res.lam, *res.factors)
    rel = np.linalg.norm(rec - dense) / np.linalg.norm(dense)
    print(f"fit={res.fit:.5f}  reconstruction rel-err={rel:.2e}  "
          f"iters={res.iters}")
    assert res.fit > 0.99

    # 4-mode decomposition through the fused N-mode Pallas path end-to-end
    # (backend="auto" dispatches every mode to the in-kernel-gather fused
    # kernel — the factors here easily fit VMEM-resident).
    shape4, R4 = (12, 10, 8, 6), 8   # R >= 8 so "auto" picks the fused path
    facs4 = [rng.standard_normal((d, R4)) for d in shape4]
    dense4 = np.einsum("ir,jr,kr,lr->ijkl", *facs4)
    idx4 = np.array(list(itertools.product(*map(range, shape4))), np.int32)
    t4 = SparseTensor(idx4, dense4.reshape(-1).astype(np.float32), shape4)
    ft4 = build_flycoo(t4, D, m_bounds=(2, 8), g_bounds=(8, 64))
    res4 = cp_als_distributed(ft4, R4, mesh, iters=15, seed=1,
                              backend="auto")
    rec4 = np.einsum("r,ir,jr,kr,lr->ijkl", res4.lam, *res4.factors)
    rel4 = np.linalg.norm(rec4 - dense4) / np.linalg.norm(dense4)
    print(f"4-mode fused CP-ALS: fit={res4.fit:.5f}  rel-err={rel4:.2e}")
    assert res4.fit > 0.99

    # Dynasor vs nonzero-parallel all-reduce baseline on a FROSTT profile
    t2 = frostt_like("nell-2", scale=0.15)
    ft2 = build_flycoo(t2, D)
    rt, (pidx, pval, pmask) = dist.prepare_runtime(ft2, rank=16)
    factors = dist.init_factors(ft2, rt, seed=0)
    dynasor = dist.make_spmttkrp_all_modes(rt, mesh, backend="segsum")
    baseline = dist.make_baseline_all_modes(rt, mesh)
    bidx, bval, bmask = dist.even_split_pack(ft2, rt)

    for name, fn, args in (("dynasor", dynasor, (pidx, pval, pmask)),
                           ("allreduce-baseline", baseline,
                            (bidx, bval, bmask))):
        out = fn(*args, *factors)         # warmup/compile
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(3):
            jax.block_until_ready(fn(*args, *factors))
        dt = (time.perf_counter() - t0) / 3
        print(f"{name:20s} all-modes spMTTKRP: {dt * 1e3:.1f} ms "
              f"(nnz={t2.nnz}, R=16, {D} workers)")
    print("note: on emulated same-host devices collectives are ~free, so "
          "the all-reduce baseline wins wall-clock at toy scale; the "
          "compiled collective-byte comparison (benchmarks/"
          "bench_collective_traffic.py) is the hardware-relevant metric "
          "(baseline moves 1.4-1.8x more bytes).")
    print("OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
