"""Distributed Dynasor (shard_map owner-computes + remap) — runs in a
subprocess so the 4-device XLA flag never leaks into other tests."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
from jax.sharding import Mesh
from repro.core.tensors import random_sparse_tensor, SparseTensor
from repro.core.flycoo import build_flycoo
from repro.core.mttkrp import mttkrp_elementwise_ref
from repro.core import distributed as dist
from repro.core.cpals import cp_als, cp_als_distributed
import itertools

# --- owner-computes == elementwise ref == all-reduce baseline -------------
t = random_sparse_tensor((60, 45, 30), 500, seed=1, distribution="powerlaw")
ft = build_flycoo(t, 4, m_bounds=(4, 16), g_bounds=(8, 64), cache_bytes=1<<20)
rt, (idx, val, mask) = dist.prepare_runtime(ft, rank=8, tile_rows=8)
mesh = Mesh(np.array(jax.devices()), (dist.AXIS,))
factors = dist.init_factors(ft, rt, seed=0)

fn = dist.make_spmttkrp_all_modes(rt, mesh, backend="segsum", remap=True)
outs, packed2, diags = fn(idx, val, mask, *factors)
assert int(diags["dropped"]) == 0
perm_idx = dist._repad_indices(ft, ft.perm_indices.astype(np.int32), rt.rows_cap)
for n in range(3):
    ref = mttkrp_elementwise_ref(perm_idx, t.values, factors, n, out_rows=rt.i_pad[n])
    err = np.abs(np.asarray(outs[n]) - ref).max() / (np.abs(ref).max() + 1e-9)
    assert err < 1e-4, (n, err)

# remap round-trip: a full mode cycle returns an equivalent layout
outs2, _, _ = fn(*packed2, *factors)
for n in range(3):
    err = np.abs(np.asarray(outs2[n]) - np.asarray(outs[n])).max()
    assert err < 1e-3, (n, err)

# lock-free claim: owner-computes equals nonzero-parallel + all-reduce
fnb = dist.make_baseline_all_modes(rt, mesh)
outsb = fnb(*dist.even_split_pack(ft, rt), *factors)
for n in range(3):
    r = np.asarray(outs[n]); g = np.asarray(outsb[n])
    assert np.abs(g - r).max() / (np.abs(r).max() + 1e-9) < 1e-4

# the row-gathering Pallas rung inside shard_map
fnp = dist.make_spmttkrp_all_modes(rt, mesh, backend="pallas_fused",
                                   remap=True)
outsp, _, _ = fnp(idx, val, mask, *factors)
for n in range(3):
    r = np.asarray(outs[n]); g = np.asarray(outsp[n])
    assert np.abs(g - r).max() / (np.abs(r).max() + 1e-9) < 1e-4

# --- 4-mode fused N-mode kernel end-to-end under shard_map ----------------
t4 = random_sparse_tensor((20, 15, 12, 10), 400, seed=2)
ft4 = build_flycoo(t4, 4, m_bounds=(2, 8), g_bounds=(8, 64), cache_bytes=1<<20)
rt4, (idx4, val4, mask4) = dist.prepare_runtime(ft4, rank=8, tile_rows=8)
f4 = dist.init_factors(ft4, rt4, seed=0)
perm4 = dist._repad_indices(ft4, ft4.perm_indices.astype(np.int32), rt4.rows_cap)
for bk in ("pallas_fused", "auto"):
    fn4 = dist.make_spmttkrp_all_modes(rt4, mesh, backend=bk, remap=True)
    outs4, _, d4 = fn4(idx4, val4, mask4, *f4)
    assert int(d4["dropped"]) == 0
    for n in range(4):
        ref = mttkrp_elementwise_ref(perm4, t4.values, f4, n, out_rows=rt4.i_pad[n])
        err = np.abs(np.asarray(outs4[n]) - ref).max() / (np.abs(ref).max() + 1e-9)
        assert err < 1e-4, ("4mode", bk, n, err)

# --- distributed CP-ALS == single-device CP-ALS ----------------------------
rng = np.random.default_rng(0)
shape = (24, 18, 12); R = 4
facs = [rng.standard_normal((d, R)) for d in shape]
dense = np.einsum("ir,jr,kr->ijk", *facs)
idx2 = np.array(list(itertools.product(*[range(d) for d in shape])), dtype=np.int32)
td = SparseTensor(idx2, dense.reshape(-1).astype(np.float32), shape)
res_s = cp_als(td, rank=R, iters=25, seed=1)
ft2 = build_flycoo(td, 4, m_bounds=(2, 8), g_bounds=(8, 64), cache_bytes=1<<20)
res_d = cp_als_distributed(ft2, R, mesh, iters=25, seed=1)
assert res_d.fit > 0.999, res_d.fits
rec = np.einsum("r,ir,jr,kr->ijk", res_d.lam, *res_d.factors)
assert np.linalg.norm(rec - dense) / np.linalg.norm(dense) < 1e-2

# --- owner-computes MoE (shard_map EP) == gather baseline, fwd + grad -----
from jax.sharding import Mesh as Mesh2
import jax.numpy as jnp
from repro.models import moe
from repro.models.params import init_params
from repro.models.sharding import use_mesh_rules, default_rules
mesh2 = Mesh2(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
d, f, E, K = 16, 32, 8, 2
mparams = init_params({"m": moe.moe_specs(d, f, E, 1, E)}, seed=0)["m"]
xm = jnp.asarray(np.random.default_rng(0).standard_normal((4, 8, d)),
                 jnp.float32)
ref_y, _ = moe._moe_apply_gather(mparams, xm, n_real=E, top_k=K,
                                 deterministic_cap=64)
with use_mesh_rules(mesh2, default_rules()):
    own_y, own_m = jax.jit(lambda p, x: moe.moe_apply_owner(
        p, x, n_real=E, top_k=K, deterministic_cap=64))(mparams, xm)
assert np.abs(np.asarray(own_y) - np.asarray(ref_y)).max() < 2e-4
assert int(own_m["moe_dropped"]) == 0

def loss_o(p):
    with use_mesh_rules(mesh2, default_rules()):
        y, _ = moe.moe_apply_owner(p, xm, n_real=E, top_k=K,
                                   deterministic_cap=64)
    return jnp.sum(y ** 2)
def loss_g(p):
    y, _ = moe._moe_apply_gather(p, xm, n_real=E, top_k=K,
                                 deterministic_cap=64)
    return jnp.sum(y ** 2)
g1 = jax.jit(jax.grad(loss_o))(mparams)
g2 = jax.jit(jax.grad(loss_g))(mparams)
for kk in ("w_gate", "w_up", "w_down", "router"):
    e = np.abs(np.asarray(g1[kk]) - np.asarray(g2[kk])).max()
    assert e / (np.abs(np.asarray(g2[kk])).max() + 1e-9) < 1e-3, kk
print("DISTRIBUTED-OK")
"""


@pytest.mark.slow
def test_distributed_dynasor_subprocess():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert "DISTRIBUTED-OK" in out.stdout, out.stdout + out.stderr
