"""Mixture-of-Experts with Dynasor-style owner-computes dispatch.

MoE dispatch **is** the paper's sparse problem in disguise: tokens are
nonzeros, experts are super-shards (output owners), and routing is the
dynamic remap. We reuse the same sort-into-static-buckets primitive as
``core.remap.bucket_by_destination``: tokens are argsorted by expert id into
a capacity-padded ``(E, cap, d)`` buffer (lock-free — each expert's GEMM
reads a private contiguous slab), processed with stacked-expert einsums, and
combined with a masked scatter-add. Over-capacity tokens are dropped
(counted in metrics), exactly like the remap-capacity accounting in
``core.remap``.

Expert weights carry the ``experts`` logical axis → the `model` mesh axis
(expert parallelism); the token buffers shard the same way, so each device
computes only its owned experts — the paper's "all updates to an output row
happen on its owner" invariant.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .params import ParamSpec
from .sharding import active_mesh_rules, shard

__all__ = ["moe_specs", "moe_apply", "moe_apply_owner", "router_assign"]


def moe_specs(d: int, d_ff: int, n_experts_padded: int, n_shared: int,
              n_experts_real: int, dtype=jnp.float32) -> dict:
    E = n_experts_padded
    specs = {
        "router": ParamSpec((d, E), ("embed", None), init="small",
                            dtype=jnp.float32),
        "w_gate": ParamSpec((E, d, d_ff), ("experts", "embed", "expert_mlp"),
                            dtype=dtype, fan_in_dims=(1,)),
        "w_up": ParamSpec((E, d, d_ff), ("experts", "embed", "expert_mlp"),
                          dtype=dtype, fan_in_dims=(1,)),
        "w_down": ParamSpec((E, d_ff, d), ("experts", "expert_mlp", "embed"),
                            dtype=dtype, fan_in_dims=(1,)),
    }
    if n_shared:
        f = n_shared * d_ff
        specs["shared"] = {
            "w_gate": ParamSpec((d, f), ("embed", "mlp"), dtype=dtype),
            "w_up": ParamSpec((d, f), ("embed", "mlp"), dtype=dtype),
            "w_down": ParamSpec((f, d), ("mlp", "embed"), dtype=dtype),
        }
    return specs


def router_assign(xf, router_w, n_real: int, top_k: int):
    """Router: returns ``(probs[(T,k)], ids[(T,k)], aux_loss)``."""
    T, _ = xf.shape
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router_w)
    E_pad = logits.shape[-1]
    pad_mask = jnp.arange(E_pad) < n_real
    logits = jnp.where(pad_mask[None, :], logits, -1e30)
    probs_full = jax.nn.softmax(logits, axis=-1)
    probs, ids = jax.lax.top_k(probs_full, top_k)
    probs = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss over real experts.
    density = jnp.zeros((E_pad,)).at[ids.reshape(-1)].add(1.0) / (T * top_k)
    mean_prob = probs_full.mean(0)
    aux = n_real * jnp.sum(density * mean_prob)
    return probs, ids.astype(jnp.int32), aux


def moe_apply(params, x, *, n_real: int, top_k: int,
              capacity_factor: float = 1.25, deterministic_cap: int = 0,
              impl: str = "auto"):
    """Apply the MoE block to ``x[(b, l, d)]`` → ``(y, metrics)``.

    ``impl='owner'`` (default whenever a mesh context is active) uses the
    Dynasor owner-computes dispatch under ``shard_map``: tokens stay on
    their data shard, every device locally buckets the tokens routed to
    *its* experts (the super-shard invariant — all updates to an output
    owner happen on that owner, lock-free), and one ``psum`` over the
    expert axis combines. No (tokens × d_model) tensor is ever replicated
    — the GSPMD gather fallback ('gather') materializes exactly that and
    is kept for single-device use and as the measured §Perf baseline.
    """
    ctx = active_mesh_rules()
    if impl == "auto":
        impl = "owner" if ctx is not None else "gather"
    if impl == "owner" and ctx is not None:
        return moe_apply_owner(params, x, n_real=n_real, top_k=top_k,
                               capacity_factor=capacity_factor,
                               deterministic_cap=deterministic_cap)
    return _moe_apply_gather(params, x, n_real=n_real, top_k=top_k,
                             capacity_factor=capacity_factor,
                             deterministic_cap=deterministic_cap)


def _moe_apply_gather(params, x, *, n_real: int, top_k: int,
                      capacity_factor: float = 1.25,
                      deterministic_cap: int = 0):
    """GSPMD gather/scatter dispatch (baseline path)."""
    b, l, d = x.shape
    T = b * l
    xf = shard(x.reshape(T, d), "batch", None)
    E = params["w_gate"].shape[0]
    probs, ids, aux = router_assign(xf, params["router"], n_real, top_k)

    cap = deterministic_cap or max(
        8, int(-(-T * top_k * capacity_factor // E)))
    # --- Dynasor dispatch: sort (token, slot) pairs by owning expert -----
    e_flat = ids.reshape(-1)                              # (T·k,)
    p_flat = probs.reshape(-1)
    tok = jnp.arange(T * top_k, dtype=jnp.int32) // top_k
    order = jnp.argsort(e_flat)                           # stable
    e_s = jnp.take(e_flat, order)
    tok_s = jnp.take(tok, order)
    p_s = jnp.take(p_flat, order)
    start = jnp.searchsorted(e_s, e_s, side="left")
    pos = jnp.arange(T * top_k, dtype=jnp.int32) - start.astype(jnp.int32)
    ok = pos < cap
    slot = jnp.where(ok, e_s * cap + pos, E * cap)        # dump slot
    buf_tok = jnp.zeros((E * cap + 1,), jnp.int32).at[slot].set(tok_s)[:-1]
    buf_p = jnp.zeros((E * cap + 1,), p_s.dtype).at[slot].set(p_s)[:-1]
    buf_ok = jnp.zeros((E * cap + 1,), bool).at[slot].set(ok)[:-1]
    dropped = jnp.sum(~ok)

    # --- owner-computes expert GEMMs ------------------------------------
    # Buffers shard (experts → model, capacity → data): each device owns a
    # private slab of its experts' tokens — the lock-free super-shard
    # property — and the (E, cap, d_ff) hidden never materializes anywhere.
    xe = jnp.take(xf, buf_tok, axis=0).reshape(E, cap, d)
    xe = jnp.where(buf_ok.reshape(E, cap, 1), xe, 0)
    xe = shard(xe, "experts", "batch", None)
    dt = x.dtype
    gate = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"].astype(dt))
    up = jnp.einsum("ecd,edf->ecf", xe, params["w_up"].astype(dt))
    h = jax.nn.silu(gate) * up
    h = shard(h, "experts", "batch", None)
    out = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(dt))
    out = shard(out, "experts", "batch", None)

    # --- combine (masked scatter-add, weighted by router prob) ----------
    w = jnp.where(buf_ok, buf_p, 0.0).astype(out.dtype)
    y = jnp.zeros((T, d), out.dtype).at[buf_tok.reshape(-1)].add(
        out.reshape(E * cap, d) * w.reshape(-1, 1))
    y = shard(y, "batch", None)

    if "shared" in params:
        sh = params["shared"]
        g = jnp.einsum("td,df->tf", xf, sh["w_gate"].astype(dt))
        u = jnp.einsum("td,df->tf", xf, sh["w_up"].astype(dt))
        y = y + jnp.einsum("tf,fd->td", jax.nn.silu(g) * u,
                           sh["w_down"].astype(dt))

    metrics = {"moe_aux": aux, "moe_dropped": dropped}
    return y.reshape(b, l, d), metrics


# ---------------------------------------------------------------------------
# Owner-computes dispatch (Dynasor super-shard semantics, shard_map)
# ---------------------------------------------------------------------------

def _resolve_axes(rules, name, mesh):
    r = rules.get(name)
    if r is None:
        return ()
    if isinstance(r, str):
        r = (r,)
    return tuple(a for a in r if a in mesh.axis_names)


def moe_apply_owner(params, x, *, n_real: int, top_k: int,
                    capacity_factor: float = 1.25,
                    deterministic_cap: int = 0):
    """Expert-parallel MoE with the paper's owner-computes invariant.

    Tokens stay sharded over the data axes (replicated over the expert
    axis); every device *locally* buckets the tokens routed to the experts
    it owns (sort-into-capacity-slabs — ``core.remap`` semantics), runs the
    expert GEMMs on its private slab, scatter-adds into a local partial
    output, and a single ``psum`` over the expert axis combines. The only
    other collective is the FSDP all-gather of the owned experts' weights.
    Nothing of size (tokens × d_model) is ever replicated.
    """
    from jax.sharding import PartitionSpec as P

    mesh, rules = active_mesh_rules()
    b, l, d = x.shape
    T = b * l
    xf = x.reshape(T, d)
    tok_axes = _resolve_axes(rules, "batch", mesh)
    exp_axes = _resolve_axes(rules, "experts", mesh)
    fsdp_axes = _resolve_axes(rules, "embed", mesh)
    if not exp_axes:
        return _moe_apply_gather(params, x, n_real=n_real, top_k=top_k,
                                 capacity_factor=capacity_factor,
                                 deterministic_cap=deterministic_cap)
    import math
    n_tok = math.prod(mesh.shape[a] for a in tok_axes) if tok_axes else 1
    n_exp = math.prod(mesh.shape[a] for a in exp_axes)
    E = params["w_gate"].shape[0]
    assert E % n_exp == 0, (E, n_exp)
    E_local = E // n_exp
    T_local = T // n_tok
    cap = deterministic_cap or max(
        8, int(-(-T_local * top_k * capacity_factor // E)))
    has_shared = "shared" in params
    dt = x.dtype

    def local(xf_l, router, wg_l, wu_l, wd_l, *shared_ws):
        eid = jax.lax.axis_index(exp_axes[0]) if len(exp_axes) == 1 else \
            jax.lax.axis_index(exp_axes)
        e0 = eid * E_local
        probs, ids, aux = router_assign(xf_l, router, n_real, top_k)
        e_flat = ids.reshape(-1)
        p_flat = probs.reshape(-1)
        tok = jnp.arange(T_local * top_k, dtype=jnp.int32) // top_k
        mine = (e_flat >= e0) & (e_flat < e0 + E_local)
        dest = jnp.where(mine, e_flat - e0, E_local)
        order = jnp.argsort(dest)
        d_s = jnp.take(dest, order)
        tok_s = jnp.take(tok, order)
        p_s = jnp.take(p_flat, order)
        start = jnp.searchsorted(d_s, d_s, side="left")
        pos = jnp.arange(d_s.shape[0], dtype=jnp.int32) - start.astype(
            jnp.int32)
        valid = d_s < E_local
        ok = valid & (pos < cap)
        slot = jnp.where(ok, d_s * cap + pos, E_local * cap)
        buf_tok = jnp.zeros((E_local * cap + 1,), jnp.int32
                            ).at[slot].set(tok_s)[:-1]
        buf_p = jnp.zeros((E_local * cap + 1,), p_s.dtype
                          ).at[slot].set(p_s)[:-1]
        buf_ok = jnp.zeros((E_local * cap + 1,), bool).at[slot].set(ok)[:-1]
        dropped = jnp.sum(valid) - jnp.sum(ok)

        xe = jnp.take(xf_l, buf_tok, axis=0).reshape(E_local, cap, d)
        xe = jnp.where(buf_ok.reshape(E_local, cap, 1), xe, 0)
        # FSDP gather of the owned experts' weights (ZeRO-3 style)
        wg = jax.lax.all_gather(wg_l, fsdp_axes, axis=1, tiled=True) \
            if fsdp_axes else wg_l
        wu = jax.lax.all_gather(wu_l, fsdp_axes, axis=1, tiled=True) \
            if fsdp_axes else wu_l
        wd = jax.lax.all_gather(wd_l, fsdp_axes, axis=2, tiled=True) \
            if fsdp_axes else wd_l
        gate = jnp.einsum("ecd,edf->ecf", xe, wg.astype(dt))
        up = jnp.einsum("ecd,edf->ecf", xe, wu.astype(dt))
        hh = jax.nn.silu(gate) * up
        out = jnp.einsum("ecf,efd->ecd", hh, wd.astype(dt))
        w = jnp.where(buf_ok, buf_p, 0.0).astype(out.dtype)
        y = jnp.zeros((T_local, d), out.dtype).at[buf_tok.reshape(-1)].add(
            out.reshape(E_local * cap, d) * w.reshape(-1, 1))

        if has_shared:
            sg_l, su_l, sd_l = shared_ws
            # shared weights: f sharded over expert axis, d over fsdp
            sg = jax.lax.all_gather(sg_l, fsdp_axes, axis=0, tiled=True) \
                if fsdp_axes else sg_l
            su = jax.lax.all_gather(su_l, fsdp_axes, axis=0, tiled=True) \
                if fsdp_axes else su_l
            sd = jax.lax.all_gather(sd_l, fsdp_axes, axis=1, tiled=True) \
                if fsdp_axes else sd_l
            g = jnp.einsum("td,df->tf", xf_l, sg.astype(dt))
            u = jnp.einsum("td,df->tf", xf_l, su.astype(dt))
            y = y + jnp.einsum("tf,fd->td", jax.nn.silu(g) * u,
                               sd.astype(dt))
        y = jax.lax.psum(y, exp_axes)
        # each routed pair has exactly one owner → plain global sum
        dropped = jax.lax.psum(dropped, exp_axes + tok_axes)
        if tok_axes:
            aux = jax.lax.pmean(aux, tok_axes)
        return y, aux, dropped

    tok_spec = tok_axes if tok_axes else None
    in_specs = [
        P(tok_spec, None),                                # xf
        P(None, None),                                    # router
        P(exp_axes, fsdp_axes or None, None),             # w_gate
        P(exp_axes, fsdp_axes or None, None),             # w_up
        P(exp_axes, None, fsdp_axes or None),             # w_down
    ]
    args = [xf, params["router"], params["w_gate"], params["w_up"],
            params["w_down"]]
    if has_shared:
        sh = params["shared"]
        in_specs += [P(fsdp_axes or None, exp_axes),      # shared w_gate
                     P(fsdp_axes or None, exp_axes),      # shared w_up
                     P(exp_axes, fsdp_axes or None)]      # shared w_down
        args += [sh["w_gate"], sh["w_up"], sh["w_down"]]
    y, aux, dropped = jax.shard_map(
        local, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(tok_spec, None), P(), P()),
        check_vma=False,
    )(*args)
    return y.reshape(b, l, d), {"moe_aux": aux, "moe_dropped": dropped}
