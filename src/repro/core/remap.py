"""Dynamic tensor remapping (paper §III-B, Alg. 2 line 27).

On CPU, Dynasor writes each nonzero into a second ``|T|`` buffer at the slot
it needs for the *next* mode while computing the current one. On TPU the
equivalent is a **bucketed all_to_all**: while mode ``n`` is being computed,
every nonzero is bucketed by the device that owns its mode-``n+1`` output row
and exchanged. XLA schedules the collective asynchronously with the gather/
compute stream — the TPU analogue of the paper's "integrated same-thread
remapping" (Fig. 2). Storage stays ``2·|T|`` (send + receive buffers), never
``N·|T|`` mode-specific copies.

All shapes are static: bucket capacity is the preprocessing-time max bucket
size (like MoE capacity), padding is masked, and every element is accounted
for (the round-trip property is tested). Each bucket is a contiguous window
of the destination-sorted stream, filled by one row gather: the bucketing
holds no scatter and no dump row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .flycoo import FlycooTensor

__all__ = [
    "remap_capacity",
    "remap_capacities",
    "bucket_by_destination",
    "exchange",
    "compact_sorted",
    "remap_local",
]


def remap_capacities(ft: FlycooTensor) -> list[int]:
    """Per-transition max (src, dst) exchange sizes, mode n → n+1 (cyclic).

    One entry per mode transition of the N-mode ALS cycle — the exact
    all_to_all payload bound each remap pays. ``remap_capacity`` (the max)
    sizes the static double buffer; the per-transition values feed the
    traffic accounting in ``benchmarks.bench_remap_traffic``.
    """
    D = ft.params.num_workers
    caps = []
    for n in range(ft.nmodes):
        nxt = (n + 1) % ft.nmodes
        src = ft.owner_of(n).astype(np.int64)
        dst = ft.owner_of(nxt).astype(np.int64)
        counts = np.bincount(src * D + dst, minlength=D * D)
        caps.append(max(1, int(counts.max())))
    return caps


def remap_capacity(ft: FlycooTensor) -> int:
    """Max nonzeros any (src, dst) pair exchanges over all mode transitions.

    Static upper bound for the all_to_all buckets, computed at preprocessing
    (the paper's shard-pointer metadata plays the same role).
    """
    return max(remap_capacities(ft))


def bucket_by_destination(dest, payload, num_devices: int, bucket_cap: int):
    """Gather ``payload`` rows into per-destination buckets (static shape).

    Args:
      dest: ``(n,)`` int32 destination worker per element; ``>= num_devices``
        marks padding/invalid elements.
      payload: ``(n, F)`` element data (coords + value packed as float/int —
        caller packs).
      num_devices: D.
      bucket_cap: per-destination capacity B.

    Returns:
      ``(buckets[(D, B, F)], bucket_mask[(D, B)], dropped)`` — ``dropped`` is
      the number of elements that exceeded capacity (must be 0 when
      ``bucket_cap >= remap_capacity``; exposed for the fault-tolerance
      check).

    Bucket ``d`` is one contiguous window of the destination-sorted stream:
    the stable sort puts its elements, in stream order, at positions
    ``[start_d, start_d + count_d)``, where ``start_d`` is the sum of the
    per-destination counts below ``d``. Each bucket is filled by gathering
    the payload rows of its window's first ``min(count_d, B)`` positions, so
    no scatter, no dump row and no search over the stream is made. Rows
    past a bucket's count are zero and masked; padding elements
    (``dest >= D``) sort after every bucket and land in none.
    """
    order = jnp.argsort(dest, stable=True)
    # D is small and static: the counts are D reductions over the stream,
    # the starts their exclusive cumulative sum.
    count = jnp.stack([jnp.sum(dest == d, dtype=dest.dtype)
                       for d in range(num_devices)])
    start = jnp.cumsum(count) - count
    # ``bucket_cap`` filler entries keep a window that runs past the end
    # of the stream where it starts (``dynamic_slice`` would clamp it).
    order_padded = jnp.concatenate(
        [order, jnp.zeros((bucket_cap,), order.dtype)])
    window = jnp.concatenate([
        jax.lax.dynamic_slice(order_padded, (start[d],), (bucket_cap,))
        for d in range(num_devices)
    ])
    rows = jnp.take(payload, window, axis=0).reshape(
        num_devices, bucket_cap, payload.shape[1])
    mask = (jnp.arange(bucket_cap, dtype=count.dtype)[None, :]
            < jnp.minimum(count, bucket_cap)[:, None])
    dropped = jnp.sum(jnp.maximum(count - bucket_cap, 0))
    return jnp.where(mask[..., None], rows, 0), mask, dropped


def exchange(buckets, bucket_mask, axis_name: str):
    """all_to_all the buckets: entry ``[d]`` goes to worker ``d``.

    Must be called inside ``shard_map``. Returns the received buckets
    (``recv[s]`` = what source ``s`` sent here) and their mask.
    """
    recv = jax.lax.all_to_all(
        buckets, axis_name, split_axis=0, concat_axis=0, tiled=True
    )
    recv_mask = jax.lax.all_to_all(
        bucket_mask, axis_name, split_axis=0, concat_axis=0, tiled=True
    )
    return recv, recv_mask


def compact_sorted(payload_flat, mask_flat, sort_key, out_cap: int):
    """Compact valid elements, sorted by ``sort_key``, into ``(out_cap, F)``.

    Invalid entries sort last (key forced to +max) and are truncated;
    the caller guarantees ``valid_count <= out_cap`` (FLYCOO preprocessing
    bound). Returns ``(payload[(out_cap, F)], mask[(out_cap,)])``.
    """
    big = jnp.iinfo(sort_key.dtype).max
    key = jnp.where(mask_flat, sort_key, big)
    order = jnp.argsort(key, stable=True)[:out_cap]
    return jnp.take(payload_flat, order, axis=0), jnp.take(mask_flat, order)


def remap_local(ft: FlycooTensor, to_mode: int):
    """Single-worker reference remap (numpy): the post-remap layout oracle.

    The distributed all_to_all remap of ``pack_mode(ft, from_mode)``
    must equal ``pack_mode(ft, to_mode)`` up to padding — and since the
    FLYCOO preprocessing already knows every mode's packed layout, the
    oracle *is* ``pack_mode(ft, to_mode)``. The signature says exactly
    that: no source-layout arguments, because the expected result does
    not depend on them (an earlier version accepted and silently
    ignored ``from_mode``/``idx``/``val``/``mask``, which misstated the
    contract).
    """
    from .flycoo import pack_mode  # local import to avoid cycle at import time

    return pack_mode(ft, to_mode)
