"""Dynamic tensor remapping (paper §III-B): round-trip + capacity bounds."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import remap as remap_lib
from repro.core.flycoo import build_flycoo, pack_mode
from repro.core.tensors import random_sparse_tensor


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 100), st.integers(1, 6), st.integers(4, 64))
def test_bucket_by_destination_is_lossless(seed, num_dev, n):
    """No element lost or duplicated when capacity suffices."""
    rng = np.random.default_rng(seed)
    dest = rng.integers(0, num_dev, n).astype(np.int32)
    payload = rng.standard_normal((n, 3)).astype(np.float32)
    cap = int(np.bincount(dest, minlength=num_dev).max())
    buckets, mask, dropped = remap_lib.bucket_by_destination(
        jnp.asarray(dest), jnp.asarray(payload), num_dev, cap)
    assert int(dropped) == 0
    got = np.asarray(buckets)[np.asarray(mask)]
    assert got.shape[0] == n
    assert np.isclose(sorted(got[:, 0].tolist()),
                      sorted(payload[:, 0].tolist())).all()
    # every row landed in its destination bucket
    for d in range(num_dev):
        rows = np.asarray(buckets[d])[np.asarray(mask[d])]
        want = payload[dest == d]
        assert np.isclose(sorted(rows[:, 1].tolist()),
                          sorted(want[:, 1].tolist())).all()


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 100))
def test_bucket_counts_dropped_on_overflow(seed):
    rng = np.random.default_rng(seed)
    n, num_dev = 64, 2
    dest = np.zeros(n, np.int32)                 # all to device 0
    payload = rng.standard_normal((n, 2)).astype(np.float32)
    cap = 10
    _, mask, dropped = remap_lib.bucket_by_destination(
        jnp.asarray(dest), jnp.asarray(payload), num_dev, cap)
    assert int(dropped) == n - cap
    assert int(np.asarray(mask).sum()) == cap


def test_remap_capacity_is_a_true_upper_bound():
    t = random_sparse_tensor((40, 30, 20), 400, seed=3,
                             distribution="powerlaw")
    ft = build_flycoo(t, 4, m_bounds=(4, 16), g_bounds=(8, 64))
    cap = remap_lib.remap_capacity(ft)
    D = 4
    for n in range(t.nmodes):
        src = ft.owner_of(n).astype(np.int64)
        dst = ft.owner_of((n + 1) % t.nmodes).astype(np.int64)
        counts = np.bincount(src * D + dst, minlength=D * D)
        assert counts.max() <= cap


def test_compact_sorted_orders_and_truncates():
    payload = jnp.asarray(np.arange(12, dtype=np.float32).reshape(6, 2))
    mask = jnp.asarray([True, False, True, True, False, True])
    key = jnp.asarray([5, 0, 3, 1, 2, 4], jnp.int32)
    out, omask = remap_lib.compact_sorted(payload, mask, key, 4)
    assert out.shape == (4, 2)
    assert bool(omask.all())
    # sorted by key among valid: keys 1,3,4,5 -> rows 3,2,5,0
    assert np.array_equal(np.asarray(out[:, 0]), [6.0, 4.0, 10.0, 0.0])


def test_remap_local_oracle_is_pack_mode_and_takes_no_source_layout():
    import inspect

    t = random_sparse_tensor((40, 30, 20), 400, seed=3,
                             distribution="powerlaw")
    ft = build_flycoo(t, 4, m_bounds=(4, 16), g_bounds=(8, 64))
    for m in range(t.nmodes):
        got = remap_lib.remap_local(ft, m)
        want = pack_mode(ft, m)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # Contract: the expected post-remap layout depends only on (ft,
    # to_mode) — the oracle must not accept (and ignore) source-layout
    # arguments.
    assert list(inspect.signature(remap_lib.remap_local).parameters) == \
        ["ft", "to_mode"]


def _bucket_oracle(dest, payload, num_devices, bucket_cap):
    """numpy ``bucket_by_destination``: elements in stream order, each at
    its destination's next free slot; padding (``dest >= D``) and overflow
    are left out, overflow counted."""
    buckets = np.zeros((num_devices, bucket_cap) + payload.shape[1:],
                       payload.dtype)
    mask = np.zeros((num_devices, bucket_cap), bool)
    fill = np.zeros(num_devices, np.int64)
    dropped = 0
    for d, row in zip(dest, payload):
        if d >= num_devices:
            continue
        if fill[d] < bucket_cap:
            buckets[d, fill[d]] = row
            mask[d, fill[d]] = True
        else:
            dropped += 1
        fill[d] += 1
    return buckets, mask, dropped


def _assert_buckets_match_oracle(dest, payload, num_dev, cap):
    buckets, mask, dropped = remap_lib.bucket_by_destination(
        jnp.asarray(dest), jnp.asarray(payload), num_dev, cap)
    want_b, want_m, want_dropped = _bucket_oracle(dest, payload, num_dev,
                                                  cap)
    np.testing.assert_array_equal(np.asarray(buckets), want_b)
    np.testing.assert_array_equal(np.asarray(mask), want_m)
    assert int(dropped) == want_dropped


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 6), st.integers(1, 80),
       st.floats(0.2, 1.5))
def test_bucket_by_destination_matches_oracle(seed, num_dev, n, fill):
    """Bucket starts from per-destination counts place every element
    where the stable stream-order oracle does, bit for bit, with padding
    and with buckets that fit or overflow."""
    rng = np.random.default_rng(seed)
    dest = rng.integers(0, num_dev + 1, n).astype(np.int32)
    payload = rng.standard_normal((n, 3)).astype(np.float32)
    cap = max(1, int(fill * n / num_dev))
    _assert_buckets_match_oracle(dest, payload, num_dev, cap)


def _skewed(num_dev, overflow):
    """Skewed toward device 0, with ``dest == D`` padding trailing and
    mixed in; the capacity fits the largest bucket or half of it."""
    rng = np.random.default_rng(num_dev)
    n = 120
    dest = np.minimum(rng.geometric(0.4, n) - 1, num_dev).astype(np.int32)
    dest[-10:] = num_dev
    payload = np.stack([np.arange(n), rng.integers(-9, 9, n)], 1)\
        .astype(np.int32)
    most = int(np.bincount(dest[dest < num_dev]).max())
    cap = max(1, most // 2) if overflow else most
    return dest, payload, num_dev, cap


def _int_payload(dest, seed=7):
    n = dest.shape[0]
    rng = np.random.default_rng(seed)
    return np.stack([np.arange(n), rng.integers(-9, 9, n)], 1)\
        .astype(np.int32)


def _empty_between_full():
    """D = 4: bucket 1 empty between full buckets 0 and 2, padding mixed in."""
    rng = np.random.default_rng(7)
    dest = rng.permutation(np.repeat(np.int32([0, 2, 3, 4]), [12, 12, 5, 7]))
    return dest, _int_payload(dest), 4, 12


def _all_padding():
    """Every element is padding (``dest == D``): every bucket is empty."""
    dest = np.full(40, 3, np.int32)
    return dest, _int_payload(dest), 3, 8


def _window_past_stream_end():
    """D = 1 with ``cap > n``: the only window runs past the end of the
    stream, so only the appended filler makes it a whole slice."""
    dest = np.random.default_rng(7).integers(0, 2, 30).astype(np.int32)
    dest[:3] = 1                          # padding ahead of the bucket's run
    return dest, _int_payload(dest), 1, 45


@pytest.mark.parametrize("case", [
    *(pytest.param(functools.partial(_skewed, num_dev, overflow),
                   id=f"{num_dev}-{'overflow' if overflow else 'fits'}")
      for num_dev in [1, 2, 3, 4, 5, 6] for overflow in [False, True]),
    pytest.param(_empty_between_full, id="empty-between-full"),
    pytest.param(_all_padding, id="all-padding"),
    pytest.param(_window_past_stream_end, id="cap-past-stream-end"),
])
def test_bucket_by_destination_matches_oracle_per_device_count(case):
    _assert_buckets_match_oracle(*case())


@pytest.mark.parametrize("num_dev", [1, 4])
def test_bucket_by_destination_lowers_no_loop_or_scatter(num_dev):
    """Bucket starts come from counts and buckets are gathered windows of
    the sorted stream: the lowered bucketing holds no ``while``, so no
    binary search over the stream, and no ``scatter``."""
    n = 1000
    text = jax.jit(
        lambda d, p: remap_lib.bucket_by_destination(d, p, num_dev, 300)
    ).lower(jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n, 3), jnp.float32)).as_text()
    assert "stablehlo.while" not in text
    assert "stablehlo.scatter" not in text
