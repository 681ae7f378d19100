"""The reduction from a trace to busy time, layer times and the breakdown."""
import gzip
import os
import shutil

import pytest

import devtrace
from devtrace import Op, Trace


KERNEL = ('%custom-call.1 = f32[256,128]{1,0:T(8,128)} custom-call('
          'f32[1024,128]{1,0:T(8,128)} %p0), '
          'custom_call_target="tpu_custom_call"')
SORT = ('%sort.2 = (s32[1024]{0:T(1024)}, s32[1024]{0:T(1024)}) '
        'sort(s32[1024]{0:T(1024)} %a, s32[1024]{0:T(1024)} %b), '
        'dimensions={0}, is_stable=true, to_apply=%region_0.1')
A2A = '%all-to-all.3 = f32[4,8]{1,0} all-to-all(f32[4,8]{1,0} %x)'
AG = ('%all-gather-start.4 = (f32[8]{0}, f32[32]{0}) all-gather-start('
      'f32[8]{0} %y), dimensions={0}')
FUSION = ('%fusion.5 = s32[1024]{0:T(1024)} fusion(s32[1024]{0:T(1024)} '
          '%z), kind=kCustom, calls=%fused_computation.5')


def synthetic():
    # Window 0..100 ns on two devices. Device 0: a kernel 10..40 that
    # overlaps a sort 30..50, then an all-to-all 60..70. Device 1: one
    # all-gather 0..20 and a fusion 50..100.
    dev0 = [Op(KERNEL, 10, 30), Op(SORT, 30, 20),
            Op(A2A, 60, 10)]
    dev1 = [Op(AG, 0, 20), Op(FUSION, 50, 50)]
    host = [("bench.window", 0, 100), ("bench.sweep", 0, 100),
            ("float", 75, 25)]
    return Trace((0.0, 100.0), {0: dev0, 1: dev1}, host)


@pytest.mark.parametrize("name,want", [
    (KERNEL, "custom-call"), (SORT, "sort"), (A2A, "all-to-all"),
    (AG, "all-gather-start"), (FUSION, "fusion"), ("sort.7", "sort"),
    ("%while.3 = (s32[]{:T(128)}, /*index=1*/s32[4]{0}) while((s32[]{:T(128)}"
     ", s32[4]{0}) %t), condition=%c, body=%b", "while")])
def test_opcode_from_trace_name(name, want):
    assert devtrace.opcode(Op(name, 0, 1)) == want


def test_union_and_gaps():
    t = synthetic()
    assert devtrace.merged(t.devices[0]) == [(10, 50), (60, 70)]
    assert devtrace.gaps(t, 0) == [(0.0, 10), (50, 60), (70, 100.0)]
    # busy: device 0 50 ns, device 1 70 ns -> mean 60 ns
    assert devtrace.busy_s(t) == pytest.approx(60e-9)


def test_layer_predicates():
    t = synthetic()
    assert devtrace.per_device_seconds(t, devtrace.is_pallas) == \
        pytest.approx({0: 30e-9, 1: 0.0})
    assert devtrace.per_device_seconds(t, devtrace.is_sort) == \
        pytest.approx({0: 20e-9, 1: 0.0})
    assert devtrace.per_device_seconds(t, devtrace.is_collective) == \
        pytest.approx({0: 10e-9, 1: 20e-9})


def test_breakdown_names_gaps_by_host_activity():
    b = devtrace.breakdown(synthetic())
    assert b["device_ops"][0] == [FUSION, pytest.approx(25e-9)]
    longest = b["idle_gaps"][0]
    assert longest[0] == "float (TPU 0)"
    assert longest[1] == pytest.approx(30e-9)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace recorded on a TPU v5e: one sweep of a 61 x 47 x 83 tensor
    with 4,000 nonzeros at R=16 (``conftest.TINY_CONFIG``), traced by the
    harness with ``--trace 1``."""
    src = os.path.join(os.path.dirname(__file__), "data",
                       "tiny_v5e.xplane.pb.gz")
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(src) as f, open(path, "wb") as out:
        shutil.copyfileobj(f, out)
    return devtrace.load(str(path))


def test_recorded_trace_window_and_busy(recorded):
    assert list(recorded.devices) == [0]
    assert len(recorded.devices[0]) == 762
    assert recorded.window_s == pytest.approx(0.017826257)
    assert devtrace.busy_s(recorded) == pytest.approx(0.003147246)
    assert [h[0] for h in recorded.host].count(devtrace.SWEEP_SPAN) == 1


@pytest.mark.parametrize("pred,count,seconds", [
    # one Pallas kernel per mode: fused_mttkrp_nmode_gather.{3,4,5}
    (devtrace.is_pallas, 3, 7.2091e-05),
    # the layout's argsorts and the remap's compaction
    (devtrace.is_sort, 9, 5.4728e-05),
    # jnp.searchsorted's loops and the solve's SVD loops
    (devtrace.is_loop, 9, 0.002085744),
    (devtrace.is_collective, 0, 0.0),
])
def test_recorded_trace_layers(recorded, pred, count, seconds):
    assert sum(map(pred, recorded.devices[0])) == count
    assert devtrace.per_device_seconds(recorded, pred)[0] == \
        pytest.approx(seconds)


def test_recorded_trace_breakdown(recorded):
    b = devtrace.breakdown(recorded)
    assert devtrace.opcode(devtrace.Op(b["device_ops"][0][0], 0, 1)) \
        == "while"
    assert b["device_ops"][0][1] == pytest.approx(0.000346802)
    assert b["idle_gaps"][0][0] == "EnqueueContinuationProgram (TPU 0)"
    assert b["idle_gaps"][0][1] == pytest.approx(0.012926875)
