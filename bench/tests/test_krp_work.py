"""The krp_scatter call count and its roofline reader, on a made-up trace."""
import dataclasses

import pytest

import devtrace
import harness
import krp_work

CALL = ("%krp_scatter.3 = f32[6072,128]{1,0:T(8,128)S(1)} custom-call("
        "s32[34]{0} %a), custom_call_target=\"tpu_custom_call\"")
GATHER = ("%fused_mttkrp_nmode_gather.1 = f32[244272,128]{1,0:T(8,128)} "
          "custom-call(s32[3]{0} %b), custom_call_target=\"tpu_custom_call\"")


def test_call_by_hand():
    # 100 nonzeros, R 4, 8 output rows: 100*5*4 B read, 8*4*4 B written.
    assert krp_work.call_work(8, 100, 4) == (400.0, 100 * 5 * 4 + 8 * 4 * 4)
    assert krp_work.output_rows(CALL) == 6072
    assert krp_work.output_rows(GATHER) is None
    assert krp_work.output_rows("%krp_scatter_x.1 = f32[8,128]") is None


@dataclasses.dataclass(frozen=True)
class _Ctx:
    trace: object
    nnz: int = 4_000_000
    rank: int = 16
    chips: int = 1
    device_kind: str = "TPU v5 lite"


def test_reader_is_least_over_spent():
    least = krp_work.least_seconds(6072, 4_000_000, 16, "TPU v5 lite")
    assert least == pytest.approx((4e6 * 17 * 4 + 6072 * 16 * 4) / 819e9)
    ops = [devtrace.Op(CALL, 0.0, 2 * least * 1e9),
           devtrace.Op(GATHER, 5e9, 1e9)]
    trace = devtrace.Trace((0.0, 1e10), {0: ops}, [])
    read = harness.load_reader("krp_scatter_roofline")
    assert read(_Ctx(trace)) == pytest.approx(50.0)
    assert read(_Ctx(devtrace.Trace((0.0, 1e10), {0: ops[1:]}, []))) is None
