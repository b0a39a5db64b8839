"""The trace reduction, on a trace recorded on one TPU v5e and on
hand-made intervals.  CPU only: reading a trace touches no device."""
from pathlib import Path

import pytest

from chipbench import tracing

TRACE = Path(__file__).parent / "data" / "tpu_v5e_spmm.xplane.pb"
# The recorded window: 3 rounds of three jitted programs with one Pallas
# SpMM kernel each (nnz_stream, row_loop, nnz_stream under a scan), then a
# fourth call whose result the host copied back ('bench.host_argmax').


@pytest.fixture(scope="module")
def trace():
    return tracing.load(TRACE)


def test_window_and_device(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    assert trace.window_s == pytest.approx(0.01253449)
    assert trace.busy_s() == pytest.approx(0.003661876)
    assert 0 < trace.busy_s() < trace.window_s


def test_program_and_kernel_events(trace):
    assert len(trace.modules()) == 10            # 9 calls + the copied one
    kernels = trace.ops('custom_call_target="tpu_custom_call"')
    assert len(kernels) == 10
    assert sum(e - s for s, e, _ in kernels) == pytest.approx(3470867.0)
    assert sum(1 for *_, n in trace.spans if n == "bench.call") == 9
    # each of the 10 programs ran one kernel, inside its own execution
    for s, e, _ in kernels:
        assert sum(1 for ms, me, _ in trace.modules()
                   if ms <= s and e <= me) == 1
    assert trace.ops("no such operation") == []


def test_device_clock_shifted_onto_host(trace):
    # every program starts after the host's window opened and ends before
    # it closed, once the device clock is shifted
    lo, hi = trace.window
    assert all(lo <= s < e <= hi for s, e, _ in trace.modules())
    assert len(trace.modules()) == 10


def test_breakdown(trace):
    b = tracing.breakdown(trace)
    assert b["device_ops"][0][0] == "%_lambda_.1 custom-call tpu_custom_call"
    assert b["device_ops"][0][1] == pytest.approx(0.002318217)
    assert len(b["device_ops"]) == len(b["idle_gaps"]) == 10
    assert b["idle_gaps"][0][0].startswith("bench.host_argmax")
    secs = [g for _, g in b["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)
    # idle gaps and busy time cover the window at most once
    assert sum(secs) <= trace.window_s - trace.busy_s() + 1e-12


def test_union_and_self_times():
    ev = [(0.0, 10.0, "a"), (5.0, 12.0, "b"), (20.0, 25.0, "c")]
    assert tracing.union_ns(ev) == 17.0
    nested = [(0.0, 10.0, "loop"), (1.0, 3.0, "x"), (4.0, 8.0, "y"),
              (12.0, 13.0, "z")]
    assert dict(tracing.self_times(nested)) == {
        "loop": 4.0, "x": 2.0, "y": 4.0, "z": 1.0}


def test_op_label():
    text = ('%closed_call.8 = bf16[8192,128]{1,0} custom-call(s32[1671]{0} '
            '%a), custom_call_target="tpu_custom_call", x={}')
    assert tracing.op_label(text) == \
        "%closed_call.8 custom-call tpu_custom_call"
    assert tracing.op_label(
        "%fusion.1 = s32[512]{0:T(512)} fusion(s32[1671]{0} %a), kind=kCustom"
    ) == "%fusion.1 fusion"
