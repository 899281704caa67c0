"""The trace reduction: on a small trace recorded on a TPU v5e (three
2^12-step chains through ``cumulative_lmme``) and on made-up intervals."""

import re

import benchutil
import pytest

from bench import trace

FIXTURE = str(benchutil.ROOT / "tests" / "bench" / "fixtures" / "chain4096.xplane.pb")
KERNEL = re.compile(r"^%\w*(lmme|scan)\w*kernel")


def test_recorded_trace_busy_and_kernel_time():
    ops = trace.device_ops(FIXTURE)
    assert list(ops) == [0] and len(ops[0]) == 48
    s = trace.summarize(ops, 0.005, KERNEL)
    # per chain: the zero-B matrix_scan kernel (231.3, 231.3, 231.2 us) and
    # four layout copies; nothing overlaps on the ops line
    assert s["kernel_s"] == pytest.approx(693.892e-6, rel=1e-9)
    assert s["busy_s"] == pytest.approx(1079.450e-6, rel=1e-9)
    assert s["collective_s"] == 0.0 and s["chips"] == 1
    assert s["device_ops"][0][0] == "matrix_scan_kernel_call_zero_b"
    assert s["device_ops"][1][0] == "copy"
    # between chains the device waits for the host's next dispatch
    assert s["idle_gaps"][0][0] == "after copy, before slice-start"
    assert s["idle_gaps"][0][1] == pytest.approx(989.506e-6, rel=1e-6)


def test_busy_union_and_exposed_collectives():
    ops = {
        0: [("%fusion.1 = f32[] fusion()", 0, 10), ("%all-gather.2 = f32[] all-gather()", 5, 15),
            ("%lmme_kernel_call.3 = f32[] custom-call()", 20, 30)],
        1: [("%fusion.7 = f32[] fusion(%all-reduce.1)", 0, 2),
            ("%all-reduce.1 = f32[] all-reduce()", 1, 6)],
    }
    s = trace.summarize(ops, 50e-9, KERNEL)
    assert s["busy_s"] == pytest.approx((25 + 6) / 2 * 1e-9)
    assert s["collective_s"] == pytest.approx((10 + 5) / 2 * 1e-9)
    assert s["collective_exposed_s"] == pytest.approx((5 + 4) / 2 * 1e-9)
    assert s["kernel_s"] == pytest.approx(10 / 2 * 1e-9)
    assert s["chips"] == 2


def test_interval_helpers():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert trace.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (25, 26)]) == [
        (0, 2), (4, 8), (22, 25), (26, 30)]
    assert trace.short("%lmme_kernel_call.390 = (f32[1]) custom-call(%pad.1)") == "lmme_kernel_call"
