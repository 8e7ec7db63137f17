"""reduce_trace.py against a trace recorded on one TPU v5e chip
(``record_trace.py``): three annotated units of a jitted 3-iteration scan.
The expected numbers were worked by hand from the events of the file.

The device plane's clock runs ~1.3 ms ahead of the host's here, so the
first program (39.967 ms) falls before the first unit (41.226 ms) and the
third unit holds none: the window holds programs two and three."""

import os

import pytest

import reduce_trace as rt

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "recorded_v5e.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return rt.reduce_file(TRACE, ["ft.grads", "ft.exchange"])


def test_window_and_busy(reduced):
    assert reduced["chips"] == 1
    # first unit starts 41226430 ns, last ends 47852390 + 3202770 ns
    assert reduced["window_s"] == pytest.approx(9.82873e-3, rel=1e-9)
    # program 2: ops from 43402463 to 43445916 ns with one 2 ns hole = 43451;
    # program 3: 46534131 to 46577583 ns with two 1 ns holes = 43450
    assert reduced["busy_s"] == pytest.approx((43451 + 43450) / 1e9, rel=1e-9)
    assert [round(u["busy_s"] * 1e9) for u in reduced["units"]] == [43451, 43450, 0]
    assert reduced["units"][0]["dur_s"] == pytest.approx(3.34939e-3, rel=1e-9)


def test_op_time_is_self_time_under_short_names(reduced):
    ops = dict(reduced["device_ops"])
    # six runs of the scan body's matmul fusion inside the window
    assert ops["%fusion.8 bf16[1024,1024]"] == pytest.approx(69437e-9, rel=1e-9)
    # the while spans its body: 36356 ns each, of which 55 and 57 ns its own
    assert ops["%while s32[]"] == pytest.approx(112e-9, rel=1e-6)
    assert all(len(name) <= 80 for name in ops)
    assert sum(ops.values()) == pytest.approx(reduced["busy_s"], rel=1e-6)


def test_gaps_go_to_the_innermost_host_span(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert set(gaps) == {"ft.exchange", "ft.grads", "step, outside the wrapped calls", "outside any span"}
    assert gaps["ft.exchange"] > gaps["ft.grads"] > gaps["outside any span"]
    # busy + idle = the window
    assert sum(gaps.values()) + reduced["busy_s"] == pytest.approx(reduced["window_s"], rel=1e-9)


def test_self_times_and_union():
    assert rt._self_times([(0, 100, "while"), (10, 30, "a"), (40, 60, "a"), (100, 120, "b")]) == pytest.approx(
        {"while": 60e-9, "a": 40e-9, "b": 20e-9}
    )
    assert rt.union_seconds([(0, 10), (5, 20), (30, 40)], 8, 35) == pytest.approx(17e-9)
    assert rt.short_op_name("%fusion.8 = bf16[1024,1024]{1,0:T(8,128)} fusion(x)") == "%fusion.8 bf16[1024,1024]"
    assert rt.short_op_name("%t = (f32[8,256]{1,0}, bf16[2]) fusion(x)") == "%t f32[8,256]"


def test_a_trace_without_units_or_device_is_an_error():
    with pytest.raises(ValueError, match="bench_unit"):
        rt.reduce_planes([], ["ft.grads"])
