"""The trace reduction on a hand-made trace with known answers, and on a
small trace recorded on a v5e chip (``record_trace.py``)."""
from collections import namedtuple
from pathlib import Path

import pytest

from bench import trace_reduce

Plane = namedtuple("Plane", "name lines")
Line = namedtuple("Line", "name events")
Event = namedtuple("Event", "name start_ns duration_ns")
MS = 1e6   # ns


def _trace():
    host = Plane("/host:CPU", [Line("python", [
        Event("bench.window", 0, 100 * MS),
        Event("bench.dispatch", 0, 10 * MS),
        Event("bench.block", 10 * MS, 80 * MS),
        Event("bench.pick", 90 * MS, 5 * MS),
        Event("other", 0, 100 * MS)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Ops", [
            Event("%while.1 = (f32[8]{0}) while(%t), body=%b", 5 * MS,
                  45 * MS),
            Event("%fusion.a = f32[8]{0} fusion(%x), kind=kLoop", 5 * MS,
                  25 * MS),
            Event("%fusion.b = f32[8]{0} fusion(%fusion.a)", 30 * MS,
                  20 * MS),
            Event("%signature_corr_pallas.3 = f32[8,12]{1,0} custom-call("
                  "%w, %s)", 60 * MS, 10 * MS),
            Event("%fusion.c = s32[4]{0:T(128)} fusion(%signature_corr_"
                  "pallas.3)", 95 * MS, 25 * MS)]),
        Line("XLA Modules", [Event("jit_run", 0, 120 * MS)])])
    return namedtuple("PD", "planes")([host, dev])


def test_known_answers():
    r = trace_reduce.reduce(_trace())
    assert r["window_s"] == pytest.approx(0.100)
    # union [5, 50] + [60, 70] + [95, 100 (clipped)] ms
    assert r["busy_s"] == pytest.approx(0.060)
    # leaf ops only, by their own names: the while spans its body's ops,
    # and a consumer that names the kernel as its operand is not the kernel
    assert r["op_s"] == pytest.approx({
        "%fusion.a": 0.025, "%fusion.b": 0.020, "%fusion.c": 0.005,
        "%signature_corr_pallas.3": 0.010})
    assert r["device_ops"][0] == ["%fusion.a f32[8] fusion",
                                  pytest.approx(0.025)]
    assert ["%fusion.c s32[4] fusion", pytest.approx(0.005)] \
        in r["device_ops"]
    gaps = dict(r["idle_gaps"])
    # [0, 5] under dispatch; [50, 60] and [70, 95] under block
    assert gaps == pytest.approx({"bench.dispatch": 0.005,
                                  "bench.block": 0.035})


def test_no_window_span_is_an_error():
    pd = _trace()
    pd.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce(pd)


RECORDED = Path(__file__).parent / "data" / "fleet_trace.xplane.pb.gz"


def test_recorded_chip_trace():
    r = trace_reduce.reduce(trace_reduce.load(str(RECORDED)), n_devices=1)
    assert 0.0 < r["busy_s"] <= r["window_s"]
    assert 0 < len(r["device_ops"]) <= 10
    assert sum(t for _, t in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    # the kernels' own custom calls, each a leaf op of the scan body
    kernels = [n for n in r["op_s"] if "pallas" in n]
    assert any(n.startswith("%signature_corr_pallas") for n in kernels)
    assert sum("fake_quant_pallas" in n for n in kernels) == 7
    assert not any(n.startswith("%while") for n, _ in r["device_ops"])
