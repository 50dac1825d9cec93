"""The reduction from trace to metrics, on synthetic events and on a small
trace recorded on a v5e (``fixtures/v5e_trace.json``, written by
``record_trace_fixture.py``)."""
import json
from pathlib import Path

import pytest

from chipbench.tracing import Event, reduce

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "v5e_trace.json"
HOST, DEV = "/host:CPU", "/device:TPU:0"


def _ev(plane, name, start_us, dur_us, line="XLA Ops"):
    return Event(plane, line, name, start_us * 1e3, dur_us * 1e3)


def test_busy_is_the_union_of_op_intervals():
    events = [
        _ev(HOST, "cb.window", 0, 100, "python"),
        _ev(HOST, "cb.step", 0, 60, "python"),
        _ev(HOST, "cb.data", 60, 40, "python"),
        _ev(DEV, "%while = (s32[]) while(...)", 10, 30),  # a loop around its body
        _ev(DEV, "%fusion.1 = bf16[8] fusion(...)", 10, 20),  # nested: counted once
        _ev(DEV, "copy", 70, 10),
        _ev(DEV, "outside", 150, 10),  # after the window: left out
    ]
    r = reduce(events)
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy_s"] == pytest.approx(40e-6)
    assert r["idle_share"] == pytest.approx(0.6)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(20e-6)]
    assert r["device_ops"][1] == ["while", pytest.approx(10e-6)]
    assert sum(t for _, t in r["device_ops"]) == pytest.approx(r["busy_s"])
    assert "outside" not in [n for n, _ in r["device_ops"]]
    # gaps: 0-10 (cb.step), 40-70 (midpoint 55: cb.step), 80-100 (cb.data)
    assert r["idle_gaps"][0] == ["cb.step", pytest.approx(30e-6)]
    assert r["idle_gaps"][1] == ["cb.data", pytest.approx(20e-6)]
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(60e-6)


def test_busy_is_averaged_over_chips():
    events = [
        _ev(HOST, "cb.window", 0, 100, "python"),
        _ev(DEV, "a", 0, 50),
        _ev("/device:TPU:1", "a", 0, 100),
    ]
    assert reduce(events)["busy_s"] == pytest.approx(75e-6)


def test_no_window_or_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        reduce([_ev(DEV, "a", 0, 5)])
    with pytest.raises(ValueError):
        reduce([_ev(HOST, "cb.window", 0, 10, "python")])


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace fixture")
def test_recorded_v5e_trace():
    data = json.loads(FIXTURE.read_text())
    assert data["device_kind"] == "TPU v5 lite"
    r = reduce(Event(*e) for e in data["events"])
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert 0.0 < r["idle_share"] < 1.0
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert {n for n, _ in r["idle_gaps"]} <= {"cb.data", "cb.step", "outside any benchmark span"}
    ops = dict(r["device_ops"])
    assert sum(ops.values()) == pytest.approx(r["busy_s"])
    # three 2048^3 matmuls per step alone, three inside a scan's loop: the
    # loop's own event encloses its body, so its self time is tiny
    assert ops["fusion"] == pytest.approx(ops["convolution_tanh_fusion.2"], rel=0.05)
    assert ops["while"] < 0.01 * ops["fusion"]
