"""The split of a trace by the program's spans, scopes and programs
(``chipbench.layers``), on synthetic events and on a small trace recorded
on a v5e (``fixtures/v5e_trace_spans.json``, written by
``record_span_fixture.py``); and the pinned reduction of the first
fixture, which the split leaves as it was."""
import json
from pathlib import Path

import pytest

from chipbench import layers
from chipbench.layers import Op, hlo_paths, reduce_layers, scope_of, shares, split
from chipbench.tracing import Event, reduce

HERE = Path(__file__).resolve().parent / "fixtures"
SPANS_FIXTURE = HERE / "v5e_trace_spans.json"
HOST, DEV = "/host:CPU", "/device:TPU:0"


def _span(name, start_us, dur_us):
    return Event(HOST, "python", name, start_us * 1e3, dur_us * 1e3)


def _op(name, start_us, dur_us, module="jit_step", plane=DEV):
    return Op(plane, name, start_us * 1e3, dur_us * 1e3, module)


# ---------------------------------------------------------------- synthetic


HLO = """HloModule jit_step, is_scheduled=true, entry_computation_layout={()->f32[]}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %tanh.0 = f32[8]{0} tanh(%param_0), metadata={op_name="jit(step)/mlp/tanh" stack_frame_id=3}
}

ENTRY %main.1 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %dot.2 = f32[8]{0} multiply(%x.1, %x.1), metadata={op_name="jit(step)/attn/mul" stack_frame_id=4}
  ROOT %fusion.3 = f32[8]{0} fusion(%dot.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp(head))/add_any"}, backend_config={"x":1}
}
"""


def _serve_trace():
    """A window of 100 us: a refill (prefill and insert_row inside it),
    sampling, a decode dispatch (with a benchmark span), a fetch, then a
    benchmark span alone.  The device runs 10-30 and 50-70."""
    spans = [
        _span("cb.window", 0, 100),
        _span("repro.serve.refill", 0, 42),
        _span("repro.serve.prefill", 4, 16),
        _span("repro.serve.insert_row", 20, 15),
        _span("repro.serve.sample", 42, 18),
        _span("repro.serve.decode", 60, 5),
        _span("cb.decode", 60, 5),
        _span("repro.serve.fetch", 65, 15),
        _span("cb.data", 82, 10),
    ]
    ops = [
        _op("fusion.1", 10, 20, module="jit_prefill"),
        _op("scatter", 20, 5, module="jit_scatter"),  # nested in fusion.1's interval
        _op("while", 50, 20, module="jit_decode_step"),
        _op("fusion.2", 55, 10, module="jit_decode_step"),  # inside the loop
        _op("late", 120, 5, module="jit_decode_step"),  # after the window
    ]
    return spans, ops


def test_idle_splits_by_innermost_program_span():
    idle = reduce_layers(*_serve_trace())["idle_by_span"]
    assert idle == pytest.approx({
        "repro.serve.refill": 11e-6,  # 0-4, 35-42
        "repro.serve.prefill": 6e-6,  # 4-10
        "repro.serve.insert_row": 5e-6,  # 30-35
        "repro.serve.sample": 8e-6,  # 42-50
        "repro.serve.fetch": 10e-6,  # 70-80
        "none": 20e-6,  # 80-100: benchmark spans do not split the idle time
    })


def test_idle_parts_sum_to_the_idle_total():
    spans, ops = _serve_trace()
    r = reduce_layers(spans, ops)
    busy = reduce([*spans, *(Event(o.plane, "XLA Ops", o.name, o.start_ns, o.dur_ns) for o in ops)])["busy_s"]
    assert busy == pytest.approx(40e-6)
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["window_s"] - busy)
    assert sum(r["busy_by_program"].values()) == pytest.approx(busy)
    assert sum(r["busy_by_scope"].values()) == pytest.approx(busy)


def test_busy_by_program_counts_self_time():
    r = reduce_layers(*_serve_trace())
    assert r["busy_by_program"] == pytest.approx(
        {"jit_prefill": 15e-6, "jit_scatter": 5e-6, "jit_decode_step": 20e-6})


def test_gaps_are_named_by_spans_of_either_prefix():
    gaps = reduce_layers(*_serve_trace())["idle_gaps"]
    assert gaps == [["cb.data", pytest.approx(30e-6)],  # 70-100, midpoint 85
                    ["repro.serve.refill", pytest.approx(20e-6)],  # 30-50, midpoint 40
                    ["repro.serve.prefill", pytest.approx(10e-6)]]  # 0-10, midpoint 5


def test_spans_of_equal_start_nest_by_length():
    spans = [_span("cb.window", 0, 10), _span("repro.a", 0, 10), _span("repro.b", 0, 4)]
    r = reduce_layers(spans, [_op("x", 8, 1)])
    assert r["idle_by_span"] == pytest.approx({"repro.b": 4e-6, "repro.a": 5e-6})


def test_busy_is_averaged_over_chips():
    spans = [_span("cb.window", 0, 100)]
    ops = [_op("dot.2", 0, 50), _op("dot.2", 0, 100, plane="/device:TPU:1")]
    r = reduce_layers(spans, ops, [HLO])
    assert r["busy_by_scope"] == pytest.approx({"attn": 75e-6})
    assert r["idle_by_span"] == pytest.approx({"none": 25e-6})


def test_no_window_or_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        reduce_layers([_span("repro.x", 0, 5)], [_op("a", 0, 5)])
    with pytest.raises(ValueError):
        reduce_layers([_span("cb.window", 0, 5)], [_op("a", 10, 5)])


@pytest.mark.parametrize("path, scope", [
    ("jit(train_step)/jvp()/while/body/closed_call/norm/mul", "norm"),
    ("jit(train_step)/transpose(jvp())/while/body/checkpoint/mamba/dot_general", "mamba"),
    ("jit(train_step)/transpose(jvp(head))/mul", "head"),
    ("jit(train_step)/jvp(embed)/jit(_take)/gather", "embed"),
    ("jit(train_step)/optimizer/sqrt", "optimizer"),
    ("jit(train_step)/attn/jit(norm)/sqrt", "attn"),  # a function named like a scope
    ("jit(f)/transpose(jvp(mlp))/mul;jit(f)/transpose(jvp(head))/mul", "mlp"),  # the first name
    ("state.params['blocks']['sub0']['mlp']['w_down']", "other"),
    ("jit(decode_step)/while/body/dynamic_slice", "other"),
    ("", "other"),
])
def test_scope_of(path, scope):
    assert scope_of(path) == scope


def test_hlo_paths_map_instructions_to_op_names():
    paths = hlo_paths(HLO)
    assert paths[("jit_step", "dot.2")] == "jit(step)/attn/mul"
    assert paths[("jit_step", "fusion.3")] == "jit(step)/transpose(jvp(head))/add_any"
    assert paths[("jit_step", "tanh.0")] == "jit(step)/mlp/tanh"
    assert paths[("jit_step", "param_0")] == ""  # an instruction without metadata


def test_scopes_come_from_the_hlo_text():
    spans = [_span("cb.window", 0, 100)]
    ops = [
        _op("dot.2", 0, 10),
        _op("fusion.3", 10, 20),
        _op("tanh.0", 30, 30),  # inside a fusion: named as it is
        _op("copy.1", 60, 5),  # not in the text
        _op("dot.2", 70, 5, module="jit_other"),  # another program's instruction of the same name
        _op("x.1", 80, 5),  # in the text, without a scope
    ]
    r = reduce_layers(spans, ops, [HLO])
    assert r["busy_by_scope"] == pytest.approx(
        {"attn": 10e-6, "head": 20e-6, "mlp": 30e-6, "other": 15e-6})
    assert r["ops_unmatched"] == 2


def test_split_takes_each_op_module_from_the_module_line():
    raw = [
        Event(HOST, "python", "cb.window", 0.0, 100.0),
        Event(DEV, "XLA Modules", "jit_step(7)", 0.0, 50.0),
        Event(DEV, "XLA Ops", "%fusion.1 = f32[8]{0} fusion(%x)", 10.0, 5.0),
        Event(DEV, "XLA Modules", "jit_other(9)", 55.0, 10.0),
        Event(DEV, "XLA Ops", "%copy.2 = f32[8]{0} copy(%x)", 56.0, 5.0),
        Event(DEV, "XLA Ops", "%add.3 = f32[8]{0} add(%x, %x)", 80.0, 5.0),
    ]
    spans, ops = split(raw)
    assert [e.name for e in spans] == ["cb.window"]
    assert [(o.name, o.module) for o in ops] == [("fusion.1", "jit_step"), ("copy.2", "jit_other"), ("add.3", "")]


def test_clock_offset_bounds_the_shift_of_device_times():
    raw = [
        Event(HOST, "python", "repro.call", 100.0, 50.0),
        Event(HOST, "python", "repro.call", 300.0, 50.0),
        Event(DEV, "XLA Modules", "jit_f(3)", 95.0, 20.0),  # reads early: launched at 100 or later
        Event(DEV, "XLA Modules", "jit_f(3)", 310.0, 30.0),
        Event(DEV, "XLA Modules", "jit_g(4)", 0.0, 500.0),  # another program
    ]
    assert layers.clock_offset(raw, "repro.call", "jit_f") == pytest.approx((5e-9, 10e-9))
    # a run whose span the trace cut off pairs with no span
    assert layers.clock_offset(raw[1:], "repro.call", "jit_f") == pytest.approx((-10e-9, 10e-9))
    with pytest.raises(ValueError):
        layers.clock_offset(raw, "repro.call", "jit_h")


def test_shares_of_a_train_split():
    r = {"window_s": 1.0, "idle_by_span": {"none": 0.1},
         "busy_by_scope": {"mamba": 0.6, "head": 0.1, "embed": 0.02, "norm": 0.05, "optimizer": 0.03, "other": 0.1},
         "busy_by_program": {"jit_train_step": 0.9}}
    s = shares(r, "train")
    assert s == pytest.approx({"mixer_share.train": 66.6667, "head_share.train": 13.3333,
                               "optimizer_share.train": 3.3333}, rel=1e-4)
    r["busy_by_scope"]["mlp"] = 0.3
    assert shares(r, "train")["mlp_share.train"] == pytest.approx(25.0)


def test_shares_of_a_backlog_split():
    r = {"window_s": 2.0,
         "idle_by_span": {"repro.serve.sample": 0.16, "repro.serve.fetch": 0.04, "repro.serve.refill": 0.01,
                          "repro.serve.prefill": 0.01, "repro.serve.insert_row": 0.02, "none": 0.01},
         "busy_by_scope": {"mamba": 1.5, "other": 0.25},
         "busy_by_program": {"jit_decode_step": 1.4, "jit_prefill": 0.3, "jit_scatter": 0.05}}
    assert shares(r, "serve_closed") == pytest.approx({
        "idle_in_sample.backlog": 8.0, "idle_in_fetch.backlog": 2.0, "idle_in_refill.backlog": 2.0,
        "refill_device_share.backlog": 20.0})


# ---------------------------------------------------------------- recorded


def test_first_fixture_reduces_as_before():
    """``tracing.reduce`` on ``v5e_trace.json``, pinned to what it gave
    before the program had spans and scopes."""
    data = json.loads((HERE / "v5e_trace.json").read_text())
    r = reduce(Event(*e) for e in data["events"])
    assert r["busy_s"] == PINNED["busy_s"]
    assert r["window_s"] == PINNED["window_s"]
    assert r["idle_share"] == PINNED["idle_share"]
    assert r["device_ops"] == PINNED["device_ops"]


PINNED = {
    "busy_s": 0.00243241,
    "window_s": 0.136338308,
    "idle_share": 0.982159012857927,
    "device_ops": [["fusion", 0.001091801], ["convolution_tanh_fusion.2", 0.00107945],
                   ["copy-done", 0.000189898], ["copy-done.1", 4.6224e-05], ["copy.11", 2.4609e-05],
                   ["while", 2.24e-07], ["copy-start", 1.83e-07], ["copy-start.1", 2.1e-08]],
}


@pytest.fixture(scope="module")
def recorded():
    if not SPANS_FIXTURE.exists():
        pytest.skip("no recorded span fixture")
    data = json.loads(SPANS_FIXTURE.read_text())
    spans, ops = split(Event(*e) for e in data["events"])
    return data, spans, ops


def test_recorded_fixture_is_from_a_v5e(recorded):
    data, spans, ops = recorded
    assert data["device_kind"] == "TPU v5 lite"
    assert ops and [e for e in spans if e.name == "repro.fixture.call"]


def test_recorded_clock_offset(recorded):
    """The shared-clock check on the chip: one shift of the device's times
    puts every run of the call inside its own host span, and on this trace
    that shift is over a millisecond, not under 20 us: the device's times
    read early by it."""
    data, _spans, ops = recorded
    lo, hi = layers.clock_offset([Event(*e) for e in data["events"]], "repro.fixture.call", "jit_scoped")
    assert (lo, hi) == pytest.approx(OFFSET_S, abs=1e-6)
    assert lo > 20e-6
    runs = [Event(*e) for e in data["events"] if e[1] == "XLA Modules"]
    assert all(any(r.start_ns <= o.start_ns and o.start_ns + o.dur_ns <= r.start_ns + r.dur_ns for r in runs)
               for o in ops)  # every op lies inside one run of the call


OFFSET_S = (0.001444083, 0.002115438)  # measured on the chip: the device reads 1.4-2.1 ms early


def test_recorded_call_sorts_into_its_scopes(recorded):
    data, spans, ops = recorded
    r = reduce_layers(spans, ops, data["hlo"])
    scope = r["busy_by_scope"]
    assert {"norm", "mlp", "head"} <= set(scope)
    assert scope["mlp"] > scope["head"] > 0  # two matmuls against one
    assert scope.get("other", 0.0) < 0.1 * sum(scope.values())


def test_recorded_idle_splits_by_host_span(recorded):
    _data, spans, ops = recorded
    r = reduce_layers(spans, ops)
    busy = reduce([*spans, *(Event(o.plane, "XLA Ops", o.name, o.start_ns, o.dur_ns) for o in ops)])["busy_s"]
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["window_s"] - busy, rel=1e-9)
    assert r["idle_by_span"]["repro.fixture.host"] >= 4 * 0.002  # four 2 ms sleeps
    assert {n for n, _d in r["idle_gaps"]} <= {"repro.fixture.host", "repro.fixture.call", "none"}
