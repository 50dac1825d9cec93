"""The ``repro.serve.*`` host spans that ``ServeEngine.generate`` opens:
each phase appears in a profiler trace as often as it runs, and tracing
changes no output."""
import collections
import glob
import os

import jax
import pytest
from jax.profiler import ProfileData

from repro.configs import reduced_config
from repro.models import Model
from repro.serve.engine import Request, ServeEngine

PROMPTS = [[5, 6, 7], [9, 10, 11, 2, 5, 3, 8], [7], [1, 2, 3, 4]]
BUDGETS = [4, 2, 5, 3]
SPANS = ("refill", "prefill", "insert_row", "sample", "decode", "fetch")


def _requests():
    return [Request(i, list(p), max_new_tokens=n) for i, (p, n) in enumerate(zip(PROMPTS, BUDGETS))]


def _engine():
    cfg = reduced_config("mamba2-370m")
    params = Model(cfg).init(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_len=64, batch_size=2)
    inner = eng._decode
    eng.decode_calls = 0

    def counted(*args):  # the engine looks ``_decode`` up at each step
        eng.decode_calls += 1
        return inner(*args)

    eng._decode = counted
    return eng


def _span_counts(trace_dir) -> collections.Counter:
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)[0]
    counts = collections.Counter()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro.serve."):
                    counts[e.name[len("repro.serve."):]] += 1
    return counts


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(outputs, span counts, decode steps) of one traced ``generate``."""
    d = str(tmp_path_factory.mktemp("trace"))
    eng = _engine()
    eng.generate(_requests())  # compiles outside the trace
    eng.decode_calls = 0
    jax.profiler.start_trace(d)
    try:
        out = eng.generate(_requests())
    finally:
        jax.profiler.stop_trace()
    return out, _span_counts(d), eng.decode_calls


def test_every_span_appears(traced):
    _out, counts, _steps = traced
    assert set(counts) == set(SPANS)


def test_refill_spans_once_per_request(traced):
    _out, counts, _steps = traced
    for name in ("refill", "prefill", "insert_row"):
        assert counts[name] == len(PROMPTS), name


def test_step_spans_once_per_decode_step(traced):
    _out, counts, steps = traced
    assert steps > 0
    assert counts["decode"] == steps
    assert counts["fetch"] == steps
    # the last pass samples the last tokens, retires every row and ends
    # the call before another decode step
    assert counts["sample"] == steps + 1


def test_tracing_changes_no_output(traced):
    out, _counts, _steps = traced
    plain = _engine().generate(_requests())
    assert out == plain
    assert [len(out[i]) for i in range(len(PROMPTS))] == BUDGETS
