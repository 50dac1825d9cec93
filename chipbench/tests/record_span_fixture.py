"""Record the device trace that ``test_layers.py`` splits by span, scope
and program, and measure what one host span costs.

    python3 chipbench/tests/record_span_fixture.py <out.json>

Run on the chip.  A jitted call whose ops sit in named scopes runs four
times, each inside a ``repro.fixture.call`` span that ends in
``block_until_ready``, after a ``repro.fixture.host`` span of host work
that leaves the device idle; all inside the benchmark's ``cb.window``.
Writes the profile's device ops and modules, the host spans and the
call's compiled HLO text as JSON, and prints, one per line: the trace's
lines and the stats its op events carry, the offset between the host's
and the device's clocks (``layers.clock_offset``), and the cost of one
span with the profiler off and on.
"""
from __future__ import annotations

import glob
import json
import os
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CALL, HOST, COST = "repro.fixture.call", "repro.fixture.host", "repro.fixture.cost"


def scoped(x, w):
    import jax
    import jax.numpy as jnp

    with jax.named_scope("norm"):
        x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)).astype(x.dtype)
    with jax.named_scope("mlp"):
        x = x + jnp.tanh(x @ w) @ w
    with jax.named_scope("head"):
        return (x @ w).astype(jnp.float32).sum()


def span_cost_ns(n: int) -> float:
    """Mean cost of entering and leaving one span, less an empty loop's."""
    from jax.profiler import TraceAnnotation

    t = time.perf_counter_ns()
    for _ in range(n):
        pass
    empty = time.perf_counter_ns() - t
    t = time.perf_counter_ns()
    for _ in range(n):
        with TraceAnnotation(COST):
            pass
    return (time.perf_counter_ns() - t - empty) / n


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, TraceAnnotation

    from chipbench.layers import clock_offset, raw_events
    from chipbench.tracing import WINDOW_SPAN

    f = jax.jit(scoped)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    w = jnp.full((2048, 2048), 0.01, jnp.bfloat16)
    # source paths relative to the checkout (a cached program keeps the path it was compiled from)
    hlo = re.sub(r'"/[^"]*/(chipbench/[^"]*)"', r'"\1"', f.lower(x, w).compile().as_text())
    f(x, w).block_until_ready()
    cost = {"off": span_cost_ns(200_000)}
    d = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(d)
        with TraceAnnotation(WINDOW_SPAN):
            for _ in range(4):
                with TraceAnnotation(HOST):
                    time.sleep(0.002)
                with TraceAnnotation(CALL):
                    f(x, w).block_until_ready()
        cost["on"] = span_cost_ns(20_000)
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
        profile = ProfileData.from_file(path)
        lines = [[p.name, [ln.name for ln in p.lines]] for p in profile.planes]
        op_stats = sorted({k for p in profile.planes for ln in p.lines if ln.name == "XLA Ops"
                           for e in ln.events for k, _v in e.stats})
        raw = [e for e in raw_events(profile) if e.name != COST]
    finally:
        shutil.rmtree(d, ignore_errors=True)
    print(json.dumps({"lines": lines, "op_stats": op_stats}))
    print(json.dumps({"clock_offset_s": clock_offset(raw, CALL, "jit_scoped")}))
    print(json.dumps({"span_cost_ns": cost}))
    with open(out, "w") as fh:
        json.dump({"device_kind": jax.devices()[0].device_kind, "hlo": [hlo], "span_cost_ns": cost,
                   "events": [list(e) for e in raw]}, fh)
    print(f"{len(raw)} events -> {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
