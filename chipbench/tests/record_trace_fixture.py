"""Record the small device trace that ``test_tracing.py`` reduces.

    python3 chipbench/tests/record_trace_fixture.py <out.json>

Run on the chip: jitted matmuls and a scan over matmuls (a loop whose
body's ops nest inside it), with the benchmark's host spans
around host work and steps, traced by ``chipbench.tracing.Tracer``'s
profiler calls; writes the extracted events (device ops and ``cb.*``
spans) as JSON.  The recorded file is kept in ``tests/fixtures``.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from chipbench.tracing import WINDOW_SPAN, extract

    f = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    loop = jax.jit(lambda x: jax.lax.scan(lambda c, _: (jnp.tanh(c @ c), None), x, None, length=3)[0])
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    loop(x).block_until_ready()
    d = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            for _ in range(4):
                with jax.profiler.TraceAnnotation("cb.data"):
                    time.sleep(0.002)
                with jax.profiler.TraceAnnotation("cb.step"):
                    y = x
                    for _ in range(3):
                        y = f(y)
                    loop(y).block_until_ready()
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
        events = extract(ProfileData.from_file(path))
    finally:
        shutil.rmtree(d, ignore_errors=True)
    with open(out, "w") as fh:
        json.dump({"device_kind": jax.devices()[0].device_kind,
                   "events": [list(e) for e in events]}, fh)
    print(f"{len(events)} events -> {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
