"""Closed backlog: ``ServeEngine.generate`` over a list of
``requests_per_call`` requests, more than a window serves, so that the
engine never waits for work; another such call follows where one ends.

The window opens at the first decode step, when every row of the batch
holds a request (filling the batch is set-up), and closes ``--seconds``
later: the first token after it ends the call.  The window's time runs
to the start of the last decode step that began inside it, and its
tokens are those handed to the host before then: the tokens of whole
steps.  The requests finished inside the window are the ones checked.
"""
from __future__ import annotations

import gc
import time

from chipbench import gen
from chipbench.drivers import serving
from chipbench.harness import mark, memory_peak_bytes, say
from chipbench.tracing import Tracer

TRACE_S = 8.0  # the traced sub-window: the last seconds of the window


def run(cell, seed: int, seconds: float, trace: bool, clock0: float, counter=None) -> tuple[dict, dict]:
    record, done = serve(cell, seed, seconds, trace, clock0, counter)
    return record, serving.check(cell, seed, done)


def serve(cell, seed: int, seconds: float, trace: bool, clock0: float, counter=None) -> tuple[dict, list]:
    """The window; returns its record and the requests finished in it."""
    import jax

    mix = cell.traffic
    V = cell.sizes["vocab_size"]
    engine = serving.build(cell, seed)
    mark("weights")
    serving.warm_up(engine, mix)
    mark("warm-up")
    window = serving.Window(seconds, on_open=counter and (lambda: setattr(counter, "counting", True)))
    tracer = Tracer()

    def on_decode(now):  # traced runs: trace the window's last seconds
        if not tracer.running and not tracer.result and window.t_end - TRACE_S <= now < window.t_end:
            tracer.start()
        elif tracer.running and now >= window.t_end:
            tracer.stop()

    hooks = serving.StepHooks(engine, window, synced=trace, on_decode=on_decode if trace else None)
    sent, call = [], 0
    gc.collect()  # set-up's garbage is collected in set-up, and not scanned again in the window
    gc.freeze()
    while window.t_end is None or time.perf_counter() < window.t_end:
        reqs = serving.requests(gen.backlog_call(mix, V, seed, call), window)
        sent += reqs
        call += 1
        try:
            with jax.profiler.TraceAnnotation("cb.generate"):
                engine.generate(reqs)
        except serving.WindowClosed:
            break
    gc.unfreeze()
    if counter is not None:
        counter.counting = False
    if tracer.running:
        tracer.stop()

    t0 = window.t0
    say(f"[setup] batch filled at {t0 - clock0:.3f}s")
    done = [r for r in sent if r.done]
    t_last = max(t for t in hooks.starts if t <= window.t_end)  # the last step the window began
    record = {
        "kind": "serve_closed",
        "setup_s": t0 - clock0,
        "window_s": t_last - t0,
        "serve_tokens": sum(1 for r in sent for t in r.generated.times if t0 < t <= t_last),
        "attempted": len(done),
        "failed": serving.budget_misses(done),
        "decode": [d for d in hooks.decode if d[1] + d[2] <= window.t_end] if trace else None,
        "batch": mix["batch"],
        "sizes": cell.sizes,
        "trace": tracer.result or None,
        "memory_peak_bytes": memory_peak_bytes(cell.workload["chips"]),
    }
    prog = serving.footprint(engine, hooks, mix)
    say(f"[window] calls={call} finished={len(done)} tokens={record['serve_tokens']} window_s={record['window_s']!r} "
        f"allocator_peak={record['memory_peak_bytes']} program_bytes={prog}")
    record["memory_peak_bytes"] = max(record["memory_peak_bytes"] or 0, prog)
    del engine, hooks
    return record, done
