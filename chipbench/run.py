"""The chip benchmark.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the accelerator this process holds:
builds it from its files (see ``chipbench/README.md``), makes its weights
on the device from the seed, warms up exactly the shapes its window uses,
measures for ``--seconds``, then checks what the timed path produced
against the cell's plain reference.  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared beside its limit, which also end standard error.

Exits non-zero and prints no result where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

CLOCK0 = time.perf_counter()  # the set-up time starts here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache() -> str:
    """The program's compile cache, inside the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), holding every program however quick
    to compile, so that a cell's second run compiles nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(cell, seed: int, seconds: float, trace: bool, device: dict, clock0: float = CLOCK0):
    """Run a loaded cell; returns (result line, checks)."""
    from chipbench.harness import CompileCounter, load_module, result_line, HERE
    from chipbench.peaks import peaks_for

    counter = CompileCounter()
    driver = load_module(HERE / "drivers" / f"{cell.traffic['driver']}.py")
    record, checks = driver.run(cell, seed, seconds, trace, clock0, counter)
    record["peaks"] = peaks_for(device["kind"])
    record["compiles_in_window"] = counter.count
    return result_line(cell, record, checks, device, is_correct(record, checks)), checks, record


def is_correct(record: dict, checks: dict) -> bool:
    """No request or step failed, and every number compared is within its
    limit."""
    return record["failed"] == 0 and all(v <= lim for v, lim in checks.values())


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench import harness
    from chipbench.harness import NoChip, device_info, load_cell, say

    harness.T0 = CLOCK0

    if not (ROOT / "src" / "repro").is_dir():
        say(f"chipbench: no program under {ROOT / 'src'}; the benchmark measures the repository it sits in")
        return 2
    cell = load_cell(args.workload, bool(args.trace))
    try:
        device = device_info(cell.workload["chips"])
    except NoChip as e:
        say(f"chipbench: {e}; there is no CPU fallback")
        return 2
    say(f"[cell] {args.workload} seed={args.seed} seconds={args.seconds} trace={args.trace} "
        f"device={device} compile_cache={enable_cache()}")
    harness.mark("device found")
    line, checks, record = run_cell(cell, args.seed, args.seconds, bool(args.trace), device)
    say(f"[setup] setup_s={record['setup_s']!r} compiles_in_window={record['compiles_in_window']}")
    for name, (value, limit) in checks.items():
        say(f"check {name} = {value!r} limit {limit!r} {'ok' if value <= limit else 'FAILED'}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
