"""Loading a cell by name, the chip check, and the result line.

Everything that belongs to one configuration, traffic mix or metric is
found by name: ``configs/<config>.json`` (with its plain reference beside
it), ``traffic/<traffic>.json``, ``limits/<workload>.json`` and
``metrics/<metric>.py``.  The driver that runs a cell is named by the
traffic mix (``drivers/<driver>.py``).
"""
from __future__ import annotations

import importlib.util
import json
import sys
import time
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_module(path: Path, name: str | None = None):
    spec = importlib.util.spec_from_file_location(name or "cb_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    workload: dict  # the BENCHMARK.json entry
    config: dict  # configs/<config>.json
    traffic: dict  # traffic/<traffic>.json
    limits: dict  # limits/<workload>.json: {number: limit}
    metrics: list  # the BENCHMARK.json metric entries this cell reports
    reference: object  # the configuration's plain reference module

    @property
    def sizes(self) -> dict:
        return self.config["config"]


def benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def load_cell(name: str, trace: bool, bench: dict | None = None) -> Cell:
    bench = bench or benchmark()
    try:
        w = next(x for x in bench["workloads"] if x["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json") from None
    config = _json(HERE / "configs" / f"{w['config']}.json")
    kind = "per_layer" if trace else "end_to_end"
    metrics = [m for m in bench[kind] if name in m.get("workloads", [name])]
    return Cell(
        workload=w,
        config=config,
        traffic=_json(HERE / "traffic" / f"{w['traffic']}.json"),
        limits=_json(HERE / "limits" / f"{name}.json"),
        metrics=metrics,
        reference=load_module(HERE / "configs" / config["reference"]),
    )


def arch_config(cell_config: dict, **override):
    """The program's ArchConfig for a configuration file: the registry's
    entry with every size of the file applied, checked field by field."""
    import dataclasses

    from repro.configs import get_config

    base = get_config(cell_config["registry"])
    fields = {f.name for f in dataclasses.fields(base)}
    sizes = {**cell_config["config"], **override}
    cfg = dataclasses.replace(base, **{k: v for k, v in sizes.items() if k in fields})
    if cfg.padded_vocab != sizes["padded_vocab"]:
        raise ValueError(
            f"program pads the vocabulary to {cfg.padded_vocab}, the "
            f"configuration states {sizes['padded_vocab']}"
        )
    return cfg


def device_info(chips: int) -> dict:
    """The accelerator JAX holds; raises NoChip where there is none or too
    few.  There is no CPU fallback."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": chips}


def memory_peak_bytes(chips: int) -> int | None:
    """The device allocator's peak on the fullest chip.  It leaves out the
    temporaries of a compiled program while it runs (see
    ``program_bytes``)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def program_bytes(jitted, *args) -> int:
    """The device bytes that ``jitted`` holds while it runs on ``args``
    (arrays or shape structs): its arguments, outputs and temporaries,
    less the outputs that reuse donated arguments, from the compiled
    program's own memory analysis.  The program comes from the compile
    cache, as in the run."""
    m = jitted.lower(*args).compile().memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
            - m.alias_size_in_bytes)


def shapes_of(tree):
    """Shape structs (with shardings) of a tree of arrays."""
    import jax

    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding), tree)


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads from a given
    moment, through JAX's monitoring events."""

    EVENTS = (
        "/jax/core/compile/backend_compile_duration",
        "/jax/compilation_cache/cache_retrieval_time_sec",
    )

    def __init__(self):
        import jax

        self.counting = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self.counting and event in self.EVENTS:
            self.count += 1


def result_line(cell: Cell, record: dict, checks: dict, device: dict, correct: bool) -> dict:
    """The last line of standard output: every metric of the cell that its
    reader finds, with its unit, and the numbers compared beside their
    limits under ``checks``, which comes last."""
    metrics = {}
    for m in cell.metrics:
        reader = load_module(HERE / "metrics" / f"{m['name']}.py")
        value = reader.read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device)
    dev["memory_peak_bytes"] = record["memory_peak_bytes"]
    line = {
        "correct": correct,
        "attempted": record["attempted"],
        "failed": record["failed"],
        "metrics": metrics,
        "device": dev,
    }
    tr = record.get("trace")
    if tr:
        dev["busy_s"] = tr["busy_s"]
        dev["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    line["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return line


def say(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


T0 = time.perf_counter()  # run.py sets it to the process's start


def mark(phase: str) -> None:
    """Say when a phase of set-up ended, in seconds since ``T0``."""
    say(f"[setup] {phase} at {time.perf_counter() - T0:.3f}s")
