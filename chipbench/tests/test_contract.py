"""BENCHMARK.json against the benchmark's contract and its own files:
every cell finds its configuration, traffic, limits and metric readers by
name, and the bounds and windows keep to their limits."""
import json
import re

import pytest

from chipbench.harness import HERE, ROOT, benchmark

B = benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "chipbench/run.py"]
    assert B["paths"] == ["chipbench"]
    assert 1 <= B["run_seconds"] <= 51 and isinstance(B["run_seconds"], int)
    assert len(json.dumps(B)) < 64 * 1024


def test_full_check_fits_with_24_cells():
    runs = 2 + 14 * 24
    assert runs * (B["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_why():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in B[k]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for x in B["configs"] + B["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]


@pytest.mark.parametrize("w", B["workloads"], ids=lambda w: w["name"])
def test_cell_files_exist(w):
    cfg = {c["name"]: c for c in B["configs"]}[w["config"]]
    assert (ROOT / cfg["file"]).is_file()
    assert json.loads((ROOT / cfg["file"]).read_text())["name"] == w["config"]
    assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
    limits = json.loads((HERE / "limits" / f"{w['name']}.json").read_text())
    assert limits and all(v > 0 for v in limits.values())
    assert w["chips"] in (1, 4)
    e2e = [m for m in B["end_to_end"] if w["name"] in m.get("workloads", [w["name"]])]
    per = [m for m in B["per_layer"] if w["name"] in m.get("workloads", [w["name"]])]
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2 and per
    for m in e2e + per:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
    for m in per:
        assert m["moves"] in [x["name"] for x in e2e]


def test_bounds():
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    setup = next(m for m in B["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] == 0.25


def test_per_layer_layers_are_named_alike():
    layers = {m["layer"] for m in B["per_layer"]}
    assert layers <= {"train step program", "serve step program", "device"}
