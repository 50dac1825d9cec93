"""The control: the plain reference put in the program's place, holding
in float8 e4m3 what the configurations hold in bfloat16, read by the same
comparison.

On the chip, at the cells' sizes, ``control.py`` reads the program, the
control and the planted faults, and the cells' limits are set between
them (``PERF.md``); its output is kept beside each cell's limits as
``limits/<workload>.readings.jsonl``, beside the readings of the
benchmark's own runs.  The first test holds them to the limits through
the harness's own decision (``run.is_correct``): every program reading
passes, and every control and fault reading that holds each compared
number fails.  The second keeps the procedure at a tiny size, where
fp8 error has few layers to grow through and the chip's limits do not
apply: the program passes the cell's limits and the control reads at
least five times what the program reads on one of the compared numbers."""
import json

import pytest

from chipbench import control, faults
from chipbench.harness import HERE, benchmark
from chipbench.run import is_correct
from tiny import tiny_cell

CELLS = [w["name"] for w in benchmark()["workloads"]]
SEEDS = [5, 2**31 + 6]


def _judge(readings: dict, limits: dict) -> bool:
    return is_correct({"failed": 0}, {k: (readings[k], lim) for k, lim in limits.items() if k in readings})


@pytest.mark.parametrize("name", CELLS)
def test_chip_readings_fall_on_their_sides_of_the_limits(name):
    limits = json.loads((HERE / "limits" / f"{name}.json").read_text())
    lines = [json.loads(x) for x in (HERE / "limits" / f"{name}.readings.jsonl").read_text().splitlines()]
    lines = [x for x in lines if "seed" in x]
    assert len({x["seed"] for x in lines if "program" in x}) >= 12
    assert any("control" in x for x in lines)
    for x in lines:
        assert _judge(x["program"], limits), (x["seed"], x["program"], limits)
        for who in ("control",) + faults.TRAIN + faults.SERVE:
            if who in x and set(limits) <= set(x[who]):  # readings taken before a number was compared are kept, not judged
                assert not _judge(x[who], limits), (x["seed"], who, x[who], limits)


@pytest.mark.parametrize("name", CELLS)
def test_control_separates_at_a_tiny_size(name):
    cell = tiny_cell(name)
    for seed in SEEDS:
        if cell.traffic["driver"] == "train":
            r = control.train_readings(cell, seed, True)
        else:
            r = control.serve_readings(cell, seed, 2.0, True)
        assert _judge(r["program"], cell.limits), r
        assert any(r["control"][k] >= 5 * r["program"][k] for k in cell.limits), r
