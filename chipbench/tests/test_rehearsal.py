"""A CPU rehearsal of every cell: the whole run at a tiny size, from the
cell's files through its driver, metric readers and output check.  No
number here is a device metric."""
import math

import pytest

from chipbench.harness import benchmark
from chipbench.run import run_cell
from tiny import DEVICE, tiny_cell

CELLS = [w["name"] for w in benchmark()["workloads"]]
SEED = 2**31 + 11  # the driver's seeds exceed 32 signed bits


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(name):
    cell = tiny_cell(name)
    line, checks, record = run_cell(cell, SEED, 2.0, False, DEVICE, clock0=0.0)
    assert line["correct"] is True, checks
    assert line["attempted"] > 0 and line["failed"] == 0
    wanted = {m["name"] for m in cell.metrics}
    assert set(line["metrics"]) == wanted
    assert all(math.isfinite(m["value"]) and m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == set(cell.limits)
    assert record["compiles_in_window"] == 0
