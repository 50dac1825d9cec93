"""The output check catches a broken timed path.  Each test skips the look
for a chip, plants one fault of ``chipbench.faults`` in the program
underneath a tiny run of a cell, and sees ``correct`` come out false."""
import pytest

from chipbench import faults
from chipbench.run import run_cell
from tiny import DEVICE, tiny_cell

SEED = 77


@pytest.mark.parametrize("kind", faults.TRAIN)
@pytest.mark.parametrize("name", ["mamba2-370m.train.8x2048", "h2o-danube-3-4b.pp6.train.1x4096"])
def test_train_fault_is_caught(name, kind):
    with faults.planted(kind, serving=False):
        line, checks, _ = run_cell(tiny_cell(name), SEED, 1.0, False, DEVICE, clock0=0.0)
    assert line["correct"] is False, checks


@pytest.mark.parametrize("kind", faults.SERVE)
@pytest.mark.parametrize("name", ["mamba2-370m.serve.backlog"])
def test_serve_fault_is_caught(name, kind):
    with faults.planted(kind, serving=True):  # a window long enough to finish requests of several tokens
        line, checks, _ = run_cell(tiny_cell(name), SEED, 8.0, False, DEVICE, clock0=0.0)
    assert line["correct"] is False, checks
