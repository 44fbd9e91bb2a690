"""The cell internlm2-1.8b-seq4096, whole, on the CPU at a small size: the program
agrees with the plain reference within the cell's limits, and each fault
planted underneath the timed path makes ``correct`` come out false."""
import pytest

from faults import plant
from small import run_small

CELL = "internlm2-1.8b-seq4096"


def test_program_agrees_with_reference():
    out = run_small(CELL)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "server_grad_half"])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    plant(fault, monkeypatch)
    out = run_small(CELL)
    assert not out["correct"], out["checks"]
