"""The control of `correct`: every cell comes out correct as committed,
and NOT correct when the store is built in the next lower precision
(bfloat16 rows), at the rehearsal's size. The readings at the cells' own
size, on the chip, are in PERF.md section 2."""
import pytest

from _cells import CELLS, TRAIN_CELLS, rehearse


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct_as_committed(cell):
    rc, result, checks = rehearse(cell)
    assert rc == 0 and result["correct"] is True, checks
    assert result["rehearsal"] is True and "metrics" not in result


@pytest.mark.parametrize("cell", CELLS)
def test_bf16_store_is_not_correct(cell):
    rc, result, checks = rehearse(cell, "--control", "bf16")
    assert rc == 0 and result["correct"] is False, checks
    assert any("NOT OK" in ln for ln in checks)


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_bf16_compute_is_not_correct(cell):
    rc, result, checks = rehearse(cell, "--control", "bf16-compute")
    assert rc == 0 and result["correct"] is False, checks
