"""The control of `correct`: every cell comes out correct as committed,
and NOT correct when the store is built in the next lower precision
(bfloat16 rows), at the rehearsal's size. The readings at the cells' own
size, on the chip, are in PERF.md section 2."""
import pytest

from _cells import CELLS, TRAIN_CELLS, rehearse


def _names(check_lines) -> list:
    return [ln.split("check ")[1].split(":")[0] for ln in check_lines]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct_as_committed(cell):
    rc, result, checks = rehearse(cell)
    assert rc == 0 and result["correct"] is True, checks
    assert result["rehearsal"] is True and "metrics" not in result
    # a sound run's line names no failed check; every number compared
    # stands beside its limit at the line's end
    assert "not_ok" not in result and list(result)[-1] == "checks"
    assert list(result["checks"]) == _names(checks)


@pytest.mark.parametrize("cell", CELLS)
def test_bf16_store_is_not_correct(cell):
    rc, result, checks = rehearse(cell, "--control", "bf16")
    assert rc == 0 and result["correct"] is False, checks
    # the line's last key says why: the failed checks, each [value,
    # limit] as its `NOT OK` line prints them
    bad = [ln for ln in checks if "NOT OK" in ln]
    assert bad and list(result)[-1] == "not_ok"
    assert list(result["not_ok"]) == _names(bad)
    for ln in bad:
        value, limit = result["not_ok"][_names([ln])[0]]
        assert f"value={value!r} limit={limit!r} NOT OK" in ln, ln


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_bf16_compute_is_not_correct(cell):
    rc, result, checks = rehearse(cell, "--control", "bf16-compute")
    assert rc == 0 and result["correct"] is False, checks
