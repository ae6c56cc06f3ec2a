"""The DLRM cell as a CPU rehearsal: NOT correct under each lower-precision
control and with each planted fault (correct as committed and not with a
bfloat16 store: test_control.py, every one-chip cell). The cell's traffic
carries its limits as `probe_limits`, so `_cells.TRAIN_CELLS` leaves it to
this file: `_broken_run.py example_dropped` drops the last row of every
role, for this step a member of every bag and a row of the dense network
(`_broken_run_ctr.py` has this cell's). The readings on the chip are in
PERF.md section 2."""
import pytest

from _cells import rehearse

CELL = "dlrm-dcnv2-criteo1tb.train-app"


def _bad(checks):
    return [ln.split("check ")[1].split(":")[0] for ln in checks
            if "NOT OK" in ln]


@pytest.mark.parametrize("control", ["bf16-compute", "ref-bf16"])
def test_lower_precision_is_not_correct(control):
    rc, result, checks = rehearse(CELL, "--control", control)
    assert rc == 0 and result["correct"] is False, checks
    assert any(name.startswith("probe_") for name in _bad(checks)), checks


@pytest.mark.parametrize("how,script,failing", [
    ("step_unchanged", "benchmarks/tests/_broken_run.py", "probe_update"),
    ("lr_off_1pct", "benchmarks/tests/_broken_run.py", "probe_update"),
    ("example_dropped", "benchmarks/tests/_broken_run_ctr.py", "probe_")])
def test_ctr_cell_with_a_planted_fault_is_not_correct(how, script, failing):
    rc, result, checks = rehearse(CELL, how, script=script)
    assert rc == 0 and result["correct"] is False, checks
    bad = _bad(checks)
    assert any(name.startswith(failing) for name in bad), checks
    # both classes see a fault of the step
    assert any(n.endswith(".feat") for n in bad) and \
        any(n.endswith(".dense") for n in bad), checks
    # the exact checks still hold: the fault is in the step alone
    assert "table_rows_differ" not in bad
