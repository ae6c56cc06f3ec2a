"""The MF cell as a CPU rehearsal: correct as committed, and NOT correct
with a bfloat16 store, under each lower-precision control, and with each
planted fault. The cell's traffic carries its limits as `probe_limits`,
so `_cells.TRAIN_CELLS` leaves it to this file: `_broken_run.py
example_dropped` shortens the rows and not the observed values that MF's
step is handed besides (`_broken_run_mf.py` has MF's). The readings on
the chip are in PERF.md section 2."""
import pytest

from _cells import rehearse

CELL = "mf-10mx1m.train-app"


def _bad(checks):
    return [ln.split("check ")[1].split(":")[0] for ln in checks
            if "NOT OK" in ln]


@pytest.mark.parametrize("control,failing", [
    ("bf16-compute", "probe_"), ("ref-bf16", "probe_")])
def test_mf_cell_lower_precision_is_not_correct(control, failing):
    rc, result, checks = rehearse(CELL, "--control", control)
    assert rc == 0 and result["correct"] is False, checks
    assert any(name.startswith(failing) for name in _bad(checks)), checks


@pytest.mark.parametrize("how,script,failing", [
    ("step_unchanged", "benchmarks/tests/_broken_run.py", "probe_update"),
    ("lr_off_1pct", "benchmarks/tests/_broken_run.py", "probe_update"),
    ("example_dropped", "benchmarks/tests/_broken_run_mf.py", "probe_")])
def test_mf_cell_with_a_planted_fault_is_not_correct(how, script, failing):
    rc, result, checks = rehearse(CELL, how, script=script)
    assert rc == 0 and result["correct"] is False, checks
    assert any(name.startswith(failing) for name in _bad(checks)), checks
    # the exact checks still hold: the fault is in the step alone
    assert "table_rows_differ" not in _bad(checks)


def test_mf_pass_loss_walk_broken_is_seen_by_loss_pass_gap_alone():
    """The score program leaving the batch's last cell out: every step is
    sound, only the pass-end loss is wrong."""
    rc, result, checks = rehearse(
        CELL, "score_short", script="benchmarks/tests/_broken_run_mf.py")
    assert rc == 0 and result["correct"] is False, checks
    assert _bad(checks) == ["loss_pass_gap"], checks
