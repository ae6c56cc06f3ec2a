"""The four-shard cell as a CPU rehearsal on four virtual devices: correct
as committed, and NOT correct under each lower-precision control, with
the learning rate off by 1%, with the planner standing still, and with
the device's route mirrors gone stale after set-up (which only the probe
from the live table sees). The readings on the chip are in PERF.md
section 2."""
import pytest

from _cells import rehearse

CELL = "kge-wikidata5m-kv4.train-app"


def test_kv_cell_is_correct_as_committed():
    rc, result, checks = rehearse(CELL)
    assert rc == 0 and result["correct"] is True, checks
    assert result["rehearsal"] is True and "metrics" not in result
    assert result["device"]["count"] == 4


@pytest.mark.parametrize("control", ["bf16-compute", "ref-bf16"])
def test_kv_cell_lower_precision_is_not_correct(control):
    rc, result, checks = rehearse(CELL, "--control", control)
    assert rc == 0 and result["correct"] is False, checks
    assert any("probe_" in ln and "NOT OK" in ln for ln in checks)


@pytest.mark.parametrize("how,script,failing", [
    ("lr_off_1pct", "benchmarks/tests/_broken_run.py", "probe_update"),
    ("planner_still", "benchmarks/tests/_broken_run_kv.py",
     "relocations_in_window")])
def test_kv_cell_with_a_planted_fault_is_not_correct(how, script, failing):
    rc, result, checks = rehearse(CELL, how, script=script)
    assert rc == 0 and result["correct"] is False, checks
    assert any(failing in ln and "NOT OK" in ln for ln in checks), checks


def test_only_the_live_probe_sees_routes_gone_stale_after_set_up():
    rc, result, checks = rehearse(
        CELL, "routes_stale", script="benchmarks/tests/_broken_run_kv.py")
    assert rc == 0 and result["correct"] is False, checks
    bad = [ln.split("check ")[1].split(":")[0] for ln in checks
           if "NOT OK" in ln]
    assert bad and all(name.startswith("live_probe_") for name in bad), bad
