"""The four-shard DLRM cell as a CPU rehearsal on four virtual devices:
correct as committed, and NOT correct under each lower-precision control
and with each planted fault: the step returning its pools unchanged, the
batch's last example dropped, the learning rate off by 1%, and a sync
that drops the dense class's deltas (which the feature class's checks do
not see). The readings on the chip are in PERF.md section 2."""
import pytest

from _cells import rehearse
from test_ctr_cell import _bad

CELL = "dlrm-dcnv2-criteo1tb-kv4.train-app"


def test_ctr_kv_cell_is_correct_as_committed():
    rc, result, checks = rehearse(CELL)
    assert rc == 0 and result["correct"] is True, checks
    assert result["rehearsal"] is True and "metrics" not in result
    assert result["device"]["count"] == 4
    # the three probes, both classes, and the exact checks of each class
    names = {ln.split("check ")[1].split(":")[0] for ln in checks}
    for probe in ("probe_", "turn_probe_", "live_probe_"):
        for cls in ("feat", "dense"):
            assert f"{probe}update_diff_share.{cls}" in names, names
    assert {"acked_push_rows_not_read_back",
            "dense_acked_push_rows_not_read_back",
            "dense_rows_differ_between_holders",
            "probe_replica_positions", "compiles_in_window"} <= names


@pytest.mark.parametrize("control", ["bf16-compute", "ref-bf16"])
def test_ctr_kv_cell_lower_precision_is_not_correct(control):
    rc, result, checks = rehearse(CELL, "--control", control)
    assert rc == 0 and result["correct"] is False, checks
    bad = _bad(checks)
    for probe in ("probe_", "turn_probe_", "live_probe_"):
        assert any(name.startswith(probe) for name in bad), checks


@pytest.mark.parametrize("how,script,failing", [
    ("step_unchanged", "benchmarks/tests/_broken_run.py", "probe_update"),
    ("lr_off_1pct", "benchmarks/tests/_broken_run.py", "probe_update"),
    ("example_dropped", "benchmarks/tests/_broken_run_ctr.py", "probe_")])
def test_ctr_kv_cell_with_a_fault_in_the_step_is_not_correct(how, script,
                                                             failing):
    rc, result, checks = rehearse(CELL, how, script=script)
    assert rc == 0 and result["correct"] is False, checks
    bad = _bad(checks)
    assert any(name.startswith(failing) for name in bad), checks
    # both classes see a fault of the step, in every probe
    for probe in ("probe_", "turn_probe_", "live_probe_"):
        assert any(n.startswith(probe) and n.endswith(".feat")
                   for n in bad), checks
        assert any(n.startswith(probe) and n.endswith(".dense")
                   for n in bad), checks
    # the exact checks still hold: the fault is in the step alone
    assert not {"table_rows_differ", "acked_push_rows_not_read_back",
                "dense_acked_push_rows_not_read_back"} & set(bad)


def test_a_sync_that_drops_the_dense_class_s_deltas_is_not_correct():
    rc, result, checks = rehearse(
        CELL, "class_deltas_dropped",
        script="benchmarks/tests/_broken_run_ctr_kv.py")
    assert rc == 0 and result["correct"] is False, checks
    bad = _bad(checks)
    # the dense class's updates through replicas are lost: every probe's
    # dense numbers and the dense class's acknowledged pushes
    for probe in ("probe_", "turn_probe_", "live_probe_"):
        assert f"{probe}update_diff_share.dense" in bad, checks
    assert "dense_acked_push_rows_not_read_back" in bad, checks
    # the feature class syncs as it should
    assert "acked_push_rows_not_read_back" not in bad, checks
    assert "table_rows_differ" not in bad
