"""With the timed path broken underneath, a run reports correct=false."""
import pytest

from _cells import TRAIN_CELLS, rehearse

BROKEN = [(how, cell) for cell in TRAIN_CELLS
          for how in ("step_unchanged", "example_dropped", "lr_off_1pct")] + [
          ("answer_altered", "kge-wikidata5m.serve-open")]


@pytest.mark.parametrize("how,cell", BROKEN)
def test_broken_path_is_not_correct(how, cell):
    rc, result, checks = rehearse(
        cell, how, script="benchmarks/tests/_broken_run.py")
    assert rc == 0 and result["correct"] is False, checks
