"""The six per-layer metrics of PR 35 (the host's own time beside every
step span, the enqueue wait, the steps in flight): each file names a
kind that exists and reads nothing from a program without the
histograms (the parent), and a traced CPU rehearsal of each cell lists
the cell's own. `test_program_spans.py` holds the same for PR 24's; its
checks are called here by name, its list is not edited."""
import json
import os

import pytest

import test_program_spans as spans
from _cells import ROOT, rehearse

NEW = ["step_host_work_ms", "step_enqueue_ms", "steps_in_flight",
       "route_refresh_work_ms", "planner_round_work_ms",
       "pass_end_work_ms"]

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    _BENCH = json.load(f)
PER_LAYER = {m["name"]: m for m in _BENCH["per_layer"]}
# every cell that lists one of the six: the four-chip cell too (its
# rehearsal runs on four virtual CPU devices)
CELLS = [c["name"] for c in _BENCH["workloads"]
         if any(c["name"] in PER_LAYER[n]["workloads"] for n in NEW)]


@pytest.mark.parametrize("name", NEW)
def test_metric_file_names_a_kind_that_exists(name):
    spans.test_metric_file_names_a_kind_that_exists(name)
    # an existing reading kind: this PR adds no code under sources/
    assert spans._spec(name)["kind"] in ("obs_histogram_mean",
                                         "obs_histogram_sum_per")


def test_the_cells_are_the_ones_the_issue_names():
    train = set(PER_LAYER["step_host_ms"]["workloads"])
    assert len(train) == 6
    for name in ("step_host_work_ms", "step_enqueue_ms",
                 "steps_in_flight", "planner_round_work_ms"):
        assert set(PER_LAYER[name]["workloads"]) == train, name
    assert PER_LAYER["route_refresh_work_ms"]["workloads"] == \
        PER_LAYER["route_refresh_ms"]["workloads"]
    assert PER_LAYER["pass_end_work_ms"]["workloads"] == \
        PER_LAYER["pass_end_ms"]["workloads"]
    assert [m["name"] for m in _BENCH["per_layer"][-6:]] == NEW


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_lists_the_new_metrics(cell):
    rc, result, _ = rehearse(cell, "--trace", "1")
    assert rc == 0 and result["correct"], result
    want = [n for n in NEW if cell in PER_LAYER[n]["workloads"]]
    assert want, cell
    missing = [n for n in want if n not in result["metric_names"]]
    assert not missing, (missing, result["metric_names"])
    # and the whole each is a part of
    for whole in ("step_host_ms", "planner_round_ms"):
        assert whole in result["metric_names"], whole
