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


def _reads_a_work_histogram(name: str) -> bool:
    """Whether the metric's file reads a span's own-work histogram
    (`*_work_s`): the kind of metric PR 35 added beside each whole."""
    args = spans._spec(name).get("args", {})
    return any(h.endswith("_work_s")
               for h in args.get("sum", []) + [args.get("name", "")])


def test_the_cells_are_the_ones_the_issue_names():
    """By kind, not by position: the training cells are those that
    report `train_examples_per_s`, however many later PRs added, and the
    work metrics are found by the histogram they read, wherever they
    stand in `per_layer`."""
    train = {c["name"] for c in _BENCH["workloads"]} & set(next(
        m for m in _BENCH["end_to_end"]
        if m["name"] == "train_examples_per_s")["workloads"])
    assert len(train) >= 6 and set(NEW) <= set(PER_LAYER)
    for name in ("step_host_ms", "step_host_work_ms", "step_enqueue_ms",
                 "steps_in_flight", "planner_round_work_ms"):
        assert set(PER_LAYER[name]["workloads"]) == train, name
    # a work metric is read where the whole it is a part of is read
    assert sorted(n for n in PER_LAYER if _reads_a_work_histogram(n)) == \
        sorted(n for n in NEW if n.endswith("_work_ms"))
    for name in PER_LAYER:
        if _reads_a_work_histogram(name):
            whole = name.replace("_work_ms", "_ms")
            assert PER_LAYER[name]["workloads"] == \
                PER_LAYER[whole]["workloads"], name


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
