"""The per-layer metrics that read the program's own spans and
histograms (PR 24): every new metric file names a kind that exists, the
two new kinds give hand-known answers on a hand-made `env`, and a traced
CPU rehearsal of each cell lists the cell's new metrics."""
import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

from _cells import CELLS, ROOT, rehearse  # noqa: E402

NEW = ["serve_admit_ms", "serve_queue_ms", "serve_batch_wait_ms",
       "serve_dispatch_ms", "serve_copy_out_ms",
       "serve_deliver_ms", "serve_wake_ms", "serve_lookup_ms",
       "serve_queue_depth", "step_dispatch_ms", "intent_ms",
       "step_host_ms", "train_prepare_ms", "pass_end_ms",
       "idle_attributed_share"]

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    PER_LAYER = {m["name"]: m for m in json.load(f)["per_layer"]}


def _spec(name):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", NEW)
def test_metric_file_names_a_kind_that_exists(name):
    spec, entry = _spec(name), PER_LAYER[name]
    assert (spec["name"], spec["layer"], spec["moves"]) == \
        (name, entry["layer"], entry["moves"])
    reader = importlib.import_module("sources." + spec["kind"])
    assert callable(reader.read)
    # a program without the span or counter (the parent commit): the
    # reader finds nothing, returns nothing, and does not raise
    empty = {"obs0": {}, "obs1": {}, "trace": {"idle_gaps": []},
             "res": {}, "device": {}, "ctx": None}
    assert reader.read(empty, spec.get("args", {})) is None


def _h(count, total):
    return {"count": count, "sum": total}


def test_obs_histogram_sum_per_by_hand():
    from sources import obs_histogram_sum_per as kind
    args = {"sum": ["a_s", "b_s"], "per": "b_s", "scale": 1000.0}
    env = {"obs0": {"a_s": _h(10, 1.0), "b_s": _h(5, 2.0)},
           "obs1": {"a_s": _h(30, 1.5), "b_s": _h(15, 2.25)}}
    # (0.5 + 0.25) s over 10 observations of b_s = 75 ms each
    assert kind.read(env, args) == pytest.approx(75.0)
    # a window in which the counts do not move: nothing to read
    still = {"obs0": env["obs0"], "obs1": env["obs0"]}
    assert kind.read(still, args) is None
    # one histogram of the sum absent (an older program): nothing
    part = {"obs0": {"b_s": _h(5, 2.0)}, "obs1": {"b_s": _h(15, 2.25)}}
    assert kind.read(part, args) is None


def test_trace_gap_share_by_hand():
    from sources import trace_gap_share as kind
    args = {"prefix": "adapm."}
    gaps = [["adapm.app.pass_end", 0.6], ["np.asarray_jax.Array_", 0.25],
            ["adapm.fused.dispatch", 0.1], ["(short gaps)", 0.05]]
    assert kind.read({"trace": {"idle_gaps": gaps}}, args) == \
        pytest.approx(70.0)
    # no program span among the gaps (the parent), no gaps, no trace
    assert kind.read({"trace": {"idle_gaps": gaps[1:2]}}, args) is None
    assert kind.read({"trace": {"idle_gaps": []}}, args) is None
    assert kind.read({"trace": None}, args) is None


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_lists_the_new_metrics(cell):
    """Every new metric of the cell is in the line of a `--trace 1`
    rehearsal, except what only a device trace holds: a CPU rehearsal
    has no device plane, so no idle gaps to attribute."""
    rc, result, _ = rehearse(cell, "--trace", "1")
    assert rc == 0 and result["correct"], result
    want = [n for n in NEW if cell in PER_LAYER[n]["workloads"]
            and PER_LAYER[n]["source"] != "device_trace"]
    assert want, cell
    missing = [n for n in want if n not in result["metric_names"]]
    assert not missing, (missing, result["metric_names"])
