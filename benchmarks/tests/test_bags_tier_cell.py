"""The TIERED serving cell of the DLRM tables as a CPU rehearsal: NOT
correct with each of the tier's planted faults (correct as committed and
not with a bfloat16 store: test_control.py, every one-chip cell), its
traced line lists the tier's metrics, and the yardstick's new pieces by
hand: the cold programs' roofline share (the untiered read's kind and
count over both twins), the two new reading kinds. The readings on the chip are in PERF.md sections
2 and 6."""
import os

import pytest

from _cells import rehearse

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CELL = "dlrm-dcnv2-criteo1tb-serve-tier.bags-open"
TIER = ["tier_cold_member_share", "tier_promotions_per_request",
        "tier_demotions_per_request", "tier_clean_demotion_share",
        "tier_cold_stage_ms", "tier_cold_upload_mb_per_batch",
        "tier_pass_ms", "tier_lock_hold_share",
        "tier_victim_rows_examined_per_row_moved", "serve_lock_wait_ms"]


def _bad(checks):
    return [ln.split("check ")[1].split(":")[0] for ln in checks
            if "NOT OK" in ln]


@pytest.mark.parametrize("how, seen_by", [
    # a pushed-to row demoted unread: the tier's own check alone
    ("dirty_dropped", ["moved_pushed_rows_differ"]),
    # zeros staged for the cold members: the replies alone
    ("cold_stale", ["pooled_vectors_differ"])])
def test_tier_cell_with_a_planted_fault_is_not_correct(how, seen_by):
    rc, result, checks = rehearse(
        CELL, how, script="benchmarks/tests/_broken_run_tier.py")
    assert rc == 0 and result["correct"] is False, checks
    assert _bad(checks) == seen_by, checks


def test_traced_rehearsal_lists_the_tier_metrics():
    """Every per-layer metric this cell adds that a CPU rehearsal can
    read (no device plane: not the two of the device trace), and the
    control cell's beside them."""
    rc, result, _ = rehearse(CELL, "--trace", "1")
    assert rc == 0 and result["correct"], result
    for name in TIER + ["serve_bag_plan_ms", "serve_bag_route_ms",
                        "serve_bag_members_per_batch",
                        "serve_bag_fused_share", "lookup_p95_ms",
                        "loadgen_late_p95_ms", "compile_s"]:
        assert name in result["metric_names"], name


def test_the_tier_metrics_are_absent_elsewhere():
    """Absent, not zero, in a cell without the tier: the control."""
    rc, result, _ = rehearse("dlrm-dcnv2-criteo1tb-serve.bags-open",
                             "--trace", "1")
    assert rc == 0 and result["correct"], result
    assert not [n for n in result["metric_names"]
                if n.startswith("tier_") or "cold" in n
                or n == "serve_lock_wait_ms"]


def test_the_cold_roofline_reads_both_twins_against_the_untiered_count():
    """`gather_pool_cold_roofline` is the kind the untiered cell's share
    has (`roofline_bags`, `counts_bags.bag_read_bytes`: the work, not
    the padded staged operand) with a `program` that matches both
    twins."""
    import json
    import counts_bags
    from sources import roofline_bags
    with open(os.path.join(BENCH, "layer_metrics",
                           "gather_pool_cold_roofline.json")) as f:
        metric = json.load(f)
    assert metric["kind"] == "roofline_bags"
    args = metric["args"]
    # a mean request: 308 samples x 214 members of 512 B in 26 bags,
    # whichever tier holds each member
    need = counts_bags.bag_read_bytes(308 * 214, 308 * 26, 512)
    assert need == (65_912 + 8_008) * 516 == 38_142_720
    h = lambda total: {"count": 3, "sum": float(total)}  # noqa: E731
    env = {"obs0": {"serve.bag_batch_members": h(0),
                    "serve.bag_batch_bags": h(0)},
           "obs1": {"serve.bag_batch_members": h(3 * 65_912),
                    "serve.bag_batch_bags": h(3 * 8_008)},
           "trace": {"programs": {
               "jit__gather_pool_cold": {"seconds": 0.008, "count": 2},
               "jit__gather_pool": {"seconds": 0.002, "count": 1},
               "jit__gather": {"seconds": 1.0, "count": 9}}},
           "device": {"kind": "TPU v5 lite"},
           "ctx": type("C", (), {"cfg": {"step": {"row_bytes": 512}}})()}
    # 3 x 38.14 MB at 819 GB/s is 139.7 us of BOTH twins' 10 ms
    assert roofline_bags.read(env, args) == pytest.approx(
        100 * (3 * need / 819e9) / 0.010)
    assert 1.3 < roofline_bags.read(env, args) < 1.5
    # a program without the histograms (the parent), or no such program
    assert roofline_bags.read({**env, "obs0": {}, "obs1": {}}, args) is None
    assert roofline_bags.read({**env, "trace": {"programs": {}}},
                              args) is None


def test_the_two_new_reading_kinds_by_hand():
    from sources import obs_counter_ratio_sum, obs_histogram_window_share
    env = {"obs0": {"a": 10.0, "b": 100.0, "c": 5.0},
           "obs1": {"a": 40.0, "b": 370.0, "c": 5.0}}
    # 30 of 30 + 270
    assert obs_counter_ratio_sum.read(
        env, {"num": "a", "of": ["a", "b"], "scale": 100.0}) == 10.0
    assert obs_counter_ratio_sum.read(
        env, {"num": "b", "of": ["a"]}) == 9.0
    # nothing grew, or a counter the program lacks: nothing to read
    assert obs_counter_ratio_sum.read(env, {"num": "a", "of": ["c"]}) is None
    assert obs_counter_ratio_sum.read(env, {"num": "a", "of": ["z"]}) is None
    assert obs_counter_ratio_sum.read(env, {"num": "z", "of": ["a"]}) is None
    h = lambda n, s: {"count": n, "sum": s}  # noqa: E731
    env = {"obs0": {"t": h(2, 0.5)}, "obs1": {"t": h(12, 2.5)},
           "res": {"t0": 100.0, "t1": 120.0}}
    # 2 s of brackets in a window of 20
    assert obs_histogram_window_share.read(env, {"name": "t"}) == 10.0
    assert obs_histogram_window_share.read(env, {"name": "u"}) is None
    assert obs_histogram_window_share.read({**env, "res": {}},
                                           {"name": "t"}) is None
