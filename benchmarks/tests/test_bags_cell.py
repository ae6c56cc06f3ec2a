"""The serving cell of the DLRM tables as a CPU rehearsal: NOT correct
with each planted fault (correct as committed and not with a bfloat16
store: test_control.py, every one-chip cell), and the yardstick's own
pieces by hand: the reference's copy of the table hash, its pooling, the
bytes a bag read has to move, the roofline reader. The readings on the
chip are in PERF.md sections 2 and 6."""
import numpy as np
import pytest

from _cells import rehearse

CELL = "dlrm-dcnv2-criteo1tb-serve.bags-open"


def _bad(checks):
    return [ln.split("check ")[1].split(":")[0] for ln in checks
            if "NOT OK" in ln]


@pytest.mark.parametrize("how", ["member_dropped", "offsets_shifted",
                                 "reply_altered", "pooled_in_bf16"])
def test_bags_cell_with_a_planted_fault_is_not_correct(how):
    rc, result, checks = rehearse(
        CELL, how, script="benchmarks/tests/_broken_run_bags.py")
    assert rc == 0 and result["correct"] is False, checks
    # the pooled replies alone see it: the table and the exact checks hold
    assert _bad(checks) == ["pooled_vectors_differ"], checks


def test_bf16_store_fails_the_table_and_the_replies():
    rc, result, checks = rehearse(CELL, "--control", "bf16")
    assert rc == 0 and result["correct"] is False, checks
    bad = _bad(checks)
    assert "table_rows_differ" in bad and "pooled_vectors_differ" in bad


def test_traced_rehearsal_lists_the_bag_metrics():
    """Every per-layer metric this cell adds that a CPU rehearsal can
    read (no device plane: not the two of the device trace)."""
    rc, result, _ = rehearse(CELL, "--trace", "1")
    assert rc == 0 and result["correct"], result
    for name in ("serve_bag_plan_ms", "serve_bag_route_ms",
                 "serve_bag_members_per_batch", "serve_bag_fused_share",
                 "serve_reply_mb_per_request", "serve_batch_keys",
                 "lookup_p95_ms", "loadgen_late_p95_ms", "compile_s"):
        assert name in result["metric_names"], name


def test_reference_rows_are_the_device_fill():
    """`bags_np.seeded_rows`, the reference's own copy of the hash, gives
    the rows `common.table_rows` fills the device table with."""
    import common
    from reference import bags_np
    keys = np.array([0, 1, 25_523_082, 123_456, 7])
    for seed in (0, 2 ** 32 + 12345):
        assert np.array_equal(
            bags_np.seeded_rows(keys, 128, 0.0625, seed),
            common.table_rows(keys, 128, 128, 0.0625, 0.0, seed))


def test_pool_sums_in_member_order_by_hand():
    from reference import bags_np
    big, one = np.float32(2 ** 24), np.float32(1)
    # float32: (2^24 + 1) + 1 = 2^24 (each 1 is lost), 1 + 1 + 2^24 keeps 2
    rows = np.array([[big], [one], [one], [one], [one], [big], [3.0]],
                    dtype=np.float32)
    sums, mag = bags_np.pool(rows, np.array([0, 3, 6, 6, 7]))
    assert sums[:, 0].tolist() == [2.0 ** 24, 2.0 ** 24 + 2, 0.0, 3.0]
    # the sums of |row| are float32 sums in member order too
    assert mag[:, 0].tolist() == [2.0 ** 24, 2.0 ** 24 + 2, 0.0, 3.0]
    # a repeated member is summed twice; the other order is inside the
    # bound, a bag of one member has no room
    room = bags_np.order_bound(mag, np.array([0, 3, 6, 6, 7]))
    assert abs(float(2 ** 24 + 2) - sums[0, 0]) <= room[0, 0]
    assert room[3, 0] == 0.0 and room[2, 0] == 0.0


def test_bag_read_bytes_and_roofline_by_hand():
    import counts_bags
    from sources import roofline_bags
    # a mean request: 308 samples x 214 members of 512 B in 26 bags
    need = counts_bags.bag_read_bytes(308 * 214, 308 * 26, 512)
    assert need == (65_912 + 8_008) * 516 == 38_142_720
    h = lambda total: {"count": 3, "sum": float(total)}  # noqa: E731
    env = {"obs0": {"serve.bag_batch_members": h(0),
                    "serve.bag_batch_bags": h(0)},
           "obs1": {"serve.bag_batch_members": h(2 * 65_912),
                    "serve.bag_batch_bags": h(2 * 8_008)},
           "trace": {"programs": {"jit__gather_pool": {
               "seconds": 0.008, "count": 2}}},
           "device": {"kind": "TPU v5 lite"},
           "ctx": type("C", (), {"cfg": {"step": {"row_bytes": 512}}})()}
    args = {"program": "^jit__gather_pool$"}
    # 2 x 38.14 MB at 819 GB/s is 93.1 us of the programs' 8 ms
    assert roofline_bags.read(env, args) == pytest.approx(
        100 * (2 * need / 819e9) / 0.008)
    assert 1.1 < roofline_bags.read(env, args) < 1.2
    # a program without the histograms (the parent), or no such program
    assert roofline_bags.read({**env, "obs0": {}, "obs1": {}}, args) is None
    assert roofline_bags.read({**env, "trace": {"programs": {}}},
                              args) is None
