"""The CTR app (DLRM with a low-rank DCNv2 interaction, `apps/ctr.py`,
`models/dlrm.py`) against the plain reference
(`benchmarks/reference/dlrm_np.py`: numpy float32 with a hand-written
backward, imports nothing of the program), at a few hundred keys, dim 8
and cut dense widths, on one kv shard and on four: the fused step over TWO
length classes (feature rows pooled into bags, the dense network's rows
reshaped into its matrices and multiplied), and `open_run` / `train(run)`
holding the app to a window."""
import json
import os
import signal
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

from reference import dlrm_np  # noqa: E402

from adapm_tpu.apps import ctr  # noqa: E402

ROWS, HOT = [96, 64, 1, 160, 48], [3, 2, 1, 4, 1]
DIM, ND, BOTTOM, TOP, LAYERS, RANK, ROW = 8, 13, [16, 8], [16, 8, 1], 3, 4, 32
B, LR, M = 32, 0.05, sum(HOT)
EPS = 1e-6      # the app's AdaGrad damping; its accumulators start at 0
TENS = dlrm_np.tensors(ND, DIM, len(HOT), BOTTOM, TOP, LAYERS, RANK)
DEPTH = (len(BOTTOM), LAYERS, len(TOP))
FIRST = np.concatenate([[0], np.cumsum(ROWS)])[np.repeat(
    np.arange(len(HOT)), HOT)]
FAST = ["--sys.sync.max_per_sec", "0", "--sys.prefetch", "0"]
# float32 limits, each with its reason. A loss is a mean of B terms
# behind eight layers of float32 matrix products summed in another order
# (XLA's dot against numpy's sgemm): a few ulp of a number near 0.7. A
# gradient's norm is read from the accumulator columns, sums of g*g: a
# few ulp a layer. The update divides by rsqrt against numpy's sqrt, and
# up to B positions that name one row add up in another order; 2e-5 is
# the benchmark's own limit for that share.
LOSS_GAP, NORM_GAP, DIFF_SHARE = 2e-6, 1e-5, 2e-5


@pytest.fixture(autouse=True)
def _time_limit():
    """Every test of this file fails after 55 s rather than hang."""
    def late(signum, frame):
        raise TimeoutError("test exceeded 55 s")
    before = signal.signal(signal.SIGALRM, late)
    signal.setitimer(signal.ITIMER_REAL, 55)
    yield
    signal.setitimer(signal.ITIMER_REAL, 0)
    signal.signal(signal.SIGALRM, before)


def _args(*extra, shards=1, examples=4 * B + 5, epochs=1):
    join = lambda xs: ",".join(map(str, xs))  # noqa: E731
    return ctr.build_parser().parse_args(
        ["--table_rows", join(ROWS), "--multi_hot_sizes", join(HOT),
         "--embedding_dim", str(DIM), "--dense_features", str(ND),
         "--bottom_mlp", join(BOTTOM), "--top_mlp", join(TOP),
         "--dcn_layers", str(LAYERS), "--dcn_rank", str(RANK),
         "--dense_row", str(ROW), "--examples", str(examples),
         "--batch_size", str(B), "--lr", str(LR), "--epochs", str(epochs),
         "--click_rate", "0.3", "--num_shards", str(shards),
         "--num_workers", "1", "--seed", "7",
         "--sys.main_over_alloc", "2.0"] + FAST + list(extra))


def _tables(run):
    """(feature rows [n_feat, 2 DIM], dense rows [n_dense, 2 ROW])."""
    feat = np.asarray(run.srv.read_main(np.arange(run.n_feat)))
    dense = np.asarray(run.srv.read_main(run.dense_keys))
    return (feat.reshape(run.n_feat, 2 * DIM).copy(),
            dense.reshape(run.n_dense, 2 * ROW).copy())


def _batches(rng):
    """Three batches of B examples (members [B, M] table-local ids): one
    whose bags repeat a member (every bag holds ONE id several times);
    a plain draw, in which the one-row table is named B times and rows
    repeat across examples; one that names every row of the other tables
    at most once."""
    repeat = np.concatenate([np.repeat(rng.integers(0, n, (B, 1)), h, 1)
                             for n, h in zip(ROWS, HOT)], axis=1)
    plain = np.concatenate([rng.integers(0, n, (B, h))
                            for n, h in zip(ROWS, HOT)], axis=1)
    distinct = np.concatenate(
        [rng.permutation(n)[:B * h].reshape(B, h) if n >= B * h
         else np.zeros((B, h), np.int64) for n, h in zip(ROWS, HOT)], axis=1)
    out = []
    for members in (repeat, plain, distinct):
        x = rng.normal(size=(B, ND)).astype(np.float32)
        out.append((members, x, (rng.random(B) < 0.3).astype(np.float32)))
    return out


def _gaps(got, want, init, cols):
    """The gap of the norms of the change of columns `cols`, and the norm
    of the two changes' difference, over the reference's norm."""
    p = (got[:, cols] - init[:, cols]).astype(np.float64)
    q = (want[:, cols] - init[:, cols]).astype(np.float64)
    nq = np.linalg.norm(q)
    return abs(np.linalg.norm(p) - nq) / nq, np.linalg.norm(p - q) / nq


def test_layout_is_the_reference_s():
    """The program's dense layout and the reference's agree tensor by
    tensor, and at the source's sizes give the issue's rows."""
    from adapm_tpu.models import dlrm
    lay = dlrm.DenseLayout(dlrm.dense_tensors(ND, DIM, len(HOT), BOTTOM,
                                              TOP, LAYERS, RANK), ROW)
    where, total = dlrm_np.rows_of(TENS, ROW)
    assert [(n, tuple(s), f) for n, s, f in lay.tensors] == \
        [(n, tuple(s), f) for n, s, f in TENS]
    assert (lay.rows, lay.num_rows) == (where, total)
    full = dlrm.DenseLayout(dlrm.dense_tensors(
        13, 128, 26, [512, 256, 128], [1024, 1024, 512, 256, 1], 3, 512),
        1024)
    assert (full.num_rows, full.num_params) == (15_676, 16_044_545)
    rows = [sum(full.rows[n][1] for n, _, _ in full.tensors
                if n.startswith(p)) for p in ("bot", "cross0", "top")]
    assert rows == [170, 3460, 5126]


@pytest.mark.parametrize("shards", [1, 4])
def test_fused_step_follows_the_reference_step_by_step(shards):
    """3 steps through the run's own runner (intent and planner rounds
    live on four shards): each loss, the first gradient's norm (from the
    accumulator columns), the update's norm and the share of its
    difference, per class."""
    run = ctr.open_run(_args(shards=shards))
    try:
        w = run.workers[0]
        runner = run.device_runner(w.shard)
        init = _tables(run)
        ref = [t.copy() for t in init]
        for i, (members, x, y) in enumerate(
                _batches(np.random.default_rng(3))):
            kf = (members + FIRST).T.copy()
            roles = {"feat": kf, "dense": run.dense_keys}
            w.intent(np.concatenate([np.unique(kf), run.dense_keys]),
                     w.current_clock, w.current_clock + 1)
            run.srv.wait_sync()
            before = int(run.srv.obs.find(
                "fused.writeback_rows_total").snap())
            loss = float(runner(roles, (x, y), LR, eps=EPS))
            run.srv.drive_rounds(1)
            w.advance_clock()
            run.srv.quiesce()
            want = dlrm_np.step(ref[0], ref[1], kf, x, y, TENS, ROW, HOT,
                                *DEPTH, LR, eps=EPS)
            assert abs(loss - want) / abs(want) < LOSS_GAP, (i, loss, want)
            assert int(run.srv.obs.find(
                "fused.writeback_rows_total").snap()) - before == \
                M * B + run.n_dense
            got = _tables(run)
            for cls, width in ((0, DIM), (1, ROW)):
                if i == 0:
                    # accumulators grew by the sum of g*g: its root is
                    # the first gradient's norm
                    p, q = (np.sqrt((t[cls][:, width:]
                                     - init[cls][:, width:])
                                    .astype(np.float64).sum())
                            for t in (got, ref))
                    assert abs(p - q) / q < NORM_GAP, (cls, p, q)
                norm_gap, diff = _gaps(got[cls], ref[cls], init[cls],
                                       slice(0, width))
                assert norm_gap < NORM_GAP and diff < DIFF_SHARE, \
                    (i, cls, norm_gap, diff)
    finally:
        run.srv.shutdown()


def test_loss_and_gradients_are_the_reference_s():
    """`models/dlrm.py` under `jax.value_and_grad` against the reference's
    hand-written backward, both roles."""
    import jax
    from adapm_tpu.models import dlrm
    lay = dlrm.DenseLayout(dlrm.dense_tensors(ND, DIM, len(HOT), BOTTOM,
                                              TOP, LAYERS, RANK), ROW)
    loss_fn = dlrm.make_dlrm_loss(lay, HOT, *DEPTH)
    rng = np.random.default_rng(0)
    feat = (rng.normal(size=(M, B, DIM)) * 0.3).astype(np.float32)
    rows = (rng.normal(size=(lay.num_rows, ROW)) * 0.3).astype(np.float32)
    x = rng.normal(size=(B, ND)).astype(np.float32)
    y = (rng.random(B) < 0.3).astype(np.float32)
    loss, (g_feat, g_rows) = jax.value_and_grad(
        lambda f, r: loss_fn({"feat": f, "dense": r}, (x, y)),
        argnums=(0, 1))(feat, rows)
    want, w_feat, w = dlrm_np.loss_and_grads(
        feat, dlrm_np.unpack(rows, TENS, ROW), x, y, HOT, *DEPTH)
    w_rows = dlrm_np.pack(w, TENS, ROW)
    assert abs(float(loss) - want) / want < LOSS_GAP
    for got, ref in ((g_feat, w_feat), (g_rows, w_rows)):
        assert np.abs(np.asarray(got) - ref).max() < \
            5e-6 * np.abs(ref).max()


def test_run_is_open_run_plus_train():
    a = ctr.run(_args(epochs=3))
    run = ctr.open_run(_args(epochs=3))
    b = ctr.train(run)
    run.srv.shutdown()
    assert a == b and np.isfinite(a)


def test_two_train_calls_of_one_pass_equal_one_call_of_two():
    one = ctr.open_run(_args(epochs=2))
    last_one = ctr.train(one)
    two = ctr.open_run(_args(epochs=1))
    ctr.train(two)
    assert two.epoch == 1
    last_two = ctr.train(two)
    try:
        assert (one.epoch, last_one) == (two.epoch, last_two)
        assert all(np.array_equal(a, b)
                   for a, b in zip(_tables(one), _tables(two)))
    finally:
        one.srv.shutdown()
        two.srv.shutdown()


def test_pass_losses_are_the_parent_s_to_the_bit():
    """Three `train(run)` calls of one pass on ONE shard (an intent
    moves nothing there) with --seed 0: the mean losses of PR 43's
    commit, whose app had its own loop, as float.hex(). The one batch
    walk (apps/common.py) trains the same batches in the same order."""
    run = ctr.open_run(_args("--seed", "0"))
    try:
        got = [float(ctr.train(run)).hex() for _ in range(3)]
    finally:
        run.srv.shutdown()
    assert got == ["0x1.5f9fc20000000p-1", "0x1.53d66e0000000p-1",
                   "0x1.432aa00000000p-1"]


def test_max_runtime_stops_at_the_first_pass_end():
    run = ctr.open_run(_args("--max_runtime", "1e-9", epochs=50))
    try:
        ctr.train(run)
        assert run.epoch == 1
        ctr.train(run)
        assert run.epoch == 2
    finally:
        run.srv.shutdown()


def test_open_run_compiles_what_train_runs_and_batches_are_kept():
    """`CtrRun.precompile`: the step stands before the first pass and a
    pass adds no program; a pass's batches (keys, their distinct keys,
    the uploads) are built during the examples' first pass, each
    --lookahead steps before its own, and kept; `set_examples` drops
    them."""
    run = ctr.open_run(_args(epochs=2))
    try:
        before = set(run._programs)
        assert before
        assert run._plans == [[None] * 5]   # 4 B + 5 examples: the tail wraps
        ctr.train(run)
        assert set(run._programs) == before
        plan = list(run._plans[0])
        assert all(b.staged is not None and b.roles["feat"].shape == (M, B)
                   for b in plan)
        ctr.train(run)
        assert all(a is b for a, b in zip(run._plans[0], plan))
        run.set_examples(run.members[:B], run.x[:B], run.y[:B])
        assert run._plans == [[None]]
    finally:
        run.srv.shutdown()


def test_batch_key_counters_and_spans(tmp_path):
    """`app.batch_unique_keys_total` <= `app.batch_keys_total`, both from
    the prepared batches, once a dispatch; under --sys.trace.spans the
    loop's phases are in the span trace."""
    run = ctr.open_run(_args("--sys.trace.spans", "1", "--sys.stats.out",
                             str(tmp_path), epochs=2))
    try:
        ctr.train(run)
        obs = run.srv.obs
        keys = obs.find("app.batch_keys_total").snap()
        uniq = obs.find("app.batch_unique_keys_total").snap()
        # every dispatched batch is counted, 5 a pass, and every one has
        # its intent (one `app.prepare` each: the first --lookahead at
        # the start of the worker's turn)
        assert keys == 2 * 5 * (M * B + run.n_dense)
        assert uniq == 2 * sum(len(b.keys) for b in run._plans[0])
        assert 5 * run.n_dense < uniq <= keys
        assert obs.find("app.prepare_s").snap()["count"] == 10
        assert obs.find("kv.intent_s").snap()["count"] == 10
        assert obs.find("app.pass_end_s").snap()["count"] == 2
        doc = json.load(open(run.srv.write_trace()))
        names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
        for must in ("app.prepare", "app.pass_end", "app.loss_fetch",
                     "kv.quiesce", "kv.intent", "fused.dispatch"):
            assert must in names, must
    finally:
        run.srv.shutdown()


def test_a_pass_steps_from_zero_accumulators_under_the_app_s_eps():
    """The app's own loop: accumulators start at 0 and after a pass of
    one batch hold the sums of g*g (untouched rows still 0); the step's
    size is the reference's under eps 1e-6, not the runner's default."""
    run = ctr.open_run(_args(examples=B))
    try:
        init = _tables(run)
        assert not init[0][:, DIM:].any() and not init[1][:, ROW:].any()
        ctr.train(run)
        got, ref = _tables(run), [t.copy() for t in init]
        kf = run._plans[0][0].roles["feat"]
        dlrm_np.step(ref[0], ref[1], kf, run.x, run.y, TENS, ROW, HOT,
                     *DEPTH, LR, eps=EPS)
        for cls, width in ((0, DIM), (1, ROW)):
            for cols in (slice(0, width), slice(width, 2 * width)):
                norm_gap, diff = _gaps(got[cls], ref[cls], init[cls], cols)
                assert norm_gap < NORM_GAP and diff < DIFF_SHARE, \
                    (cls, cols, norm_gap, diff)
        untouched = np.setdiff1d(np.arange(run.n_feat), kf)
        assert len(untouched) and not got[0][untouched, DIM:].any()
    finally:
        run.srv.shutdown()


def test_step_is_scoped():
    """The bag sums and the dense network carry their scope names in the
    lowered step, beside the step's own."""
    import jax
    from adapm_tpu.models import dlrm
    from adapm_tpu.ops import fused
    lay = dlrm.DenseLayout(dlrm.dense_tensors(ND, DIM, len(HOT), BOTTOM,
                                              TOP, LAYERS, RANK), ROW)
    body = fused._build_device_routed_body(
        dlrm.make_dlrm_loss(lay, HOT, *DEPTH), {"feat": 0, "dense": 1},
        {"feat": DIM, "dense": ROW}, (), None, None, True, False)
    f32, i32 = np.float32, np.int32
    n_feat, n = sum(ROWS), sum(ROWS) + lay.num_rows
    small = lambda L: np.zeros((1, 8, L), f32)  # noqa: E731
    pools = ((np.zeros((1, n_feat, 2 * DIM), f32), small(2 * DIM),
              small(2 * DIM)),
             (np.zeros((1, lay.num_rows, 2 * ROW), f32), small(2 * ROW),
              small(2 * ROW)))
    text = jax.jit(body).lower(
        pools, np.zeros(4, i32),
        (np.zeros(n, i32), np.zeros(n, i32), np.zeros(n, i32), i32(0)),
        {"feat": np.zeros((M, B), i32),
         "dense": np.arange(n_feat, n, dtype=i32)}, None, None,
        jax.random.PRNGKey(0), (np.zeros((B, ND), f32), np.zeros(B, f32)),
        f32(0.1), f32(1e-10)).as_text(debug_info=True)
    for scope in ("adapm_pool", "adapm_dense", "adapm_gather",
                  "adapm_loss_grad", "adapm_scatter_add"):
        assert scope in text, scope
