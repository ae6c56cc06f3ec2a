"""The CTR app on four kv shards with four workers, the deployment
`dlrm-dcnv2-criteo1tb-kv4` at rehearsal size on four virtual devices,
held to the plain reference (`benchmarks/reference/dlrm_np.py`) through
the PLACEMENT: every worker names every dense key in every batch, so the
planner replicates the dense class on every shard that does not own it
and a step reads and writes most dense rows through cache + delta.

CTR samples no role: every row of a step is named by the host, so a step
of ANY worker has a sequential reference as long as the replicas it reads
equal main (after `quiesce()`). Where several workers' steps interleave
with each other's sync rounds they have none, and the store's additive
contract is checked instead: the table ends at the seeded rows plus the
sum of what each step, given the rows it read, had to add."""
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
EPS = 1e-6
TENS = dlrm_np.tensors(ND, DIM, len(HOT), BOTTOM, TOP, LAYERS, RANK)
DEPTH = (len(BOTTOM), LAYERS, len(TOP))
FIRST = np.concatenate([[0], np.cumsum(ROWS)])[np.repeat(
    np.arange(len(HOT)), HOT)]
# float32 limits, tests/test_ctr_reference.py's and for its reasons: a
# loss is a mean of B terms behind eight layers of float32 products
# summed in another order than numpy's; a gradient's norm is read from
# the accumulator columns; the update divides by rsqrt against numpy's
# sqrt and positions that name one row add up in another order. On four
# shards a replica position's update goes through a delta pool and one
# more float32 addition at the sync; the limits hold as they are.
# bfloat16 products move each of them by 1e-3 and more.
LOSS_GAP, NORM_GAP, DIFF_SHARE = 2e-6, 1e-5, 2e-5


@pytest.fixture(autouse=True)
def _time_limit():
    """Every test of this file fails after 90 s rather than hang."""
    def late(signum, frame):
        raise TimeoutError("test exceeded 90 s")
    before = signal.signal(signal.SIGALRM, late)
    signal.setitimer(signal.ITIMER_REAL, 90)
    yield
    signal.setitimer(signal.ITIMER_REAL, 0)
    signal.signal(signal.SIGALRM, before)


def _args(*extra, shards=4, workers=4, examples=4 * 2 * B, epochs=1):
    join = lambda xs: ",".join(map(str, xs))  # noqa: E731
    return ctr.build_parser().parse_args(
        ["--table_rows", join(ROWS), "--multi_hot_sizes", join(HOT),
         "--embedding_dim", str(DIM), "--dense_features", str(ND),
         "--bottom_mlp", join(BOTTOM), "--top_mlp", join(TOP),
         "--dcn_layers", str(LAYERS), "--dcn_rank", str(RANK),
         "--dense_row", str(ROW), "--examples", str(examples),
         "--batch_size", str(B), "--lr", str(LR), "--epochs", str(epochs),
         "--click_rate", "0.3", "--num_shards", str(shards),
         "--num_workers", str(workers), "--seed", "7", "--lookahead", "2",
         "--sync_rounds_per_step", "1", "--sys.techniques", "all",
         "--sys.cache_slots_per_shard", "4096",
         "--sys.main_over_alloc", "2.0", "--sys.sync.max_per_sec", "0",
         "--sys.prefetch", "0"] + list(extra))


def _tables(run):
    """(feature rows [n_feat, 2 DIM], dense rows [n_dense, 2 ROW])."""
    feat = np.asarray(run.srv.read_main(np.arange(run.n_feat)))
    dense = np.asarray(run.srv.read_main(run.dense_keys))
    return (feat.reshape(run.n_feat, 2 * DIM).copy(),
            dense.reshape(run.n_dense, 2 * ROW).copy())


def _draw(rng, n):
    """n examples: Zipf-ish members, so that a head is shared by every
    batch and a tail is not."""
    members = np.concatenate(
        [np.minimum(rng.zipf(1.3, (n, h)) - 1, rows - 1)
         for rows, h in zip(ROWS, HOT)], axis=1).astype(np.int64)
    x = rng.normal(size=(n, ND)).astype(np.float32)
    return members, x, (rng.random(n) < 0.3).astype(np.float32)


def _gaps(got, want, init, cols):
    p = (got[:, cols] - init[:, cols]).astype(np.float64)
    q = (want[:, cols] - init[:, cols]).astype(np.float64)
    nq = np.linalg.norm(q)
    return abs(np.linalg.norm(p) - nq) / nq, np.linalg.norm(p - q) / nq


def _all_name_the_dense_keys(run) -> None:
    """What a pass of all workers leaves behind for a worker that then
    steps alone: every worker's intent on the dense keys, which each of
    its batches names, alive at its clock, worker 0's last. The first
    to ask takes the main copies its shard's pool has room for; every
    other key of the class stays where it is, replicated to all who
    asked (a first step would otherwise run before the round that acts
    on its intent)."""
    for w in run.workers[1:] + run.workers[:1]:
        w.intent(run.dense_keys, w.current_clock, w.current_clock + 1)
        run.srv.wait_sync()


def _train_alone(run, wi: int, batch) -> float:
    """One pass of `batch` through the app's own `train(run)`, handed a
    view of worker `wi` alone; ends in `quiesce()`."""
    workers = run.workers
    run.workers, run.num_workers = [workers[wi]], 1
    try:
        run.set_examples(*batch)
        return ctr.train(run)
    finally:
        run.workers, run.num_workers = workers, len(workers)


def _counter(run, name: str) -> int:
    for w in run.workers:
        run.device_runner(w.shard).locality_counts()    # the drain
    return int(run.srv.obs.find(name).snap())


def _check_step(i, loss, want, got, ref, init, first: bool):
    assert abs(loss - want) / abs(want) < LOSS_GAP, (i, loss, want)
    for cls, width in ((0, DIM), (1, ROW)):
        if first:
            p, q = (np.sqrt((t[cls][:, width:] - init[cls][:, width:])
                            .astype(np.float64).sum()) for t in (got, ref))
            assert abs(p - q) / q < NORM_GAP, (cls, p, q)
        norm_gap, diff = _gaps(got[cls], ref[cls], init[cls],
                               slice(0, width))
        assert norm_gap < NORM_GAP and diff < DIFF_SHARE, \
            (i, cls, norm_gap, diff)


def test_worker_0_alone_follows_the_reference_through_dense_replicas():
    """Two steps of worker 0 alone from the seeded table, every
    worker's intent on the dense keys in place: every step's loss, the
    first gradient's norm and the update of BOTH classes are the
    reference's, and more than half of the dense positions were read
    from (and written to) replicas."""
    run = ctr.open_run(_args())
    try:
        _all_name_the_dense_keys(run)
        init = _tables(run)
        ref = [t.copy() for t in init]
        rng = np.random.default_rng(3)
        for i in range(2):
            members, x, y = batch = _draw(rng, B)
            loss = _train_alone(run, 0, batch)
            kf = (members + FIRST).T.copy()
            want = dlrm_np.step(ref[0], ref[1], kf, x, y, TENS, ROW, HOT,
                                *DEPTH, LR, eps=EPS)
            _check_step(i, loss, want, _tables(run), ref, init, i == 0)
        dense = _counter(run, f"fused.replica_positions.len{2 * ROW}")
        assert dense > run.n_dense          # of 2 * n_dense positions
        assert _counter(run, "fused.replica_positions") >= dense
        assert run.srv.obs.find(
            f"sync.replicas_live.len{2 * ROW}").snap() >= run.n_dense
    finally:
        run.srv.shutdown()


def test_turns_of_two_workers_follow_the_reference():
    """One step of worker 0, `quiesce()`, one step of worker 1,
    `quiesce()`, against the reference stepping the same two batches in
    order: worker 1's dense rows are worker 0's updates, written to a
    delta pool, synced into main and read back through worker 1's own
    replicas; the feature rows both batches name went the same way or
    were relocated."""
    run = ctr.open_run(_args())
    try:
        _all_name_the_dense_keys(run)
        init = _tables(run)
        ref = [t.copy() for t in init]
        rng = np.random.default_rng(5)
        owner0 = run.srv.ab.owner.copy()
        for i, wi in enumerate((0, 1)):
            members, x, y = batch = _draw(rng, B)
            loss = _train_alone(run, wi, batch)
            kf = (members + FIRST).T.copy()
            want = dlrm_np.step(ref[0], ref[1], kf, x, y, TENS, ROW, HOT,
                                *DEPTH, LR, eps=EPS)
            _check_step(i, loss, want, _tables(run), ref, init, i == 0)
        ab, st = run.srv.ab, run.srv.sync.stats
        feat = np.arange(run.n_feat)
        assert (ab.owner[feat] != owner0[feat]).any()       # relocated
        assert (ab.cache_slot[:, feat] >= 0).any()          # replicated
        assert st.relocations > 0 and st.replicas_created > run.n_dense
        # both workers' dense positions were replica positions
        assert _counter(run, f"fused.replica_positions.len{2 * ROW}") \
            > run.n_dense
    finally:
        run.srv.shutdown()


class _ReadsRecorded:
    """A runner whose every dispatched step is preceded by a Pull of the
    rows it is about to read, as its worker sees them (a replica's
    cache + delta, or the main copy): what the step's update is a
    function of."""

    def __init__(self, runner, worker, steps: list):
        self.runner, self.worker, self.steps = runner, worker, steps

    def __getattr__(self, name):
        return getattr(self.runner, name)

    def __call__(self, roles, aux, lr, **kw):
        kf, kd = roles["feat"], roles["dense"]
        uf = np.unique(kf)
        self.steps.append({
            "kf": kf, "uf": uf, "x": np.asarray(aux[0]),
            "y": np.asarray(aux[1]),
            "feat": np.asarray(self.worker.pull_sync(uf)).reshape(
                len(uf), 2 * DIM),
            "dense": np.asarray(self.worker.pull_sync(kd)).reshape(
                len(kd), 2 * ROW)})
        return self.runner(roles, aux, lr, **kw)


def test_a_pass_of_four_workers_loses_and_doubles_no_update():
    """A whole pass of four workers, two steps a turn, then `quiesce()`:
    every worker reads every dense key equal to main bitwise, the delta
    pools are zero, and main less the seeded table is the sum over the
    steps of the update rows the reference forms from what each step
    read, per key, to float32 summation order."""
    run = ctr.open_run(_args())
    try:
        srv = run.srv
        init = _tables(run)
        steps = []
        for w in run.workers:
            run._dev_runners[w.shard] = _ReadsRecorded(
                run.device_runner(w.shard), w, steps)
        run.set_examples(*_draw(np.random.default_rng(9), 4 * 2 * B))
        ctr.train(run)
        assert len(steps) == 8
        got = _tables(run)
        main = np.asarray(srv.read_main(run.dense_keys))
        for w in run.workers:
            assert np.asarray(w.pull_sync(run.dense_keys)).tobytes() \
                == main.tobytes()
        for st in srv.stores:
            assert not np.asarray(st.delta).any()
        total = [np.zeros(t.shape, np.float64) for t in init]
        for s in steps:
            rf = s["feat"][np.searchsorted(s["uf"], s["kf"])]
            rd = s["dense"]
            _, g_feat, g = dlrm_np.loss_and_grads(
                rf[..., :DIM], dlrm_np.unpack(rd[:, :ROW], TENS, ROW),
                s["x"], s["y"], HOT, *DEPTH)
            np.add.at(total[0], s["kf"].ravel(), dlrm_np.position_updates(
                g_feat, rf[..., DIM:], LR, EPS).reshape(-1, 2 * DIM))
            total[1] += dlrm_np.position_updates(
                dlrm_np.pack(g, TENS, ROW), rd[:, ROW:], LR, EPS)
        for cls, width in ((0, DIM), (1, ROW)):
            want = init[cls] + total[cls]
            # every column, embedding and accumulator: eight steps' sums,
            # each through a delta pool and a sync or straight into main
            for cols in (slice(0, width), slice(width, 2 * width)):
                norm_gap, diff = _gaps(got[cls], want, init[cls], cols)
                assert norm_gap < NORM_GAP and diff < DIFF_SHARE, \
                    (cls, cols, norm_gap, diff)
        # (one step's update of one class left out would read 0.1 and
        # more.) From a cold start the first step of the pass finds no
        # replica and the first worker took the main copies its pool had
        # room for: still a third of the 8 * n_dense dense positions and
        # more went through cache + delta
        assert _counter(run, f"fused.replica_positions.len{2 * ROW}") \
            >= 3 * run.n_dense
    finally:
        run.srv.shutdown()


def test_a_small_class_asks_for_no_more_replica_slots_than_it_has_keys():
    """One `--sys.cache_slots_per_shard` sizes every class's replica
    pools, each capped at its own key count (in whole tiles of 8)."""
    import adapm_tpu
    from adapm_tpu.config import SystemOptions
    lens = np.concatenate([np.full(10_000, 4), np.full(21, 16)])
    srv = adapm_tpu.setup(len(lens), lens, num_shards=4, opts=SystemOptions(
        cache_slots_per_shard=512, sync_max_per_sec=0, prefetch=False))
    try:
        many, few = srv.stores
        assert (many.cache_slots, few.cache_slots) == (512, 24)
        assert few.cache.shape == few.delta.shape == (4, 24, 16)
    finally:
        srv.shutdown()
    run = ctr.open_run(_args())
    try:
        dense = run.srv.stores[run.c_dense]
        assert dense.cache_slots == -8 * (-run.n_dense // 8) < 4096
    finally:
        run.srv.shutdown()


def test_one_shard_losses_are_what_they_were_with_a_plan_prepared_whole():
    """The one-shard app over two passes: the pass losses of the loop
    that prepared a worker's whole plan at its first step and gave the
    first --lookahead batches no intent (PR 38's, read on this CPU),
    bit for bit."""
    run = ctr.open_run(_args("--sys.techniques", "all", shards=1, workers=1,
                             examples=4 * B + 5))
    try:
        losses = [ctr.train(run) for _ in range(2)]
        assert [float(x).hex() for x in losses] == \
            ["0x1.3bc6e80000000p-1", "0x1.12e8be0000000p-1"]
    finally:
        run.srv.shutdown()
