"""The MF app against the plain reference (`benchmarks/reference/mf_np.py`:
numpy float32, imports nothing of the program), at a few hundred keys and
rank 8, on one kv shard and on four: the fused step on unique, duplicated
and mixed batches, the pass-end loss that never brings the table to the
host, and `open_run` / `train(run)` holding the app to a window."""
import os
import signal
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

from reference import mf_np  # noqa: E402

from adapm_tpu.apps import matrix_factorization as mf  # noqa: E402
from adapm_tpu.core.kv import Server  # noqa: E402
from adapm_tpu.models import mf as mf_model  # noqa: E402

M, N, RANK, B, L2, LR = 160, 96, 8, 64, 0.01, 0.1
FAST = ["--sys.sync.max_per_sec", "0", "--sys.prefetch", "0"]
# float32 limits, each with its reason. A loss is a mean of B squares
# summed in another order (XLA's tree against numpy's pairwise sum):
# a few ulp. A gradient's norm is read from the accumulator columns,
# sums of g*g: the same. The update divides by rsqrt against numpy's
# sqrt, and B positions that name one row add up in another order: up to
# sqrt(B) ulp of the sum; 2e-5 is the benchmark's own limit for it.
LOSS_GAP, NORM_GAP, DIFF_SHARE = 2e-6, 5e-6, 2e-5


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


def _args(*extra, shards=1, algorithm="columnwise", nnz=600, epochs=1):
    return mf.build_parser().parse_args(
        ["--rows", str(M), "--cols", str(N), "--nnz", str(nnz),
         "--rank", str(RANK), "--batch_size", str(B), "--lr", str(LR),
         "--l2", str(L2), "--algorithm", algorithm, "--epochs", str(epochs),
         "--num_shards", str(shards), "--num_workers", "1", "--seed", "7",
         "--sys.main_over_alloc", "2.0"] + FAST + list(extra))


def _table(run):
    return np.asarray(run.srv.read_main(
        np.arange(M + N))).reshape(M + N, 2 * RANK).copy()


def _batches(rng):
    """Four batches of B points: every key once; the column role ONE key
    B times; two mixed draws with duplicates in both roles."""
    val = lambda: rng.normal(size=B).astype(np.float32)  # noqa: E731
    return [
        (rng.permutation(M)[:B], rng.permutation(N)[:B], val()),
        (rng.integers(0, M, B), np.full(B, 5), val()),
        (rng.integers(0, 24, B), rng.integers(0, 6, B), val()),
        (rng.integers(0, M, B), rng.integers(0, N, B), val())]


def _with_distinct(roles):
    return mf.Batch(roles, None, np.unique(
        np.concatenate([roles["w"], roles["h"]])))


def _leaf_gaps(got, want, init, cols):
    """Per leaf (row factors, column factors): the gap of the norms of
    the change of columns `cols`, and the norm of the two changes'
    difference, over the reference's norm."""
    out = []
    for leaf in (slice(0, M), slice(M, M + N)):
        p = (got[leaf, cols] - init[leaf, cols]).astype(np.float64)
        q = (want[leaf, cols] - init[leaf, cols]).astype(np.float64)
        nq = np.linalg.norm(q)
        out.append((abs(np.linalg.norm(p) - nq) / nq,
                    np.linalg.norm(p - q) / nq))
    return out


@pytest.mark.parametrize("shards", [1, 4])
def test_fused_step_follows_the_reference_step_by_step(shards):
    """4 steps as the app's loop takes them (intent and planner
    rounds live on four shards): each loss, the first gradient's norm
    (from the accumulator columns), the update's norm and the share of
    its difference, per leaf. The batch whose column role is one key B
    times must agree like the others."""
    run = mf.open_run(_args(shards=shards))
    try:
        w = run.workers[0]
        init = _table(run)
        ref = init.copy()
        for i, (rows, cols, x) in enumerate(
                _batches(np.random.default_rng(3))):
            roles = {"w": run.kmap(rows), "h": run.kmap(cols + M)}
            run.signal_intent(w, _with_distinct(roles), w.current_clock,
                              w.current_clock + 1)
            run.srv.wait_sync()
            before = int(run.srv.obs.find(
                "fused.writeback_rows_total").snap())
            runner = run.device_runner(w.shard)
            loss = float(runner(roles, x, LR))
            run.srv.drive_rounds(1)
            w.advance_clock()
            run.srv.quiesce()
            want = mf_np.step(ref, roles["w"], roles["h"], x, L2, LR)
            assert abs(loss - want) / abs(want) < LOSS_GAP, (i, loss, want)
            assert int(run.srv.obs.find(
                "fused.writeback_rows_total").snap()) - before == 2 * B
            got = _table(run)
            if i == 0:
                # accumulators grew by the sum of g*g: its root is the
                # first gradient's norm
                for leaf in (slice(0, M), slice(M, M + N)):
                    p = np.sqrt((got[leaf, RANK:] - init[leaf, RANK:])
                                .astype(np.float64).sum())
                    q = np.sqrt((ref[leaf, RANK:] - init[leaf, RANK:])
                                .astype(np.float64).sum())
                    assert abs(p - q) / q < NORM_GAP, (p, q)
            for norm_gap, diff in _leaf_gaps(got, ref, init,
                                             slice(0, RANK)):
                assert norm_gap < NORM_GAP and diff < DIFF_SHARE, \
                    (i, norm_gap, diff)
    finally:
        run.srv.shutdown()


@pytest.mark.parametrize("shards", [1, 4])
def test_pass_loss_equals_the_reference_and_the_old_host_path(shards):
    """After a pass (on four shards: rows relocated, vacated slots left
    behind) the device-side pass loss equals `mf_np.full_loss`, equals
    the old path (whole table to the host + numpy), and leaves every
    pool bitwise as it was. 600 points are no multiple of B: the last
    score batch is filled up and masked."""
    run = mf.open_run(_args(shards=shards, algorithm="plain"))
    try:
        mf.train(run)
        rows, cols, vals, _, _ = mf._load_data(run.args)
        pools = lambda: [np.asarray(x).copy() for st in run.srv.stores  # noqa: E731,E501
                         for x in (st.main, st.cache, st.delta)]
        before = pools()
        scored0 = run.srv.obs.find("fused.score_rows_total").snap()
        got = run.pass_loss()
        assert run.srv.obs.find("fused.score_rows_total").snap() \
            - scored0 == 2 * B * -(-len(rows) // B)
        assert all(np.array_equal(a, b, equal_nan=True)
                   for a, b in zip(before, pools()))
        W, H = run.current_factors()
        want = mf_np.full_loss(rows, cols, vals, W, H, L2)
        old = mf_model.full_loss(W, H, (rows, cols, vals), L2)
        # float32 sums of 600 squares and of 2,048 factor entries, in
        # another order than numpy's: a few ulp each
        assert abs(got - want) / want < 2e-6, (got, want)
        assert abs(got - old) / old < 2e-6, (got, old)
        assert got == run.prev_loss      # what train() gave the driver
    finally:
        run.srv.shutdown()


def test_run_is_open_run_plus_train():
    a = mf.run(_args(epochs=3))
    run = mf.open_run(_args(epochs=3))
    b = mf.train(run)
    run.srv.shutdown()
    assert a == b and np.isfinite(a)


@pytest.mark.parametrize("algorithm", ["dsgd", "columnwise", "plain"])
def test_two_train_calls_of_one_pass_equal_one_call_of_two(algorithm):
    """The bold driver's state (step size, last loss), the shuffling
    generator and the pass count live on the run."""
    one = mf.open_run(_args(algorithm=algorithm, epochs=2))
    best_one = mf.train(one)
    two = mf.open_run(_args(algorithm=algorithm, epochs=1))
    mf.train(two)
    assert (two.epoch, two.lr) == (1, LR * two.args.bold_inc)
    best_two = mf.train(two)
    try:
        assert (one.epoch, one.lr, one.prev_loss) == \
            (two.epoch, two.lr, two.prev_loss)
        assert best_one == best_two
        assert np.array_equal(_table(one), _table(two))
    finally:
        one.srv.shutdown()
        two.srv.shutdown()


# the pass loss after each of three `train(run)` calls of one pass at
# PR 43's commit (every app its own loop), as float.hex(): --seed 0 on
# ONE shard, where an intent moves nothing and no upload's moment can
# change a value, two workers (a window is flushed between them)
PARENT = {
    ("columnwise",): ["0x1.886c17dc28f5cp+5", "0x1.721ba6e147ae1p+4",
                      "0x1.59481c28f5c29p+3"],
    ("columnwise", "--scan_steps", "4"): [
        "0x1.886c17dc28f5cp+5", "0x1.721ba6e147ae1p+4",
        "0x1.59481c28f5c29p+3"],
    ("dsgd",): ["0x1.3d6b7447ae148p+5", "0x1.fb16786666666p+3",
                "0x1.b6ae26e147ae1p+2"],
    ("plain",): ["0x1.90f0b21eb851fp+5", "0x1.73a49a7ae147bp+4",
                 "0x1.52669d70a3d71p+3"],
}


@pytest.mark.parametrize("case", sorted(PARENT))
def test_pass_losses_are_the_parent_s_to_the_bit(case):
    """The one batch walk (apps/common.py) trains what the app's own
    loop trained: the same batches in the same order, pass by pass."""
    run = mf.open_run(_args("--seed", "0", "--num_workers", "2", *case[1:],
                            algorithm=case[0]))
    try:
        got = []
        for _ in range(3):
            mf.train(run)
            got.append(float(run.prev_loss).hex())
    finally:
        run.srv.shutdown()
    assert got == PARENT[case]


def test_bold_driver_halves_the_step_after_a_worse_pass():
    run = mf.open_run(_args())
    try:
        run.prev_loss = 0.0          # any pass is worse than this
        mf.train(run)
        assert run.lr == LR * run.args.bold_dec
        assert run.best_loss == run.prev_loss > 0.0
    finally:
        run.srv.shutdown()


def test_train_never_reads_the_table_to_the_host(monkeypatch, tmp_path):
    """A pass end brings two numbers to the host: `read_main` is reached
    by --export_prefix alone."""
    calls = []
    read_main = Server.read_main

    def counted(self, keys):
        calls.append(len(keys))
        return read_main(self, keys)
    monkeypatch.setattr(Server, "read_main", counted)
    assert np.isfinite(mf.run(_args(epochs=2)))
    assert calls == []
    mf.run(_args("--export_prefix", str(tmp_path) + "/"))
    assert calls == [M + N]


def test_max_runtime_stops_at_the_first_pass_end():
    run = mf.open_run(_args("--max_runtime", "1e-9", epochs=50))
    try:
        mf.train(run)
        assert run.epoch == 1
        mf.train(run)
        assert run.epoch == 2
    finally:
        run.srv.shutdown()


def test_open_run_compiles_what_train_runs():
    """`MfRun.precompile`: the step and the score program stand before
    the first pass; a pass adds no program."""
    run = mf.open_run(_args())
    try:
        before = set(run._programs)
        assert ("make_device_routed_score", True) in before
        mf.train(run)
        assert set(run._programs) == before
    finally:
        run.srv.shutdown()


@pytest.mark.parametrize("prefetch", ["0", "1"])
def test_unshuffled_walks_are_prepared_once(prefetch):
    """A columnwise pass and the loss walk are the same every pass: their
    batches (keys, values, uploads) are built at the first and kept;
    `set_points` drops them; a shuffled walk keeps none. Every batch of
    a walk is staged (its keys uploaded where it is prepared), the first
    --lookahead too, with the prefetch pipeline and without it."""
    run = mf.open_run(_args("--sys.prefetch", prefetch, epochs=2))
    try:
        mf.train(run)
        plan, loss_plan = run._train_plans[0], run._loss_plans[0]
        assert len(plan) == len(loss_plan) == -(-600 // B)
        assert all(b.staged is not None for b in loss_plan)
        assert all(b.staged is not None and b.staged.matches(b.roles)
                   for b in plan)
        uploads = [b.staged for b in plan]
        mf.train(run)
        assert run._train_plans[0] is plan and run._loss_plans[0] is \
            loss_plan
        assert all(b.staged is u for b, u in zip(plan, uploads))
        rows, cols, vals, _, _ = mf._load_data(run.args)
        run.set_points(rows[:B], cols[:B], vals[:B])
        assert run._train_plans == {} and run._loss_plans == {}
    finally:
        run.srv.shutdown()
    shuffled = mf.open_run(_args("--sys.prefetch", prefetch,
                                 algorithm="plain"))
    try:
        mf.train(shuffled)
        assert shuffled._train_plans == {} and len(shuffled._loss_plans) == 1
    finally:
        shuffled.srv.shutdown()


def test_batch_key_counters():
    """`app.batch_unique_keys_total` <= `app.batch_keys_total`, equal on
    a batch that names every key once."""
    run = mf.open_run(_args())
    try:
        obs = run.srv.obs
        snap = lambda: (obs.find("app.batch_keys_total").snap(),  # noqa: E731
                        obs.find("app.batch_unique_keys_total").snap())
        w = run.workers[0]
        rng = np.random.default_rng(5)
        uniq = {"w": rng.permutation(M)[:B],
                "h": rng.permutation(N)[:B] + M}
        k0, u0 = snap()
        run.signal_intent(w, _with_distinct(uniq), 1, 2)
        k1, u1 = snap()
        assert (k1 - k0, u1 - u0) == (2 * B, 2 * B)
        dup = {"w": rng.integers(0, M, B), "h": np.full(B, M + 5)}
        run.signal_intent(w, _with_distinct(dup), 1, 2)
        k2, u2 = snap()
        assert k2 - k1 == 2 * B
        assert u2 - u1 == len(np.unique(dup["w"])) + 1 < 2 * B
        mf.train(run)
        k3, u3 = snap()
        assert k3 > k2 and u3 - u2 <= k3 - k2
    finally:
        run.srv.shutdown()


def test_score_program_is_named_and_scoped():
    """The trace finds the score program as `jit_score`, its gather and
    loss under the step's scope names."""
    import jax
    run = mf.open_run(_args())
    try:
        fn = run._programs[("make_device_routed_score", True)]
        srv, runner = run.srv, run.device_runner(0)
        pools = tuple((s.main, s.cache, s.delta) for s in srv.stores)
        z = np.zeros(B, np.int32)
        x = np.zeros(B, np.float32)
        lowered = fn.lower(pools, runner._tables(), {"w": z, "h": z},
                           (x, np.int32(B)), jax.numpy.float32(0))
        text = lowered.as_text(debug_info=True)
        assert "jit_score" in text
        assert "adapm_gather" in text and "adapm_loss_grad" in text
        assert "adapm_scatter_add" not in text
    finally:
        run.srv.shutdown()
