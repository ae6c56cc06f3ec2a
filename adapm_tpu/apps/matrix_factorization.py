"""Matrix factorization app (reference apps/matrix_factorization.cc).

SGD MF with AdaGrad, L2, and bold-driver step size, in the reference's three
access orders (matrix_factorization.cc:409-579):

  dsgd        worker x subepoch disjoint column-block schedule, barrier per
              subepoch, intent one subepoch ahead
  columnwise  each worker walks its points sorted by column, intent
              `--lookahead` batches ahead
  plain       shuffled SGD over the worker's row-block partition

Key layout (reference :692-693): row keys [0, m), column keys [m, m+n);
value row = [factor (rank) | AdaGrad (rank)] (:695-697). Batches run as one
fused gather -> grad -> AdaGrad -> scatter-add program (ops/fused.py).

The pass-end loss (it feeds the bold driver) is computed where the table
lives (reference apps/mf/loss.h, the form without a full model pull): each
worker walks its own points batch by batch through the fused step's
gather-only score program (DeviceRoutedRunner.score), the squared errors add
up on the device, the L2 term is one reduction over the factor columns of
the main pool, and two numbers cross to the host. The whole table is read
to the host (`Server.read_main`) only by `--export_prefix`.

`open_run(args)` sets a run up, `train(run)` trains `--epochs` passes on it
(and can be called again: the step size and the bold driver's last loss live
on the run), `run(args)` is both and shuts the server down.

Run: python -m adapm_tpu.apps.matrix_factorization --synthetic ...
"""
from __future__ import annotations

import argparse
import sys

import jax.numpy as jnp
import numpy as np

from ..device import default_port
from ..exec import dispatch_gate
from ..io import mf as mfio
from ..models.mf import make_mf_loss, mf_sq_error
from .common import (AppRun, Batch, KeyMapper, add_common_arguments,
                     enforce_full_replication, make_server, wrap_batches,
                     worker0_init)

_GATE = dispatch_gate()


def _load_data(args):
    if args.data:
        rows, cols, vals, m, n = mfio.read_coo(args.data)
    else:
        rows, cols, vals, _, _ = mfio.generate_synthetic(
            args.rows, args.cols, args.rank, args.nnz, seed=args.seed)
        m, n = args.rows, args.cols
    return rows, cols, vals, m, n


def _init_factors(args, m, n, rank, rng):
    if args.init_w and args.init_h:
        W = mfio.read_dense(args.init_w)[:, :rank]
        H = mfio.read_dense(args.init_h)[:, :rank]
    else:
        W = (rng.random((m, rank)).astype(np.float32) - 0.5) / np.sqrt(rank)
        H = (rng.random((n, rank)).astype(np.float32) - 0.5) / np.sqrt(rank)
    return W, H


def _masked_sq_sum(main, occupied, rank: int):
    """Sum of squares of the first `rank` columns of the rows of `main`
    [S, M, L] whose slot is occupied [S, M]: a vacated slot keeps its
    last row."""
    f = main[..., :rank]
    return jnp.sum(jnp.where(occupied[..., None], f * f, 0))


class MfRun(AppRun):
    """One training run: the server, its workers and their fused runners,
    the data points with their keys, and what carries over from pass to
    pass (step size, the bold driver's last loss, the shuffling
    generator, the pass count over all train() calls)."""

    tag = "mf"

    def __init__(self, args, data):
        rows, cols, vals, m, n = data
        self.m, self.n, self.rank = m, n, args.rank
        num_keys = m + n
        self.rng = np.random.default_rng(args.seed)
        self.kmap = KeyMapper(num_keys, args.enforce_random_keys,
                              seed=args.seed)
        self.attach_server(args, make_server(
            args, num_keys, 2 * self.rank,
            num_workers=args.num_workers or None))
        from ..parallel import control
        self.pid = control.process_id()
        self.total_workers = control.num_processes() * self.num_workers
        self.set_points(rows, cols, vals)

        self.lr = args.lr
        self.prev_loss = np.inf
        self.best_loss = np.inf

        self._sq_sum = default_port().compile(_masked_sq_sum,
                                              static_argnums=2)
        self._occupied = None       # device mask of the pool's live slots
        self._occupied_version = None
        self._h_loss_pass = self.srv.obs.histogram("app.loss_pass_s",
                                                   shared=True)

    def set_points(self, rows, cols, vals) -> None:
        """The revealed cells this run trains on. Every point's two keys
        and its value are worked out once: a batch is an index array
        into these."""
        m = self.m
        self.cols = cols
        self.wkey = self.kmap(rows)
        self.hkey = self.kmap(cols + m)
        self.vals = np.asarray(vals, dtype=np.float32)
        # row-block data partition over ALL workers of ALL processes
        # (reference mf/io.h:125+; DSGD's block schedule spans them too)
        part = mfio.partition_points(rows, self.total_workers, m)
        self.by_worker = [
            np.nonzero(part == self.pid * self.num_workers + wi)[0]
            for wi in range(self.num_workers)]
        if self.args.algorithm == "columnwise":
            # each worker walks its points sorted by column, every pass
            self.by_worker = [mine[np.argsort(cols[mine], kind="stable")]
                              for mine in self.by_worker]
        # what a walk that is the same every pass works out once (built
        # at first use): worker -> its prepared batches
        self._train_plans = {}
        self._loss_plans = {}

    def runner_spec(self) -> dict:
        return dict(loss_fn=make_mf_loss(self.args.l2),
                    role_class={"w": 0, "h": 0},
                    role_dim={"w": self.rank, "h": self.rank},
                    score_fn=mf_sq_error)

    def precompile(self) -> int:
        """`Server.precompile` with this app's sizes: an intent names at
        most 2B keys (columnwise, plain; a DSGD block's intent can name
        more and compiles its bucket at first use); the loop drives one
        kind of runner, a batch of B points a step and a score dispatch
        (a --scan_steps window still compiles at its first use). Returns
        how many planner programs ran."""
        B = self.args.batch_size
        z = np.zeros(B, dtype=np.int64)
        x = np.zeros(B, dtype=np.float32)
        put = self.srv.ctx.put_replicated   # as `_loss_plan` hands it
        steps = [(self.device_runner(self.workers[0].shard),
                  {"w": z, "h": z}, x, (put(x), put(np.int32(0))))]
        return self.srv.precompile({0: min(2 * B, self.m + self.n)}, steps)

    def init_model(self) -> None:
        """Worker 0 sets every row from the host: uniform factors (or
        --init_w/--init_h), the AdaGrad columns at --adagrad_init."""
        a = self.args
        W, H = _init_factors(a, self.m, self.n, self.rank, self.rng)
        init = np.concatenate(
            [np.concatenate([W, np.full_like(W, a.adagrad_init)], axis=1),
             np.concatenate([H, np.full_like(H, a.adagrad_init)], axis=1)])
        worker0_init(self.workers, self.kmap(np.arange(self.m + self.n)),
                     init)

    def current_factors(self):
        """(W, H) on the host: the whole table through `read_main`. For
        --export_prefix (and a tiered store's L2 term) only."""
        flat = self.srv.read_main(self.kmap(np.arange(self.m + self.n)))
        M = flat.reshape(self.m + self.n, 2 * self.rank)[:, :self.rank]
        return M[:self.m], M[self.m:]

    # -- a training step -----------------------------------------------------

    def batch(self, idx: np.ndarray):
        """(role keys, observed values) of the points `idx`."""
        return {"w": self.wkey[idx], "h": self.hkey[idx]}, self.vals[idx]

    def prepared(self, idx: np.ndarray) -> Batch:
        """The batch of the points `idx` with what its intent needs: the
        distinct keys among its 2B."""
        roles, aux = self.batch(idx)
        return Batch(roles, aux,
                     np.unique(np.concatenate([roles["w"], roles["h"]])))

    def _walk(self, wi: int, rng) -> None:
        """Worker `wi`'s pass over its points in batches of B
        (`AppRun.walk`). Shuffled by `rng`, every batch is built where
        it is prepared; unshuffled (columnwise) the pass is the same
        every time, so its batches (keys, values, distinct keys, the
        keys' upload) are built at the first pass and kept."""
        a, mine = self.args, self.by_worker[wi]
        if rng is not None:
            index = list(wrap_batches(len(mine), a.batch_size, rng))
            get = lambda bi: self.prepared(mine[index[bi]])  # noqa: E731
            n = len(index)
        else:
            if wi not in self._train_plans:
                self._train_plans[wi] = [
                    self.prepared(mine[idx])
                    for idx in wrap_batches(len(mine), a.batch_size)]
            get = self._train_plans[wi].__getitem__
            n = len(self._train_plans[wi])
        self.walk(self.workers[wi], n, get, self.lr)

    def train_pass(self) -> None:
        """One pass over this process's points in --algorithm's order."""
        a, srv, workers = self.args, self.srv, self.workers
        B, T = a.batch_size, self.total_workers
        if a.algorithm != "dsgd":
            rng = self.rng if a.algorithm == "plain" else None
            for wi in range(len(workers)):
                self._walk(wi, rng)
            return
        sched = mfio.dsgd_schedule(T, self.epoch, seed=a.seed)
        cblock = mfio.column_block(self.cols, T, self.n)
        for s in range(T):
            for wi, w in enumerate(workers):
                gwi = self.pid * self.num_workers + wi  # global worker id
                mine = self.by_worker[wi]
                blk = mine[cblock[mine] == sched[s, gwi]]
                # intent for the *next* subepoch's block; the clock
                # advances once per batch, so the window starts after
                # this block's batches and spans the next block's
                nb_cur = max(-(-len(blk) // B), 1)
                if s + 1 < T:
                    nxt = mine[cblock[mine] == sched[s + 1, gwi]]
                    if len(nxt):
                        nb_nxt = max(-(-len(nxt) // B), 1)
                        with srv._span("app.prepare", self._h_prepare,
                                       work=self._h_prepare_work):
                            self.signal_intent(
                                w, self.prepared(nxt),
                                w.current_clock + nb_cur,
                                w.current_clock + nb_cur + nb_nxt)
                # fixed batch size B: wrap_batches tiles small blocks so
                # every fused step has one static shape (one XLA compile).
                # A window a block (shards must not mix in one, and it
                # is flushed before the barrier), at the CURRENT lr: the
                # bold driver changes it from pass to pass
                win = self.window(w, self.lr)
                for idx in wrap_batches(len(blk), B, self.rng):
                    win.add(Batch(*self.batch(blk[idx])))
                    w.advance_clock()
                win.flush()
            srv.barrier()  # per-subepoch barrier (reference :409-458)

    # -- the pass-end loss ---------------------------------------------------

    def _factor_sq_sum(self):
        """|W|^2 + |H|^2 as a device scalar: one reduction over the
        factor columns of the main pool in slot order, vacated slots
        masked (after quiesce() the main copies are the table)."""
        srv = self.srv
        store = srv.stores[0]
        with srv._lock:
            if self._occupied_version != srv.topology_version:
                ab = srv.ab
                keys = np.nonzero(ab.owner >= 0)[0]   # this process's
                occ = np.zeros(store.main.shape[:2], dtype=bool)
                occ[ab.owner[keys], ab.slot[keys]] = True
                self._occupied = default_port().put_replicated(
                    occ, srv.ctx.shard0())
                self._occupied_version = srv.topology_version
            with srv.exec.track("main"), _GATE:
                return self._sq_sum(store.main, self._occupied, self.rank)

    def _loss_plan(self, wi: int) -> list:
        """Worker `wi`'s points in batches of B for the loss walk, the
        same every pass: keys and values go to the device at the first
        walk and stay. The last batch is filled up and says how many of
        its cells count."""
        if wi not in self._loss_plans:
            B = self.args.batch_size
            mine = self.by_worker[wi]
            runner = self.device_runner(self.workers[wi].shard)
            put = self.srv.ctx.put_replicated
            plan = self._loss_plans[wi] = []
            for lo in range(0, len(mine), B):
                idx = mine[lo:lo + B]
                n = len(idx)
                if n < B:
                    idx = np.concatenate([idx, np.repeat(idx[-1:], B - n)])
                roles, x = self.batch(idx)
                plan.append(Batch(roles, (put(x), put(np.int32(n))), None,
                                  runner.prefetch_keys(roles)))
        return self._loss_plans[wi]

    def pass_loss(self) -> float:
        """Sum of squared errors over all points + l2 (|W|^2 + |H|^2),
        the reference's full loss, without the table on the host: every
        worker scores its own points batch by batch
        (DeviceRoutedRunner.score over `_loss_plan`'s staged batches),
        the sums stay on the device, and one fetch brings two numbers
        back. Several processes add theirs up."""
        srv, a = self.srv, self.args
        with srv._span("app.loss_pass", self._h_loss_pass):
            err = None
            for wi, w in enumerate(self.workers):
                runner = self.device_runner(w.shard)
                for b in self._loss_plan(wi):
                    err = runner.score(b.roles, b.aux, err,
                                       staged=b.staged)
            on_device = bool(a.l2) and srv.tier is None
            sq = self._factor_sq_sum() if on_device else 0.0
            with srv._span("app.loss_fetch", wait=True):
                err = 0.0 if err is None else float(err)
                sq = float(sq)
        if a.l2 and not on_device:
            # a tiered table is not wholly on the device
            W, H = self.current_factors()
            sq = float((W * W).sum() + (H * H).sum())
        if self.total_workers > self.num_workers:
            from ..parallel import control
            err, sq = control.allreduce([err, sq], "sum", site="mf_loss")
        return float(err) + a.l2 * float(sq)

    def pass_end(self, out) -> tuple:
        """The pass-end loss and the bold driver's new step size."""
        a = self.args
        loss = self.pass_loss()
        lr = self.lr
        # bold driver (reference matrix_factorization.cc): grow on
        # success, shrink on divergence, compared to the *previous*
        # pass, so a recovery after one bad pass counts as success
        self.lr = lr * a.bold_inc if loss <= self.prev_loss \
            else lr * a.bold_dec
        self.prev_loss = loss
        self.best_loss = min(self.best_loss, loss)
        return loss, f"lr={lr:.4f}"


def open_run(args) -> MfRun:
    """Set-up: data, server, initialized factors, compiled programs. The
    returned run's server is live; the caller shuts it down
    (`run.srv.shutdown()`), as `run` does."""
    mrun = MfRun(args, _load_data(args))
    mrun.init_model()
    if args.enforce_full_replication:
        enforce_full_replication(mrun.workers, mrun.m + mrun.n)
    mrun.precompile()
    return mrun


def train(mrun: MfRun) -> float:
    """`--epochs` passes over an opened run, each ended by the pass-end
    loss and the bold driver's new step size; stops at the first pass end
    after `--max_runtime`. Leaves the server up (see open_run) and can be
    called again on the same run. Returns the best pass loss so far."""
    args = mrun.args
    mrun.train_passes()
    if args.export_prefix and mrun.pid == 0:
        Wc, Hc = mrun.current_factors()
        mfio.write_dense(args.export_prefix + "W.mma", Wc)
        mfio.write_dense(args.export_prefix + "H.mma", Hc)
    return float(mrun.best_loss)


def run(args) -> float:
    mrun = open_run(args)
    best = train(mrun)
    mrun.srv.shutdown()
    return best


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--data", default=None,
                        help="MatrixMarket coordinate file (else synthetic)")
    parser.add_argument("--rows", type=int, default=200)
    parser.add_argument("--cols", type=int, default=100)
    parser.add_argument("--nnz", type=int, default=4000)
    parser.add_argument("--rank", type=int, default=16)
    parser.add_argument("--l2", type=float, default=0.01)
    parser.add_argument("--algorithm", default="dsgd",
                        choices=["dsgd", "columnwise", "plain"])
    parser.add_argument("--scan_steps", type=int, default=1,
                        help="batches trained per device dispatch "
                             "(lax.scan window, runner.run_scan; same "
                             "contract as the KGE app's --scan_steps)")
    parser.add_argument("--lookahead", type=int, default=2,
                        help="intent batches ahead (columnwise/plain)")
    parser.add_argument("--adagrad_init", type=float, default=1e-6)
    parser.add_argument("--bold_inc", type=float, default=1.05,
                        help="step-size factor after a pass whose loss "
                             "(computed on the device, see above) did "
                             "not rise")
    parser.add_argument("--bold_dec", type=float, default=0.5,
                        help="step-size factor after a pass whose loss "
                             "rose")
    parser.add_argument("--init_w", default=None)
    parser.add_argument("--init_h", default=None)
    parser.add_argument("--export_prefix", default=None,
                        help="write W.mma / H.mma under this prefix at "
                             "the end of train(): the one reader of the "
                             "whole table on the host")
    add_common_arguments(parser)
    return parser


def main(argv=None) -> int:
    run(build_parser().parse_args(argv))
    return 0


if __name__ == "__main__":
    sys.exit(main())
