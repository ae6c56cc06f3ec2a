"""Shared application harness: the idioms every reference app uses
(SURVEY.md §2.3 "Common app idioms").

- `enforce_random_keys`: random key shuffling for load balance — apps address
  logical keys, a fixed permutation maps them to physical PM keys
  (reference apps shuffle key assignment, e.g. kge.cc / word2vec.cc flag).
- `enforce_full_replication`: Intent all keys to CLOCK_MAX as an ablation
  (replication-everywhere baseline).
- worker-0-initializes + BeginSetup/EndSetup bracket.
- `max_runtime` epoch cutoff.
- wrap-around batching: fused steps are fixed-shape XLA programs, so the tail
  of a data partition wraps to its start (a few duplicate points per epoch
  instead of a recompile per tail size).
"""
from __future__ import annotations

import argparse
from typing import Optional

import numpy as np

from ..base import CLOCK_MAX
from ..config import SystemOptions
from ..utils import Stopwatch, alog


def add_common_arguments(parser: argparse.ArgumentParser,
                         default_epochs: int = 4) -> None:
    g = parser.add_argument_group("run")
    g.add_argument("--num_workers", type=int, default=0,
                   help="logical workers (0 = one per mesh shard)")
    g.add_argument("--num_shards", type=int, default=0,
                   help="kv shards (0 = all visible devices)")
    g.add_argument("--epochs", type=int, default=default_epochs)
    g.add_argument("--batch_size", type=int, default=256)
    g.add_argument("--lr", type=float, default=0.1)
    g.add_argument("--seed", type=int, default=42)
    g.add_argument("--max_runtime", type=float, default=0.0,
                   help="stop after this many seconds (0 = unlimited)")
    g.add_argument("--enforce_random_keys", action="store_true",
                   help="randomly permute key assignment for load balance")
    g.add_argument("--enforce_full_replication", action="store_true",
                   help="ablation: Intent all keys everywhere, forever")
    g.add_argument("--sync_rounds_per_step", type=int, default=1,
                   help="planner sync rounds driven per training step")
    SystemOptions.add_arguments(parser)


def make_server(args, num_keys: int, value_lengths, num_workers: int):
    import adapm_tpu
    opts = SystemOptions.from_args(args)
    srv = adapm_tpu.setup(num_keys, value_lengths, opts=opts,
                          num_shards=args.num_shards or None,
                          num_workers=num_workers)
    return srv


class KeyMapper:
    """Logical key -> physical PM key. Identity unless enforce_random_keys;
    then a seeded permutation (reference `enforce_random_keys`: shuffled
    assignment balances hot keys over servers)."""

    def __init__(self, num_keys: int, shuffle: bool, seed: int = 1234):
        if shuffle:
            rng = np.random.default_rng(seed)
            self.perm = rng.permutation(num_keys).astype(np.int64)
        else:
            self.perm = None

    def __call__(self, keys):
        keys = np.asarray(keys, dtype=np.int64)
        return self.perm[keys] if self.perm is not None else keys


def enforce_full_replication(workers, num_keys: int) -> None:
    """Every worker declares eternal intent on every key, then one forced
    sync round materializes the replicas (ablation mode)."""
    all_keys = np.arange(num_keys, dtype=np.int64)
    for w in workers:
        w.intent(all_keys, 0, CLOCK_MAX)
    workers[0].server.wait_sync()


def worker0_init(workers, keys: np.ndarray, values: np.ndarray,
                 slab: int = 100_000) -> None:
    """Worker 0 of PROCESS 0 initializes the model inside
    BeginSetup/EndSetup (the reference's worker-0-initializes pattern;
    under the launcher, cross-process Sets route to each key's owner)."""
    from ..parallel import control
    w0 = workers[0]
    w0.begin_setup()
    if control.process_id() == 0:
        for lo in range(0, len(keys), slab):
            hi = min(lo + slab, len(keys))
            w0.set(keys[lo:hi], values[lo:hi])
        w0.wait_all()
    w0.end_setup()  # barriers: every rank sees the initialized model


def global_worker_slices(n_items: int, num_local_workers: int):
    """Per-local-worker contiguous slices of [0, n_items) partitioned over
    ALL workers of ALL processes (reference apps partition data by global
    worker id, word2vec.cc:524-531, kge.cc:968-970). Returns a list of
    index arrays, one per local worker."""
    from ..parallel import control
    P, pid = control.num_processes(), control.process_id()
    parts = np.array_split(np.arange(n_items), P * num_local_workers)
    return [parts[pid * num_local_workers + wi]
            for wi in range(num_local_workers)]


def wrap_batches(n: int, batch_size: int, rng: Optional[np.random.Generator]
                 = None):
    """Yield index arrays of exactly batch_size covering [0, n), shuffled if
    rng given; the final batch wraps around to the start."""
    if n == 0:
        return
    order = rng.permutation(n) if rng is not None else np.arange(n)
    for lo in range(0, n, batch_size):
        idx = order[lo:lo + batch_size]
        if len(idx) < batch_size:
            reps = -(-batch_size // n)  # n may be smaller than the shortfall
            idx = np.concatenate([idx, np.tile(order, reps)])[:batch_size]
        yield idx


class Batch:
    """A batch as the walk hands it on: role keys, the step's (or the
    score's) aux, the distinct keys among the role keys (what its intent
    names; None where no intent is made) and the keys' upload (the
    `StagedKeys` handle, or None: the dispatch uploads them)."""

    __slots__ = ("roles", "aux", "keys", "staged")

    def __init__(self, roles, aux, keys=None, staged=None):
        self.roles, self.aux, self.keys, self.staged = \
            roles, aux, keys, staged


class ScanWindow:
    """A worker's dispatches, the apps' shared --scan_steps contract: a
    full K-batch window trains in ONE lax.scan dispatch
    (DeviceRoutedRunner.run_scan) followed by K * sync_rounds_per_step
    planner rounds; K = 1, and a partial tail window, dispatch step by
    step, each followed by its rounds (one compiled scan variant per K,
    and tails are rare). The caller ticks the clock as it adds (a
    batch, or a sentence), so a window's first batch is dispatched up
    to K - 1 clocks after it was added: intents end that much later
    (`AppRun.signal_intent`). One window, one runner: batches of one
    window must come from ONE worker shard, so flush at worker/block
    boundaries."""

    def __init__(self, server, runner, K: int, sync_rounds_per_step: int,
                 lr: float, eps: float = 1e-10, on_loss=None):
        self.server, self.runner = server, runner
        self.K = K
        self.rounds = sync_rounds_per_step
        self.lr, self.eps = lr, eps
        self.on_loss = on_loss or (lambda loss: None)
        self.buf: list = []  # Batch

    def add(self, b: Batch) -> None:
        self.buf.append(b)
        if len(self.buf) == self.K:
            self.flush()

    def flush(self) -> None:
        if not self.buf:
            return
        if len(self.buf) == self.K and self.K > 1:
            has_aux = self.buf[0].aux is not None
            self.on_loss(self.runner.run_scan(
                [b.roles for b in self.buf],
                [b.aux for b in self.buf] if has_aux else None,
                self.lr, eps=self.eps))
            # drive_rounds: inline planner rounds, or delegated to the
            # prefetch pipeline's background thread (SystemOptions
            # .prefetch) so they overlap the in-flight scan window
            self.server.drive_rounds(len(self.buf) * self.rounds)
        else:
            for b in self.buf:
                self.on_loss(self.runner(b.roles, b.aux, self.lr,
                                         eps=self.eps, staged=b.staged))
                self.server.drive_rounds(self.rounds)
        self.buf.clear()


class AppRun:
    """What the apps' run objects share (KGE, MF, CTR, word2vec): the
    server and its workers, the fused runners, the loop's own metrics,
    the batch walk and the pass loop. An app is a loss and a key layout
    (`runner_spec`), its batches (`get(bi)` handed to `walk`, or a loop
    of its own over `window`), a pass (`train_pass`) and a pass end
    (`pass_end`)."""

    tag = "app"     # the log lines' `[tag]`

    def attach_server(self, args, srv) -> None:
        """`srv` is the app's `make_server(args, num_keys, value_lengths,
        num_workers=args.num_workers or None)`: made by the app's module,
        where a caller that needs other store options than the flags
        give puts its own (`benchmarks/drivers/_kge.py`)."""
        self.args = args
        self.srv = srv
        self.num_workers = args.num_workers or self.srv.num_shards
        self.workers = [self.srv.make_worker(i)
                        for i in range(self.num_workers)]
        self.epoch = 0      # passes trained so far
        # --scan_steps K: K batches train in ONE dispatch (ScanWindow)
        self.K = max(1, getattr(args, "scan_steps", 1))
        # the workers' device runners are built alike but for their
        # shard, an operand of the step: they share their compiled
        # programs (ops/fused.py DeviceRoutedRunner, `programs`)
        self._programs = {}
        self._dev_runners = {}      # shard -> DeviceRoutedRunner
        # host time of the loop's own phases (Server._span; the step's
        # other phases are bracketed where they live: kv.intent,
        # fused.dispatch, kv.drive_rounds, kv.advance_clock), and how
        # many of a batch's keys are distinct
        obs = self.srv.obs
        self._h_prepare = obs.histogram("app.prepare_s", shared=True)
        self._h_pass_end = obs.histogram("app.pass_end_s", shared=True)
        # the same less the waits for the device beneath them (`work=`)
        self._h_prepare_work = obs.histogram("app.prepare_work_s",
                                             shared=True)
        self._h_pass_end_work = obs.histogram("app.pass_end_work_s",
                                              shared=True)
        self._c_keys = obs.counter("app.batch_keys_total", unit="keys",
                                   shared=True)
        self._c_unique = obs.counter("app.batch_unique_keys_total",
                                     unit="keys", shared=True)

    # -- the fused runners -----------------------------------------------------

    def runner_spec(self) -> dict:
        """`DeviceRoutedRunner`'s keywords for this app: `loss_fn`,
        `role_class`, `role_dim`, and what else its step needs (the
        sampler's, `score_fn`); `shard`, `seed` and the shared
        `programs` are `device_runner`'s unless named here."""
        raise NotImplementedError

    def device_runner(self, shard: int):
        """The fused step's runner for the worker on `shard`, built at
        first use: routing tables (and negative sampling) live on
        device; one runner per worker shard, all sharing their compiled
        programs."""
        if shard not in self._dev_runners:
            from ..ops import DeviceRoutedRunner
            spec = dict(shard=shard, seed=self.args.seed + shard,
                        programs=self._programs)
            spec.update(self.runner_spec())
            self._dev_runners[shard] = DeviceRoutedRunner(self.srv, **spec)
        return self._dev_runners[shard]

    # -- a worker's turn -------------------------------------------------------

    def signal_intent(self, w, b: Batch, start: int, end: int) -> None:
        """The intent on `b`'s distinct keys for the clocks [start, end),
        kept K - 1 clocks longer (a window's dispatch delay), and the
        counts of what it names."""
        self._c_keys.inc(sum(np.size(k) for k in b.roles.values()))
        self._c_unique.inc(len(b.keys))
        w.intent(b.keys, start, end + (self.K - 1))

    def window(self, w, lr: float, eps: float = 1e-10,
               on_loss=None) -> ScanWindow:
        """Worker `w`'s dispatches at step size `lr`: `add` a batch,
        tick the clock, `flush` at the end of its turn."""
        return ScanWindow(self.srv, self.device_runner(w.shard), self.K,
                          self.args.sync_rounds_per_step, lr, eps, on_loss)

    def walk(self, w, n: int, get, lr: float, eps: float = 1e-10,
             on_loss=None) -> None:
        """Worker `w`'s turn over the batches `get(0..n-1)`, a clock tick
        a batch. Batch `bi` is prepared `--lookahead` batches (a window,
        if that is more) before its turn, the first ones at the start:
        `get(bi)` builds it or hands back one the app kept, its intent
        names its distinct keys from the clock at which it runs, and a
        batch that will be dispatched as a single step has its keys
        uploaded there (`DeviceRoutedRunner.prefetch_keys`), ahead of
        the dispatch and outside its critical section. Then the turn:
        the dispatch, the planner's rounds (`ScanWindow`), the clock."""
        srv, K = self.srv, self.K
        look = max(self.args.lookahead, K)
        runner = self.device_runner(w.shard)
        win = self.window(w, lr, eps, on_loss)
        ready = {}

        def prepare(bi: int, ahead: int) -> None:
            with srv._span("app.prepare", self._h_prepare,
                           work=self._h_prepare_work):
                b = ready[bi] = get(bi)
                fut = w.current_clock + ahead
                self.signal_intent(w, b, fut, fut + 1)
                if b.staged is None and K == 1:
                    b.staged = runner.prefetch_keys(b.roles)

        for bi in range(min(look, n)):
            prepare(bi, ahead=bi)
        for bi in range(n):
            if bi + look < n:
                prepare(bi + look, ahead=look)
            win.add(ready.pop(bi))
            w.advance_clock()
        win.flush()

    # -- the passes ------------------------------------------------------------

    def train_pass(self):
        """One pass over this process's data; what it returns is handed
        to `pass_end`."""
        raise NotImplementedError

    def pass_end(self, out) -> tuple:
        """After `quiesce()`, inside the `app.pass_end` span: the pass's
        (loss, extra text for its log line)."""
        raise NotImplementedError

    def after_pass(self) -> None:
        """After the pass's log line, outside the span (evaluation,
        exports); `self.epoch` still counts the passes before it."""

    def train_passes(self) -> None:
        """`--epochs` passes, each ended by `quiesce()` and the app's
        pass end; stops at the first pass end after `--max_runtime`.
        Leaves the server up."""
        args, srv = self.args, self.srv
        guard = RuntimeGuard(args.max_runtime)
        watch = Stopwatch(start=True)
        for _ in range(args.epochs):
            out = self.train_pass()
            with srv._span("app.pass_end", self._h_pass_end,
                           work=self._h_pass_end_work):
                srv.quiesce()
                loss, extra = self.pass_end(out)
            epoch_report(self.tag, self.epoch, loss, watch, extra)
            self.after_pass()
            self.epoch += 1
            if guard.expired():
                alog(f"[{self.tag}] max_runtime reached")
                break
        alog(f"[{self.tag}]", srv.sync.report())


class RuntimeGuard:
    """max_runtime cutoff (reference apps' --max_runtime). The decision is
    COLLECTIVE in a multi-process run: every rank must leave the epoch
    loop together or the per-epoch barriers deadlock."""

    def __init__(self, max_runtime_s: float):
        self.max = max_runtime_s
        self.watch = Stopwatch(start=True)

    def expired(self) -> bool:
        mine = self.max > 0 and self.watch.elapsed_s > self.max
        from ..parallel import control
        if control.num_processes() == 1:
            return mine
        return bool(control.allreduce(float(mine), "max")[0] > 0)


def is_rank0() -> bool:
    from ..parallel import control
    return control.process_id() == 0


def epoch_report(name: str, epoch: int, loss: float, watch: Stopwatch,
                 extra: str = "") -> None:
    alog(f"[{name}] epoch {epoch}: loss={loss:.6f} "
         f"time={watch.elapsed_s:.2f}s {extra}")
