"""Training through the CTR app's own loop on several kv shards with
several workers: `apps.ctr.train(run)`, one call a pass, every pass a
FRESH seeded draw of examples (`train_app_kv`'s shape over `_ctr.py`'s app
and probes). Every worker names every dense key in every batch, so the
planner holds the dense class as replicas on every shard that does not
own it, and the Zipf head of the tables beside it: replication, not
relocation, carries most positions of a step. The harness owns no copy
of the loop: it builds the run the way `open_run` does, from flags alone,
and hands `train()` the next draw.

`correct` is decided by four comparisons. (a) worker 0 ALONE, two steps
from the seeded table, both classes, against `reference/dlrm_np.py`
(`probe_*`); (b) one more step, of worker 1 alone, after worker 0's and
a `quiesce()`: worker 1 reads worker 0's updates through its own
replicas, and the reference follows all three steps in order from the
seeded rows (`turn_probe_*`); (c) after the window one step of worker 0
alone from the table as the window left it, the reference started from
the rows as they stand (`live_probe_*`); (d) the exact checks of a cell
with several shards over BOTH classes, and every dense key read back
equal to main by its four holders. CTR samples no role, so a step of
any worker has a sequential reference while its replicas equal main."""
from __future__ import annotations

import gc
import sys

import numpy as np

from common import read_rows, rng_for, say
from drivers import _ctr, _exact_checks_kv
from drivers._probe_kv import Named
from drivers.train_app_ctr import _train


def _caps_replica_pools() -> bool:
    """Whether this checkout's store caps a class's replica pools at the
    class's key count: a tiny server of two classes tells."""
    import adapm_tpu
    from adapm_tpu.config import SystemOptions
    srv = adapm_tpu.setup(
        64, np.repeat([4, 8], [56, 8]),
        opts=SystemOptions(cache_slots_per_shard=32, sync_max_per_sec=0,
                           prefetch=False))
    try:
        return srv.stores[1].cache_slots <= 8
    finally:
        srv.shutdown()


def _pass(ctx, state):
    """train(run) over the next fresh draw; (t0, t1, the pass's loss)."""
    i = state["next_draw"]
    if i >= len(state["draws"]):
        raise RuntimeError(
            f"the window outran the {len(state['draws'])} passes drawn at "
            f"set-up: raise the traffic's passes_drawn")
    state["next_draw"] = i + 1
    state["run"].set_examples(*state["draws"][i])
    return _train(ctx, state, 1, 0.0)


def _runners(run):
    return [run.device_runner(w.shard) for w in run.workers]


def _drain(run) -> None:
    """Fold the runners' device-side counts into the program's counters
    (`fused.rows_total`, `fused.replica_positions`, ...): they move only
    at a drain, and a window is shorter than the drain interval."""
    for r in _runners(run):
        r.locality_counts()


def _sized_by(run) -> dict:
    """The program's counts of what the replica pools and the main
    pools' over-allocation were too small for (relocations demoted,
    replica creations truncated: counters) and of the replicas alive,
    each total and by length class."""
    obs = run.srv.obs
    return {name: obs.find(name).snap() for name in sorted(obs.names())
            if name.startswith(("sync.relocations_demoted_total",
                                "sync.replicas_truncated_total",
                                "sync.replicas_live.len"))}


def _all_want_the_dense_keys(run) -> None:
    """What a pass of all workers leaves behind for a worker that then
    steps alone: every worker's intent on the dense keys, which each of
    its batches names, alive at its clock; worker 0's comes last. The
    first to ask takes the main copies its shard's pool has room for,
    every other dense key stays where it was seeded and is replicated to
    all who asked. (A lone first step would otherwise run before the
    round that acts on its intent, on no replica at all.)"""
    for w in run.workers[1:] + run.workers[:1]:
        w.intent(run.dense_keys, w.current_clock, w.current_clock + 1)
        run.srv.wait_sync()


class _Recorder(_ctr.CtrStepRecorder):
    """`before(keys)`, if given, is called with a step's keys before the
    compiled step runs (`_probe_kv.StepRecorderKv`, for a step that
    samples nothing)."""

    def __init__(self, runner, before=None):
        self.before = before
        super().__init__(runner)

    def _wrap(self, fn):
        recorded = super()._wrap(fn)

        def with_before(pools, locstat, tables, keys, *rest):
            if self.before is not None:
                self.before({r: np.asarray(k).astype(np.int64)
                             for r, k in keys.items()})
            return recorded(pools, locstat, tables, keys, *rest)
        return with_before


class _LiveRows:
    """keys -> rows as they stood before the probe's steps touched them,
    per class: the reference's copy of a table that no seed can
    reproduce (`_probe_kv.LiveRows`, over two row lengths). `note` is
    the recorder's `before`: at the compiled step's boundary the
    dispatch holds the server lock and the table is quiesced, so the
    main copy is what every holder reads."""

    def __init__(self, srv):
        self.srv, self.kept = srv, {}

    def note(self, keys: dict) -> None:
        for cls, ks in keys.items():
            ks = np.unique(ks)
            have = self.kept.get(cls)
            new = ks if have is None else ks[~np.isin(ks, have[0])]
            if not len(new):
                continue
            rows = read_rows(self.srv, new)
            if have is not None:
                new = np.concatenate([have[0], new])
                rows = np.concatenate([have[1], rows])
            order = np.argsort(new)
            self.kept[cls] = new[order], rows[order]

    def rows_of(self, cls: str):
        def rows(keys):
            keys = np.asarray(keys, dtype=np.int64)
            ks, rs = self.kept[cls]
            return rs[np.searchsorted(ks, keys.ravel())].reshape(
                keys.shape + rs.shape[1:])
        return rows


def _steps_alone(ctx, state, turns, probes, before=None) -> None:
    """One step of ONE worker for each `(worker index, batch)` of
    `turns`, each one pass of the window's own call (it ends in
    `quiesce()`), recorded at the compiled step's boundary; every probe
    of `probes` that still lacks steps notes it. train() is handed a
    one-worker view."""
    run = state["run"]
    workers = run.workers
    recs = {wi: _Recorder(run.device_runner(workers[wi].shard), before)
            for wi in {wi for wi, _ in turns}}
    try:
        for wi, batch in turns:
            run.workers, run.num_workers = [workers[wi]], 1
            run.set_examples(*batch)
            seen = len(recs[wi].steps)
            _train(ctx, state, 1, 0.0)
            if len(recs[wi].steps) != seen + 1:
                raise RuntimeError(
                    f"a probe pass drove {len(recs[wi].steps) - seen} "
                    f"steps of worker {wi}'s runner, expected 1")
            for p in probes:
                if len(p.steps) < p.n_steps:
                    p.note_step(recs[wi].steps[-1],
                                lambda ks, cols: read_rows(run.srv, ks,
                                                           cols))
    finally:
        for rec in recs.values():
            rec.remove()
        run.workers, run.num_workers = workers, len(workers)
    state["first_recorder"] = state.get("first_recorder") or \
        next(iter(recs.values()))


def _matmul_ops(rec) -> list:
    """`_ctr.matmul_ops` for a step over pools of several devices: the
    operands the recorder saw that lay on ONE device were placed there
    by nobody (the PRNG key, the scalars) and are lowered as such."""
    import jax
    fn, operands = rec.called
    operands = jax.tree.map(
        lambda a: a if len(a.sharding.device_set) > 1
        else jax.ShapeDtypeStruct(a.shape, a.dtype), operands)
    return _ctr.matmul_ops_of(fn.lower(*operands).compile().as_text())


def _live_batch(ctx, run, owner0, checks):
    """The live probe's batch: a plain draw whose members in the five
    largest tables are ids whose main copy has MOVED since set-up (the
    first half of each bag) and ids that some shard holds a REPLICA of
    (the second half); the dense keys, which every step names, are on
    replicas by then."""
    cfg, B = ctx.cfg, ctx.cfg["batch_size"]
    ab, rng = run.srv.ab, rng_for(ctx.seed, "liveprobe")
    members, x, y = _ctr.draw_examples(cfg, ctx.seed, B, "liveprobe")
    at = np.concatenate([[0], np.cumsum(cfg["multi_hot_sizes"])])
    big = max(cfg["source_table_rows"])
    n_moved = n_held = 0
    for f, src in enumerate(cfg["source_table_rows"]):
        if src != big:
            continue
        lo, hi = (int(v) for v in run.table_first[f:f + 2])
        ks = np.arange(lo, hi)
        moved = np.nonzero(ab.owner[ks] != owner0[ks])[0]
        held = np.nonzero((ab.cache_slot[:, lo:hi] >= 0).any(axis=0))[0]
        half = at[f] + (at[f + 1] - at[f] + 1) // 2
        for ids, cols in ((moved, slice(at[f], half)),
                          (held, slice(half, at[f + 1]))):
            if len(ids) and cols.stop > cols.start:
                members[:, cols] = rng.choice(
                    ids, (B, cols.stop - cols.start))
        n_moved, n_held = n_moved + len(moved), n_held + len(held)
    checks.add("live_probe_keys_moved", n_moved, "> 0", ok=n_moved > 0)
    checks.add("live_probe_keys_replicated", n_held, "> 0", ok=n_held > 0)
    return members, x, y


def setup(ctx) -> dict:
    try:
        from adapm_tpu.apps import ctr  # noqa: F401
    except ImportError:
        print("train_app_ctr_kv: this checkout has no adapm_tpu.apps.ctr; "
              "the cell cannot run on it", file=sys.stderr)
        raise SystemExit(2)
    if not _caps_replica_pools():
        # one --sys.cache_slots_per_shard sizes every class: the dense
        # class of 15,676 keys would get the feature class's slots,
        # 4.3 GB a chip that nothing can fill, and the step no room
        print("train_app_ctr_kv: this checkout sizes every class's "
              "replica pools by the one option, not by the class's key "
              "count; the cell cannot run on it", file=sys.stderr)
        raise SystemExit(2)
    cfg, n = ctx.cfg, ctx.cfg["examples_per_pass"]
    drawn = _ctr.draw_examples(cfg, ctx.seed,
                               ctx.traffic["passes_drawn"] * n, "train")
    draws = [tuple(a[lo:lo + n] for a in drawn)
             for lo in range(0, len(drawn[0]), n)]
    run = _ctr.build_run(ctx, draws[0])
    state = {"run": run, "srv": run.srv, "next_draw": 0, "draws": draws,
             "owner0": run.srv.ab.owner.copy()}
    rows_of_class = _ctr.make_rows(ctx)
    _ctr.table_is_seeded(ctx, run, rows_of_class, ctx.checks)

    # (a) and (b): worker 0 alone for two steps, then worker 1 alone for
    # one, from the quiesced seeded table, through the window's own call
    # with intents and planner rounds live
    _all_want_the_dense_keys(run)
    batches = _ctr.probe_examples(cfg, ctx.seed) \
        + [_ctr.draw_examples(cfg, ctx.seed, cfg["batch_size"], "probe3")]
    alone = _ctr.CtrProbe(cfg, 2, rows_of_class)
    turn = _ctr.CtrProbe(cfg, 3, rows_of_class)
    _drain(run)
    held0 = int(run.srv.obs.find("fused.replica_positions").snap())
    _steps_alone(ctx, state, list(zip((0, 0, 1), batches)), [alone, turn])
    _drain(run)
    held = int(run.srv.obs.find("fused.replica_positions").snap()) - held0
    # the probes mean what they say only if they went through replicas:
    # more than half of the three steps' dense positions
    ctx.checks.add("probe_replica_positions", held,
                   f"> {3 * run.n_dense // 2}",
                   ok=held > 3 * run.n_dense // 2)
    state["probe"], state["turn_probe"] = alone, turn
    # a traced run names the step's matrix products for its reader
    state["matmul_ops"] = _matmul_ops(state["first_recorder"]) \
        if ctx.trace else None
    say(f"probes: losses {[s['loss'] for s in turn.steps]}, {held} "
        f"replica positions")
    # warm-up passes of fresh draws, all workers: the head's replicas
    # exist and every shape of the window has run
    for _ in range(ctx.traffic["warmup_passes"]):
        _pass(ctx, state)
    _drain(run)
    gc.collect()
    gc.freeze()
    return state


def window(ctx, state) -> dict:
    run = state["run"]
    stats = run.srv.sync.stats
    steps0 = sum(r.steps for r in _runners(run))
    reloc0, sized0 = stats.relocations, _sized_by(run)
    losses, t0, t1 = [], None, None
    while t1 is None or t1 - t0 < ctx.seconds:
        a, t1, loss = _pass(ctx, state)
        t0 = a if t0 is None else t0
        losses += loss
    steps = sum(r.steps for r in _runners(run)) - steps0
    live = sum(len(t) for t in run.srv.sync.replicas)
    _drain(run)
    rate = len(losses) * ctx.cfg["examples_per_pass"] / (t1 - t0)
    say(f"window: {len(losses)} passes, {steps} steps in {t1 - t0:.3f} s "
        f"({(t1 - t0) / max(steps, 1) * 1e3:.3f} ms/step); "
        f"{stats.relocations - reloc0} relocations, {live} replicas live")
    # what the configuration's sizing rule reads, in every run's log
    sized = _sized_by(run)
    say("in the window: " + ", ".join(
        f"{name} +{n - sized0[name]}" if "_total" in name
        else f"{name} {n}" for name, n in sized.items()))
    return {"attempted": steps, "failed": 0, "steps": steps,
            "t0": t0, "t1": t1, "losses": losses,
            "relocations": stats.relocations - reloc0,
            "replicas_live": live, "matmul_ops": state["matmul_ops"],
            "metrics": {"train_examples_per_s": rate}}


def check(ctx, state, out, checks) -> None:
    run, srv = state["run"], state["srv"]
    checks.add("passes_finished", len(out["losses"]), 1,
               ok=len(out["losses"]) >= 1)
    # (c) one step of worker 0 alone from the table as the window left it
    srv.quiesce()
    live_rows = _LiveRows(srv)
    live = _ctr.CtrProbe(
        ctx.cfg, ctx.traffic["live_probe_steps"],
        tuple(live_rows.rows_of(cls) for cls in _ctr.CLASSES))
    _drain(run)
    held0 = int(srv.obs.find("fused.replica_positions").snap())
    _steps_alone(ctx, state,
                 [(0, _live_batch(ctx, run, state["owner0"], checks))],
                 [live], before=live_rows.note)
    _drain(run)
    held = int(srv.obs.find("fused.replica_positions").snap()) - held0
    checks.add("live_probe_replica_positions", held,
               f"> {run.n_dense // 2}", ok=held > run.n_dense // 2)
    # (d) the exact checks, over each class, and the dense class whole
    feat = np.arange(run.n_feat, dtype=np.int64)
    _exact_checks_kv.after_window(ctx, srv, run.workers, feat,
                                  state["owner0"], out, checks)
    _exact_checks_kv.after_window(ctx, srv, run.workers, run.dense_keys,
                                  state["owner0"], out,
                                  Named(checks, "dense_"))
    srv.quiesce()
    main = np.asarray(srv.read_main(run.dense_keys)).reshape(
        run.n_dense, -1)
    holders = (srv.ab.cache_slot[:, run.dense_keys] >= 0).sum(axis=0)
    bad = sum(int((np.asarray(w.pull_sync(run.dense_keys)).reshape(
        main.shape) != main).any(axis=1).sum()) for w in run.workers)
    checks.add("dense_rows_differ_between_holders", bad, 0)
    checks.add("dense_keys_on_3_replicas", int((holders >= 3).sum()),
               f">= {run.n_dense // 2}",
               ok=int((holders >= 3).sum()) >= run.n_dense // 2)
    limits = ctx.traffic["probe_limits"]
    state["probe"].compare(checks, limits, ctx.control)
    state["turn_probe"].compare(Named(checks, "turn_"), limits, ctx.control)
    lived = live.compare(Named(checks, "live_"),
                         ctx.traffic["live_probe_limits"], ctx.control)
    if lived:
        # the live loss gap is relative to a TRAINED loss: say it and the
        # passes it was trained for beside the reading
        say(f"live probe after {len(out['losses'])} passes in the window: "
            f"loss {lived[0][0]!r} against the reference's "
            f"{lived[1][0]!r}")


def close(ctx, state) -> None:
    state["run"].srv.shutdown()
