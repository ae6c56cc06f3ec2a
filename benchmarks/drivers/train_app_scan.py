"""`train_app`'s cell with `--scan_steps` (the traffic's `scan_steps`)
added to the configuration's `app_args`: the KGE app's own `train(run)`
dispatching K steps at a time as one `lax.scan` program. Window, checks
and close are `train_app`'s; set-up differs in the probe, which is ONE
scan dispatch of K probe batches through the window's own call, recorded
at the scan program's boundary."""
from __future__ import annotations

import numpy as np

from common import read_rows, say
from drivers import _exact_checks, _kge, train_app
from drivers._probe import BLOCK_EXAMPLES, Probe
from reference import adagrad_np, complex_np

window, check, close = train_app.window, train_app.check, train_app.close


class ScanRecorder:
    """Wraps the scan programs of a `DeviceRoutedRunner` (looked up per
    dispatch by `_scan_fn`) as `_probe.StepRecorder` wraps the step's:
    every dispatch gives one entry a scanned step (its keys, its PRNG
    key, its loss). `remove()` puts the runner's own look-up back."""

    def __init__(self, runner):
        self.runner = runner
        self.steps = []
        scan_fn = runner._scan_fn
        runner._scan_fn = lambda **variant: self._wrap(scan_fn(**variant))

    def _wrap(self, fn):
        def recorded(pools, locstat, tables, keys, local_index, alias,
                     rng_keys, aux, lr, eps):
            out = fn(pools, locstat, tables, keys, local_index, alias,
                     rng_keys, aux, lr, eps)
            keys = {r: np.asarray(k).astype(np.int64)
                    for r, k in keys.items()}
            for k in range(len(rng_keys)):
                self.steps.append({
                    "keys": {r: v[k] for r, v in keys.items()},
                    "rng_key": rng_keys[k], "loss": out[2][k]})
            return out
        return recorded

    def remove(self) -> None:
        del self.runner._scan_fn


class ScanProbe(Probe):
    """`Probe` over the steps of ONE scan dispatch: no state between them
    is visible, so the accumulator columns ("first") are read, and
    followed, after the LAST step, of every row touched: the root of the
    sum of g*g over all the steps."""

    def note_step(self, rec: dict, read_rows) -> None:
        super().note_step(rec, lambda ks, cols: None)
        if len(self.steps) == self.n_steps:
            w = self.emb_cols
            keys = self._touched(self.steps)
            self.after_first = (keys, read_rows(keys, slice(w, 2 * w)))
            self.after_last = (keys, read_rows(keys, slice(0, w)))

    def follow(self, sink, dtype=np.float32) -> list:
        """`Probe.follow` with the accumulator columns handed over after
        the last step: a row named once where its block reads it, the
        held rows at the end."""
        w = self.emb_cols
        cast = (lambda x: x) if dtype == np.float32 else \
            (lambda x: x.astype(dtype).astype(np.float32))
        named, times = np.unique(np.concatenate(
            [k.ravel() for rec in self.steps
             for k in self._roles(rec).values()]), return_counts=True)
        state = adagrad_np.RowState(2 * w)
        state.ensure(named[times > 1], self.make_rows)
        seeded = state.rows.copy()
        state.rows = cast(state.rows)
        losses = []
        for rec in self.steps:
            roles = {r: k.reshape(-1) if r != self.neg_role else k
                     for r, k in self._roles(rec).items()}
            B = len(next(iter(rec["keys"].values())))
            before = state.rows.copy()
            loss = 0.0
            for lo in range(0, B, BLOCK_EXAMPLES):
                ks = {r: k[lo:lo + BLOCK_EXAMPLES] for r, k in roles.items()}
                rows, kept = {}, {}
                for r, k in ks.items():
                    rows[r] = cast(self.make_rows(k))
                    kept[r] = np.isin(k, state.keys)
                    rows[r][kept[r]] = before[state.index(k[kept[r]])]
                part, grads = self.model.loss_and_grads(
                    **{r: v[..., :w] for r, v in rows.items()},
                    dtype=dtype, batch_size=B)
                loss += part
                for r, k in ks.items():
                    upd = adagrad_np.position_updates(
                        grads[r], rows[r][..., w:], self.lr)
                    state.add(k[kept[r]], upd[kept[r]])
                    once = ~kept[r]
                    base, after = rows[r][once], cast(rows[r][once]
                                                      + upd[once])
                    sink.rows("first", k[once], base[:, w:], after[:, w:])
                    sink.rows("last", k[once], base[:, :w], after[:, :w])
            losses.append(loss)
            state.rows = cast(state.rows)
        sink.rows("first", state.keys, seeded[:, w:], state.rows[:, w:])
        sink.rows("last", state.keys, seeded[:, :w], state.rows[:, :w])
        return losses


def setup(ctx) -> dict:
    cfg, B = ctx.cfg, ctx.cfg["batch_size"]
    K = ctx.traffic["scan_steps"]
    cfg["app_args"] = list(cfg["app_args"]) + ["--scan_steps", str(K)]
    train = _kge.draw_triples(cfg, ctx.seed, cfg["train_triples"], "train")
    probe_triples = _kge.draw_triples(cfg, ctx.seed, K * B, "probe")
    run = _kge.build_run(ctx, train)
    state = {"run": run, "srv": run.srv}
    make_rows = _kge.make_rows(ctx)
    _exact_checks.table_is_seeded(
        ctx, run.srv, np.arange(run.E + run.R, dtype=np.int64), make_rows,
        ctx.checks)

    # the first K steps of the timed object, through the window's own
    # call: one train(run) pass over K batches of triples is one scan
    # dispatch
    runner = run.device_runner(run.workers[0].shard)
    probe = ScanProbe(K, complex_np, "neg", (B, cfg["neg_ratio"]),
                      run.ekey(np.arange(run.E)), None, run.ent_dim,
                      lambda ks: (ks >= run.E).astype(np.int64),
                      ["entity", "relation"], make_rows, cfg["lr"])
    rec = ScanRecorder(runner)
    try:
        run.ds.train = probe_triples
        train_app._train(ctx, state, 1, 0.0)
        if len(rec.steps) != K:
            raise RuntimeError(f"the probe pass drove {len(rec.steps)} "
                               f"scanned steps, expected {K}")
        for step in rec.steps:
            probe.note_step(step,
                            lambda ks, cols: read_rows(run.srv, ks, cols))
    finally:
        rec.remove()
        run.ds.train = train
    state["probe"] = probe
    say(f"probe: one scan of {K} steps recorded, losses "
        f"{[s['loss'] for s in probe.steps]}")
    # one whole pass as warm-up: every shape of the window
    train_app._train(ctx, state, 1, 0.0)
    return state
