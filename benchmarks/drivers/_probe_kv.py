"""The probe of a cell with several kv shards: what `_probe.py` does, and
the two things more that several shards need.

A worker draws its negatives from the keys resident on its OWN shard, and
which keys those are is the planner's doing, so the recorder keeps each
step's local index (an operand of the compiled step) and the reference
draws from that.

And a second probe runs AFTER the window, from the live table: hundreds
of thousands of keys relocated, thousands replicated, the device's route
mirrors rebuilt a hundred times. Its reference cannot start from the
seed, so the recorder reads the rows a step is about to touch, main
copies through `Server.read_main`, at the compiled step's boundary:
there the step's keys, its PRNG key and its local index are known, the
dispatch holds the server lock (reentrant, as the dispatch gate is), and
the table is quiesced, so the main copy is what every holder reads."""
from __future__ import annotations

import numpy as np

from drivers._probe import Probe, StepRecorder, negatives


class StepRecorderKv(StepRecorder):
    """`before(keys, rng_key, local)`, if given, is called with a step's
    inputs before the compiled step runs."""

    def __init__(self, runner, before=None):
        self.before = before
        super().__init__(runner)

    def _wrap(self, fn):
        recorded = super()._wrap(fn)

        def with_local_index(pools, locstat, tables, keys, local_index,
                             alias, rng_key, *rest):
            idx, count = local_index
            local = np.asarray(idx)[:int(count)].astype(np.int64)
            if self.before is not None:
                self.before({r: np.asarray(k).astype(np.int64)
                             for r, k in keys.items()}, rng_key, local)
            out = recorded(pools, locstat, tables, keys, local_index,
                           alias, rng_key, *rest)
            self.steps[-1]["local"] = local
            return out
        return with_local_index


class ProbeKv(Probe):
    def note_step(self, rec: dict, read_rows) -> None:
        rec = dict(rec)
        # sorted, as `negatives` wants it: the runner builds it from the
        # sorted population
        self.population = rec.pop("local")
        super().note_step(rec, read_rows)


class LiveRows:
    """keys -> rows as they stood before the probe's steps touched them:
    the reference's copy of a table that no seed can reproduce. `note` is
    the recorder's `before`: it reads the rows of the keys a step names
    that no earlier step of the probe named (those it has already, from
    before they were written)."""

    def __init__(self, read_rows, neg_shape):
        self.read_rows, self.neg_shape = read_rows, tuple(neg_shape)
        self.keys = np.empty(0, dtype=np.int64)
        self.rows = None

    def note(self, keys: dict, rng_key, local: np.ndarray) -> None:
        named = np.unique(np.concatenate(
            [k.ravel() for k in keys.values()]
            + [negatives(rng_key, self.neg_shape, local).ravel()]))
        new = named[~np.isin(named, self.keys)]
        if not len(new):
            return
        rows = self.read_rows(new)
        if self.rows is not None:
            new = np.concatenate([self.keys, new])
            rows = np.concatenate([self.rows, rows])
        order = np.argsort(new)
        self.keys, self.rows = new[order], rows[order]

    def __call__(self, keys) -> np.ndarray:
        keys = np.asarray(keys, dtype=np.int64)
        pos = np.searchsorted(self.keys, keys.ravel())
        return self.rows[pos].reshape(keys.shape + self.rows.shape[1:])


class Named:
    """`checks` with every name prefixed: a second probe's four numbers
    beside the first's."""

    def __init__(self, checks, prefix: str):
        self.checks, self.prefix = checks, prefix

    def add(self, name, *a, **kw):
        return self.checks.add(self.prefix + name, *a, **kw)
