"""The training cells' comparison with the plain reference.

Set-up drives the timed object (the cell's own `DeviceRoutedRunner` and
its compiled step, through the window's own call) from the seeded table
through its first steps (the traffic's `probe_steps`), with a recorder at the boundary of the
compiled step: what went in (the batch's keys, the PRNG key the step draws
its negatives from) and what came out (the loss). The
rows those steps touched are read back from the store. After the window
the reference follows the same steps in numpy from its own copy of
the seeded rows, and the numbers below are compared.

The one thing the reference mirrors from the program is how a step turns
its PRNG key into negatives (`negatives` below: uniform positions into the
sorted population, or a Vose alias draw snapped to it), because the
compiled step draws them itself and hands them to nobody. PERF.md lists
that under Open questions.
"""
from __future__ import annotations

import numpy as np

from common import say
from reference import adagrad_np

BLOCK_EXAMPLES = 128     # examples of a step the reference works at a time
BLOCK_ROWS = 512         # rows the comparison works in float64 at a time


class StepRecorder:
    """Wraps the two compiled step variants of a `DeviceRoutedRunner`
    while the probe steps run; `remove()` puts the originals back, so the
    window drives the same compiled programs with nothing in between."""

    def __init__(self, runner):
        self.runner = runner
        self.steps = []
        self._orig = {}
        for name in ("step_fn", "_step_fn_norep"):
            fn = getattr(runner, name)
            self._orig[name] = fn
            setattr(runner, name, self._wrap(fn))

    def _wrap(self, fn):
        def recorded(pools, locstat, tables, keys, local_index, alias,
                     rng_key, aux, lr, eps):
            out = fn(pools, locstat, tables, keys, local_index, alias,
                     rng_key, aux, lr, eps)
            self.steps.append({
                "keys": {r: np.asarray(k).astype(np.int64)
                         for r, k in keys.items()},
                "rng_key": rng_key, "loss": out[2]})
            return out
        return recorded

    def remove(self) -> None:
        for name, fn in self._orig.items():
            setattr(self.runner, name, fn)


def negatives(rng_key, shape, population: np.ndarray, alias=None):
    """The negatives a step draws from `rng_key`: the Local sampling
    scheme over a population that is all resident on this shard.
    `population` is sorted; `alias` is (prob, alias, key_table) or None."""
    import jax
    import jax.numpy as jnp
    count = jnp.int32(len(population))
    if alias is None:
        pos = jax.random.randint(rng_key, shape, 0, count)
        return population[np.asarray(pos)]
    prob, alias_t, key_table = alias
    k1, k2 = jax.random.split(rng_key)
    u = np.asarray(jax.random.randint(k1, shape, 0, len(prob)))
    v = np.asarray(jax.random.uniform(k2, shape))
    cand = key_table[np.where(v < prob[u], u, alias_t[u])]
    pos = np.searchsorted(population, cand)
    pos = np.where(pos >= len(population), 0, pos)
    return population[pos]


class Probe:
    """Readings of the program's first steps, then the comparison.

    steps       how many first steps are followed (the traffic's
                `probe_steps`: three, or two where three steps' rows make
                the reference longer than the window)
    model       module with loss_and_grads(**role rows) (reference/)
    neg_role    the role the step samples itself
    emb_cols    embedding width of a row (the rest is the accumulator)
    leaf_of     keys -> small ints naming the parameter leaf of each key
    leaf_names  names of those leaves
    make_rows   keys -> the seeded rows [n, row_len] (numpy, the reference's
                own copy of the table)
    lr          the configuration's learning rate: the reference steps by
                it, whatever the program handed its compiled step

    Of the program's rows only what is compared is kept until after the
    window: the accumulator columns after the first step (the first
    gradient as the optimizer got it) and the embedding columns after the
    last (the parameters' change). At 8 KB a row a step touches over a
    gigabyte of rows, and fresh host memory costs about a second a
    gigabyte on the chip's host, so both sides work in blocks.
    """

    def __init__(self, steps, model, neg_role, neg_shape, population, alias,
                 emb_cols, leaf_of, leaf_names, make_rows, lr):
        self.n_steps = int(steps)
        self.lr = float(lr)
        self.model = model
        self.neg_role = neg_role
        self.neg_shape = tuple(neg_shape)
        self.population = np.unique(np.asarray(population, dtype=np.int64))
        self.alias = alias
        self.emb_cols = emb_cols
        self.leaf_of = leaf_of
        self.leaf_names = leaf_names
        self.make_rows = make_rows
        self.steps = []          # recorder entries + "neg"
        self.after_first = None  # (keys, accumulator columns) after step 1
        self.after_last = None   # (keys, embedding columns) after the last

    # -- set-up side: program readings --------------------------------------

    def note_step(self, rec: dict, read_rows) -> None:
        """Called once per probe step, right after it: works out the
        negatives, and reads back what is compared of the touched rows
        after the first and the last step (`read_rows(keys, cols)`)."""
        rec = dict(rec)
        rec["neg"] = negatives(rec["rng_key"], self.neg_shape,
                               self.population, self.alias)
        rec["loss"] = float(rec["loss"])
        self.steps.append(rec)
        w = self.emb_cols
        if len(self.steps) == 1:
            keys = self._touched(self.steps)
            self.after_first = (keys, read_rows(keys, slice(w, 2 * w)))
        if len(self.steps) == self.n_steps:
            keys = self._touched(self.steps)
            self.after_last = (keys, read_rows(keys, slice(0, w)))

    def _roles(self, rec) -> dict:
        roles = dict(rec["keys"])
        roles[self.neg_role] = rec["neg"]
        return roles

    def _touched(self, steps) -> np.ndarray:
        return np.unique(np.concatenate(
            [k.ravel() for rec in steps for k in self._roles(rec).values()]))

    # -- after the window: the reference follows ----------------------------

    def follow(self, sink, dtype=np.float32) -> list:
        """The reference's steps from its own seeded rows; returns the
        losses, and hands `sink.rows(which, keys, base, after)` the
        columns that are compared, before and after: of every row touched
        in the first step its accumulator columns after that step
        (`which` "first"), of every row touched at all its embedding
        columns after the last ("last"). `dtype` other than float32 is
        the lower-precision control: rows are held, and the loss and its
        gradients computed, in that type.

        A step is worked through in blocks of examples, and no table is
        held: a row that the steps name once is made from the seed where
        its block reads it, pushed to, handed to the sink and dropped (it
        was never pushed to before, and will not be again). Only the rows
        that the steps name more than once are kept in a state, and a
        block reads those as they were before the step."""
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
        for i, rec in enumerate(self.steps):
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
                    if i == 0:
                        sink.rows("first", k[once], base[:, w:],
                                  after[:, w:])
                    sink.rows("last", k[once], base[:, :w], after[:, :w])
            losses.append(loss)
            state.rows = cast(state.rows)
            if i == 0:
                first = np.isin(state.keys, self._touched(self.steps[:1]))
                sink.rows("first", state.keys[first], seeded[first, w:],
                          state.rows[first, w:])
        sink.rows("last", state.keys, seeded[:, :w], state.rows[:, :w])
        return losses

    def compare(self, checks, limits: dict, control: str = "") -> None:
        if len(self.steps) != self.n_steps:
            checks.add("probe_steps_recorded", len(self.steps), self.n_steps,
                       ok=False)
            return
        program = {"first": self.after_first, "last": self.after_last}
        prog_losses = [rec["loss"] for rec in self.steps]
        if control == "ref-bf16":
            # the control: the reference in the program's place, computed
            # in bfloat16
            import ml_dtypes
            program = _Keep(program)
            prog_losses = self.follow(program, ml_dtypes.bfloat16)
            program = program.kept
        sums = _LeafSums(program, self.leaf_of, len(self.leaf_names))
        losses = self.follow(sums)
        loss_gap = max(abs(p - q) / abs(q)
                       for p, q in zip(prog_losses, losses))
        checks.add("probe_loss_gap", loss_gap, limits["probe_loss_gap"])
        checks.add("probe_grad_norm_gap", sums.worst("first"),
                   limits["probe_grad_norm_gap"])
        checks.add("probe_update_norm_gap", sums.worst("last"),
                   limits["probe_update_norm_gap"])
        checks.add("probe_update_diff_share", sums.worst("last", diff=True),
                   limits["probe_update_diff_share"])
        # which leaf read the worst gap of each, for the log: a seed that
        # reads high says where
        say("worst leaf: " + "; ".join(
            sums.worst_leaf_text(what, which, self.leaf_names)
            for what, which in (("first gradient", "first"),
                                ("update", "last"))))


class _Keep:
    """A sink that keeps the reference's rows where the program's would
    be: the lower-precision control in the program's place."""

    def __init__(self, program: dict):
        self.kept = {which: (keys, np.zeros_like(rows))
                     for which, (keys, rows) in program.items()}

    def rows(self, which, keys, base, after) -> None:
        all_keys, out = self.kept[which]
        out[np.searchsorted(all_keys, keys)] = after


class _LeafSums:
    """A sink that compares, per parameter leaf and in float64. With p the
    program's change of a row from its seeded value and q the
    reference's: of the embedding columns ("last") the sums over the
    leaf's rows of |p|^2, |q|^2 and |p-q|^2; of the accumulator columns
    ("first"), whose change is a sum of g*g, the sums of p and of q
    themselves, so that their roots are the first gradient's norms."""

    def __init__(self, program: dict, leaf_of, n_leaf: int):
        self.program, self.leaf_of = program, leaf_of
        self.sums = {which: np.zeros((3, n_leaf)) for which in program}
        self.seen = {which: np.zeros(n_leaf, dtype=bool)
                     for which in program}

    def rows(self, which, keys, base, after) -> None:
        all_keys, prog = self.program[which]
        for lo in range(0, len(keys), BLOCK_ROWS):
            sl = slice(lo, lo + BLOCK_ROWS)
            b = base[sl]
            p = np.subtract(prog[np.searchsorted(all_keys, keys[sl])], b,
                            dtype=np.float64)
            q = np.subtract(after[sl], b, dtype=np.float64)
            if which == "first":
                # a lower-precision store can round an accumulator down
                per_row = [np.maximum(p, 0.0).sum(1),
                           np.maximum(q, 0.0).sum(1), np.zeros(len(p))]
            else:
                per_row = [np.einsum("ij,ij->i", p, p),
                           np.einsum("ij,ij->i", q, q)]
                p -= q
                per_row.append(np.einsum("ij,ij->i", p, p))
            leaf = self.leaf_of(keys[sl])
            for li in np.unique(leaf):
                self.seen[which][li] = True
                self.sums[which][:, li] += [x[leaf == li].sum()
                                            for x in per_row]

    def worst(self, which: str, diff: bool = False) -> float:
        """Over the leaves, the largest gap between the program's and the
        reference's norm of the rows' change (with `diff` the norm of the
        two changes' difference), over the reference's norm of that leaf
        or of the median leaf, whichever is larger."""
        return self.worst_leaf(which, diff)[0]

    def worst_leaf(self, which: str, diff: bool = False):
        """(`worst`'s value, the leaf that reads it, the reference's norm
        of that leaf, of the median leaf)."""
        pp, qq, dd = np.sqrt(self.sums[which][:, self.seen[which]])
        gaps = dd if diff else np.abs(pp - qq)
        med = float(np.median(qq))
        shares = [g / max(r, med) for g, r in zip(gaps, qq)]
        i = int(np.argmax(shares))
        return (shares[i], int(np.nonzero(self.seen[which])[0][i]),
                float(qq[i]), med)

    def worst_leaf_text(self, what: str, which: str, names) -> str:
        gap, leaf, norm, med = self.worst_leaf(which)
        return (f"{what} {names[leaf]} {gap:.3g} (reference's norm "
                f"{norm:.3g}, median leaf's {med:.3g})")
