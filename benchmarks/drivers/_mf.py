"""The MF store as the app builds it (`MfRun`), filled from the seed on
the device; the revealed cells and the probe's three batches from the
seed; the recorder and the probe of a step without a sampled role."""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from common import (Zipf, app_seed, fill_store_from_seed, rng_for, say,
                    table_rows)
from drivers._probe import Probe, StepRecorder
from reference import adagrad_np, mf_np


def _zipfs(cfg: dict):
    """Row and column popularity: Zipf over a FIXED permutation of the
    ids (the same for every --seed; the draws are the seed's). Which ids
    are hot decides where the column-sorted walk's batches of one key
    fall and which slots the hot rows hold, and with them the step's
    device time: permutations drawn from the seed made passes of 1.146 to
    1.154 s on six seeds, most of the cell's spread; fixed, every seed
    reads 1.150-1.151 s (my chip runs, PR 30)."""
    expo = cfg["assumed"]["zipf_exponent"]
    return (Zipf(cfg["num_rows"], expo, rng_for(0, "rowperm")),
            Zipf(cfg["num_cols"], expo, rng_for(0, "colperm")))


def values(cfg: dict, seed: int, rows, cols, rng) -> np.ndarray:
    """<a_i, b_j> of the seeded ground truth (each factor uniform in
    [-1, 1) by the table's hash of seed + 1 and the key) plus noise."""
    k = cfg["truth_rank"]
    a = table_rows(rows, k, k, 1.0, 0.0, seed + 1)
    b = table_rows(cols + cfg["num_rows"], k, k, 1.0, 0.0, seed + 1)
    x = (a * b).sum(-1) + cfg["noise"] * rng.standard_normal(len(rows))
    return x.astype(np.float32)


def draw_points(cfg: dict, seed: int, n: int, stream: str):
    """n revealed cells (rows, cols, vals): row ids Zipf over a fixed
    permutation of the share's rows, column ids Zipf over a fixed
    permutation of the share's columns (`_zipfs`), drawn from the seed."""
    rng = rng_for(seed, stream)
    zr, zc = _zipfs(cfg)
    rows, cols = zr.draw(rng, n), zc.draw(rng, n)
    return rows, cols, values(cfg, seed, rows, cols, rng)


def probe_points(cfg: dict, seed: int) -> list:
    """The probe's three batches: Zipf rows against the hottest column;
    a plain draw; distinct rows against distinct columns."""
    B, m, n = cfg["batch_size"], cfg["num_rows"], cfg["num_cols"]
    rng = rng_for(seed, "probe")
    zr, zc = _zipfs(cfg)
    picks = [(zr.draw(rng, B), np.full(B, zc.perm[0], dtype=np.int64)),
             (zr.draw(rng, B), zc.draw(rng, B)),
             (rng.choice(m, B, replace=False).astype(np.int64),
              rng.choice(n, B, replace=False).astype(np.int64))]
    return [(r, c, values(cfg, seed, r, c, rng)) for r, c in picks]


def build_run(ctx, points):
    """`MfRun(args, data)` over `points`, as `open_run` builds it, with
    the table filled on the device from the seed instead of
    `init_model()`'s host fill."""
    from adapm_tpu.apps import matrix_factorization as mf
    cfg = ctx.cfg
    argv = ["--rank", str(cfg["rank"]),
            "--batch_size", str(cfg["batch_size"]), "--lr", str(cfg["lr"]),
            "--l2", str(cfg["l2"]), "--bold_inc", str(cfg["bold_inc"]),
            "--bold_dec", str(cfg["bold_dec"]),
            "--adagrad_init", str(cfg["adagrad_init"]),
            "--algorithm", cfg["algorithm"],
            "--lookahead", str(cfg["lookahead"]),
            "--num_shards", str(cfg["kv_shards"]),
            "--num_workers", str(cfg["workers"]), "--epochs", "1",
            "--seed", str(app_seed(ctx.seed))] + list(cfg["app_args"])
    for name, value in cfg["sys"].items():
        argv += ["--sys." + name, str(value)]
    args = mf.build_parser().parse_args(argv)
    m, n = cfg["num_rows"], cfg["num_cols"]
    run = mf.MfRun(args, (*points, m, n))
    fill_store_from_seed(run.srv, 0, np.arange(m + n, dtype=np.int64),
                         cfg["rank"], cfg["init_scale"],
                         cfg["adagrad_init"], ctx.seed)
    run.precompile()
    say(f"MfRun: {m} rows + {n} columns, rows of {2 * cfg['rank']} "
        f"{run.srv.stores[0].main.dtype}, main pool "
        f"{run.srv.stores[0].main.shape}")
    return run


def make_rows(ctx):
    """keys -> the seeded rows, numpy: the reference's copy of the table."""
    cfg = ctx.cfg
    return lambda keys: table_rows(keys, 2 * cfg["rank"], cfg["rank"],
                                   cfg["init_scale"], cfg["adagrad_init"],
                                   ctx.seed)


def seeded_sq_sum(ctx, block: int = 16384) -> float:
    """Sum of the squares of the seeded table's factor columns, numpy:
    the untrained part of the reference's L2 term. Blocks of keys on a
    few threads (numpy's loops release the lock)."""
    cfg = ctx.cfg
    total, w = cfg["num_rows"] + cfg["num_cols"], cfg["rank"]

    def part(lo):
        f = table_rows(np.arange(lo, min(lo + block, total)), w, w,
                       cfg["init_scale"], 0.0, ctx.seed)
        return float(np.einsum("ij,ij->", f, f, dtype=np.float64))
    with ThreadPoolExecutor(8) as pool:
        return float(sum(pool.map(part, range(0, total, block))))


class MfStepRecorder(StepRecorder):
    """`StepRecorder` that also keeps what a step without a sampled role
    is handed besides its keys: the observed values."""

    def _wrap(self, fn):
        def recorded(pools, locstat, tables, keys, local_index, alias,
                     rng_key, aux, lr, eps):
            out = fn(pools, locstat, tables, keys, local_index, alias,
                     rng_key, aux, lr, eps)
            self.steps.append({
                "keys": {r: np.asarray(k).astype(np.int64)
                         for r, k in keys.items()},
                "x": np.asarray(aux, dtype=np.float32), "loss": out[2]})
            return out
        return recorded


class MfProbe(Probe):
    """`Probe` for a step whose every row is named by the host, each step
    a pass of its own: besides the four `probe_*` numbers it follows the
    bold driver (the reference steps by ITS step size, from the
    configuration's, whatever the program handed its compiled step) and
    compares each pass-end loss (`loss_pass_gap`)."""

    def __init__(self, cfg, make_rows, seeded_sq):
        m = cfg["num_rows"]
        super().__init__(3, mf_np, None, (), [0], None, cfg["rank"],
                         lambda ks: (ks >= m).astype(np.int64),
                         ["w", "h"], make_rows, cfg["lr"])
        self.cfg, self.m, self.seeded_sq = cfg, m, seeded_sq
        self.pass_losses = []        # the program's, one a probe pass

    def note_step(self, rec: dict, read_rows, pass_loss: float) -> None:
        rec = dict(rec, loss=float(rec["loss"]))
        self.steps.append(rec)
        self.pass_losses.append(float(pass_loss))
        w = self.emb_cols
        if len(self.steps) == 1:
            keys = self._touched(self.steps)
            self.after_first = (keys, read_rows(keys, slice(w, 2 * w)))
        if len(self.steps) == self.n_steps:
            keys = self._touched(self.steps)
            self.after_last = (keys, read_rows(keys, slice(0, w)))

    def _roles(self, rec) -> dict:
        return rec["keys"]

    def follow(self, sink, dtype=np.float32) -> list:
        """The reference's three steps and pass ends from its own seeded
        rows; every row the steps name is held. Returns the step losses;
        `self.ref_pass_losses` are its pass-end losses."""
        cfg, w = self.cfg, self.emb_cols
        cast = (lambda x: x) if dtype == np.float32 else \
            (lambda x: x.astype(dtype).astype(np.float32))
        state = adagrad_np.RowState(2 * w)
        state.ensure(self._touched(self.steps), self.make_rows)
        seeded = state.rows.copy()
        state.rows = cast(state.rows)
        first = np.isin(state.keys, self._touched(self.steps[:1]))
        lr, prev = self.lr, np.inf
        losses, self.ref_pass_losses = [], []
        for i, rec in enumerate(self.steps):
            kw, kh = rec["keys"]["w"], rec["keys"]["h"]
            rw, rh = state.get(kw), state.get(kh)
            loss, g = mf_np.loss_and_grads(rw[:, :w], rh[:, :w], rec["x"],
                                           cfg["l2"], dtype=dtype)
            upd_w = adagrad_np.position_updates(g["w"], rw[:, w:], lr)
            upd_h = adagrad_np.position_updates(g["h"], rh[:, w:], lr)
            state.add(kw, upd_w)
            state.add(kh, upd_h)
            state.rows = cast(state.rows)
            losses.append(loss)
            if i == 0:
                sink.rows("first", state.keys[first], seeded[first, w:],
                          state.rows[first, w:])
            # the pass end: this batch's cells on the rows as they stand
            # (the held rows serve as W and as H, indexed by position),
            # the L2 term over the whole table (the seeded table's sum
            # with the held rows' share exchanged), then the bold driver
            f, f0 = state.rows[:, :w], seeded[:, :w]
            err = mf_np.full_loss(state.index(kw), state.index(kh),
                                  rec["x"], f, f, 0.0)
            sq = self.seeded_sq + float(
                np.einsum("ij,ij->", f, f, dtype=np.float64)
                - np.einsum("ij,ij->", f0, f0, dtype=np.float64))
            pl = err + cfg["l2"] * sq
            self.ref_pass_losses.append(pl)
            lr *= cfg["bold_inc"] if pl <= prev else cfg["bold_dec"]
            prev = pl
        sink.rows("last", state.keys, seeded[:, :w], state.rows[:, :w])
        return losses

    def compare(self, checks, limits: dict, control: str = "") -> None:
        super().compare(checks, limits, control)
        if len(self.steps) != self.n_steps:
            return
        gaps = [abs(p - q) / abs(q) for p, q in
                zip(self.pass_losses, self.ref_pass_losses)]
        checks.add("loss_pass_gap", max(gaps), limits["loss_pass_gap"])
        say("pass-end loss gaps by probe pass: " + ", ".join(
            f"{g:.3g} (reference's {q:.6g})"
            for g, q in zip(gaps, self.ref_pass_losses)))
