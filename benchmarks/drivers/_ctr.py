"""The DLRM store as the app builds it (`CtrRun`), both pools filled from
the seed on the device; the examples and the probe's two batches from the
seed; the recorder and the probe of a step over two length classes, one
of which (the dense network's rows) the loss multiplies."""
from __future__ import annotations

import re

import numpy as np

from common import (Zipf, app_seed, fill_store_from_seed, rng_for, say,
                    table_rows)
from drivers._probe import StepRecorder, _Keep, _LeafSums
from reference import adagrad_np, dlrm_np


def spec(cfg: dict) -> dict:
    """The reference's view of the network: its tensors in network order
    and where each sits among the dense rows."""
    tens = dlrm_np.tensors(
        cfg["dense_features"], cfg["embedding_dim"],
        len(cfg["multi_hot_sizes"]), cfg["dense_arch_layer_sizes"],
        cfg["over_arch_layer_sizes"], cfg["dcn_num_layers"],
        cfg["dcn_low_rank_dim"])
    where, n_rows = dlrm_np.rows_of(tens, cfg["dense_row"])
    n_feat = int(np.sum(cfg["table_rows"]))
    scale = np.empty(n_rows, dtype=np.float32)
    for name, _, fan_in in tens:
        at, n = where[name]
        scale[at:at + n] = 1.0 / np.sqrt(fan_in)
    return {"tensors": tens, "where": where, "n_dense": n_rows,
            "n_feat": n_feat, "row_scale": scale,
            "table_first": np.concatenate(
                [[0], np.cumsum(cfg["table_rows"])]).astype(np.int64)}


def _zipfs(cfg: dict) -> list:
    """Per table the popularity of its held rows: Zipf over a FIXED
    permutation of the ids (the same for every --seed; the draws are the
    seed's): which ids are hot decides which slots the hot rows hold and
    with them the step's device time (`_mf._zipfs`, PR 30)."""
    expo = cfg["assumed"]["zipf_exponent"]
    return [Zipf(rows, expo, rng_for(0, f"tbl{f}"))
            for f, rows in enumerate(cfg["table_rows"])]


def _dense_and_labels(cfg: dict, seed: int, n: int, rng):
    """Dense features N(0, 1) and labels Bernoulli of a logistic ground
    truth over them (weights N(0, 1/13) from the seed) whose offset puts
    the click rate near the configuration's."""
    nd, rate = cfg["dense_features"], cfg["click_rate"]
    x = rng.standard_normal((n, nd)).astype(np.float32)
    w = rng_for(seed, "truth").standard_normal(nd) / np.sqrt(nd)
    # E sigmoid(N(m, 1)) ~ sigmoid(m / sqrt(1 + pi / 8))
    offset = np.log(rate / (1.0 - rate)) * np.sqrt(1.0 + np.pi / 8.0)
    y = rng.random(n) < 1.0 / (1.0 + np.exp(-(x @ w + offset)))
    return x, y.astype(np.float32)


def draw_examples(cfg: dict, seed: int, n: int, stream: str):
    """n examples (members [n, M] table-local ids, dense features, labels):
    per table Zipf ids over the share's rows, a bag's members drawn
    independently."""
    rng = rng_for(seed, stream)
    members = np.concatenate(
        [z.draw(rng, (n, hot)) for z, hot in
         zip(_zipfs(cfg), cfg["multi_hot_sizes"])], axis=1)
    return (members, *_dense_and_labels(cfg, seed, n, rng))


def probe_examples(cfg: dict, seed: int) -> list:
    """The probe's two batches: a plain draw (bags repeat members, the
    one-row tables are named B times); the same with DISTINCT members in
    the largest tables (the five capped at 40M rows in the source)."""
    B = cfg["batch_size"]
    rng = rng_for(seed, "probe")
    plain = draw_examples(cfg, seed, B, "probe1")
    members = draw_examples(cfg, seed, B, "probe2")[0]
    big = max(cfg["source_table_rows"])
    at = np.concatenate([[0], np.cumsum(cfg["multi_hot_sizes"])])
    for f, src in enumerate(cfg["source_table_rows"]):
        if src == big:
            hot = cfg["multi_hot_sizes"][f]
            members[:, at[f]:at[f + 1]] = rng.choice(
                cfg["table_rows"][f], B * hot, replace=False).reshape(B, hot)
    return [plain, (members, *_dense_and_labels(cfg, seed, B, rng))]


def fill_dense_from_seed(srv, cid: int, keys: np.ndarray,
                         row_scale: np.ndarray, acc_init: float,
                         seed: int) -> None:
    """The dense class's main pool on the device: the row of the key in
    each slot is `table_rows` at scale 0.5 (the hash's unit draw minus a
    half, exactly) times the bound of the key's tensor, one more exactly
    rounded float32 product, so numpy (`make_rows`) agrees bitwise."""
    import jax
    import jax.numpy as jnp
    store = srv.stores[cid]
    S, M, L = store.main.shape
    slot_key = np.full((S, M), -1, dtype=np.int32)
    slot_scale = np.zeros((S, M), dtype=np.float32)
    slot_key[srv.ab.owner[keys], srv.ab.slot[keys]] = keys
    slot_scale[srv.ab.owner[keys], srv.ab.slot[keys]] = row_scale
    sharding = store.main.sharding

    dtype = store.main.dtype

    def fill(ks, scale):
        rows = table_rows(ks, L, L // 2, 0.5, acc_init, seed, xp=jnp)
        rows = jnp.concatenate(
            [rows[..., :L // 2] * scale[..., None], rows[..., L // 2:]], -1)
        return jnp.where((ks >= 0)[..., None], rows, 0).astype(dtype)

    store.main = jax.block_until_ready(jax.jit(fill, out_shardings=sharding)(
        jax.device_put(slot_key, sharding),
        jax.device_put(slot_scale, sharding)))


def make_rows(ctx):
    """(feature keys -> seeded rows, dense keys -> seeded rows), numpy:
    the reference's copy of the two tables."""
    cfg, sp = ctx.cfg, spec(ctx.cfg)
    d, r = cfg["embedding_dim"], cfg["dense_row"]

    def feat(keys):
        return table_rows(keys, 2 * d, d, cfg["init_scale"],
                          cfg["adagrad_init"], ctx.seed)

    def dense(keys):
        rows = table_rows(keys, 2 * r, r, 0.5, cfg["adagrad_init"],
                          ctx.seed)
        rows[..., :r] *= sp["row_scale"][
            np.asarray(keys) - sp["n_feat"]][..., None]
        return rows
    return feat, dense


def build_run(ctx, data):
    """`CtrRun(args, data)`, as `open_run` builds it, with both pools
    filled on the device from the seed instead of `init_model()`'s host
    fill."""
    from adapm_tpu.apps import ctr
    cfg = ctx.cfg
    join = lambda xs: ",".join(str(x) for x in xs)  # noqa: E731
    argv = ["--table_rows", join(cfg["table_rows"]),
            "--multi_hot_sizes", join(cfg["multi_hot_sizes"]),
            "--embedding_dim", str(cfg["embedding_dim"]),
            "--dense_features", str(cfg["dense_features"]),
            "--bottom_mlp", join(cfg["dense_arch_layer_sizes"]),
            "--top_mlp", join(cfg["over_arch_layer_sizes"]),
            "--dcn_layers", str(cfg["dcn_num_layers"]),
            "--dcn_rank", str(cfg["dcn_low_rank_dim"]),
            "--dense_row", str(cfg["dense_row"]),
            "--batch_size", str(cfg["batch_size"]), "--lr", str(cfg["lr"]),
            "--lookahead", str(cfg["lookahead"]),
            "--adagrad_init", str(cfg["adagrad_init"]),
            "--num_shards", str(cfg["kv_shards"]),
            "--num_workers", str(cfg["workers"]), "--epochs", "1",
            "--seed", str(app_seed(ctx.seed))] + list(cfg["app_args"])
    for name, value in cfg["sys"].items():
        argv += ["--sys." + name, str(value)]
    run = ctr.CtrRun(ctr.build_parser().parse_args(argv), data)
    sp = spec(cfg)
    assert (run.n_feat, run.n_dense) == (sp["n_feat"], sp["n_dense"])
    fill_store_from_seed(run.srv, run.c_feat,
                         np.arange(run.n_feat, dtype=np.int64),
                         cfg["embedding_dim"], cfg["init_scale"],
                         cfg["adagrad_init"], ctx.seed)
    fill_dense_from_seed(run.srv, run.c_dense, run.dense_keys,
                         sp["row_scale"], cfg["adagrad_init"], ctx.seed)
    run.precompile()
    say(f"CtrRun: {run.n_feat} feature keys, rows of {2 * run.dim}, main "
        f"pool {run.srv.stores[run.c_feat].main.shape}; {run.n_dense} "
        f"dense keys, main pool {run.srv.stores[run.c_dense].main.shape} "
        f"{run.srv.stores[run.c_dense].main.dtype}")
    return run


def sampled_keys(ctx, run, n: int, stream: str):
    """n keys drawn over BOTH classes: (feature keys, dense keys)."""
    ks = rng_for(ctx.seed, stream).choice(run.n_feat + run.n_dense, n,
                                          replace=False)
    return np.sort(ks[ks < run.n_feat]), np.sort(ks[ks >= run.n_feat])


def table_is_seeded(ctx, run, rows_of_class, checks) -> None:
    """Before any step: 1,024 rows sampled over both classes equal the
    reference's rows bitwise, with at least 64 of the dense class."""
    kf, kd = sampled_keys(ctx, run, 1024, "tblchk")
    kd = np.union1d(kd, rng_for(ctx.seed, "tblchkd").choice(
        run.dense_keys, min(64, run.n_dense), replace=False))
    bad = 0
    for ks, rows in ((kf, rows_of_class[0]), (kd, rows_of_class[1])):
        got = np.asarray(run.srv.read_main(ks)).reshape(len(ks), -1)
        bad += int((got != rows(ks)).any(axis=1).sum())
    checks.add("table_rows_differ", bad, 0)


def acked_push_dense(ctx, run, checks) -> None:
    """`_exact_checks.after_window`'s push and pull checks once more, on
    keys of the dense class (that function takes keys of one length)."""
    srv, w0 = run.srv, run.workers[0]
    rng = rng_for(ctx.seed, "postchkd")
    ks = np.sort(rng.choice(run.dense_keys, min(64, run.n_dense),
                            replace=False))
    L = int(srv.value_lengths[ks[0]])
    srv.quiesce()
    before = np.asarray(srv.read_main(ks)).reshape(len(ks), L)
    delta = rng.uniform(-1, 1, (len(ks), L)).astype(np.float32)
    w0.wait(w0.push(ks, delta))
    srv.quiesce()
    after = np.asarray(srv.read_main(ks)).reshape(len(ks), L)
    checks.add("acked_push_dense_rows_not_read_back",
               int((after != before + delta).any(axis=1).sum()), 0)
    bad = sum(int(np.asarray(w.pull_sync(ks)).reshape(len(ks), L)
                  .tobytes() != after.tobytes()) for w in run.workers)
    checks.add("workers_differ_from_main_dense", bad, 0)


class CtrStepRecorder(StepRecorder):
    """`StepRecorder` that also keeps what the step is handed besides its
    keys: the dense features and the labels; and of the first step the
    compiled program with its operands' shapes (`matmul_ops`)."""

    called = None    # (the jitted step, its operands as ShapeDtypeStructs)

    def _wrap(self, fn):
        def recorded(pools, locstat, tables, keys, local_index, alias,
                     rng_key, aux, lr, eps):
            if self.called is None:
                import jax
                self.called = (fn, jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=a.sharding),
                    (pools, locstat, tables, keys, local_index, alias,
                     rng_key, aux, lr, eps)))
            out = fn(pools, locstat, tables, keys, local_index, alias,
                     rng_key, aux, lr, eps)
            self.steps.append({
                "keys": {r: np.asarray(k).astype(np.int64)
                         for r, k in keys.items()},
                "x": np.asarray(aux[0], dtype=np.float32),
                "y": np.asarray(aux[1], dtype=np.float32),
                "loss": out[2]})
            return out
        return recorded


_COMPUTATION = re.compile(r"^(%[\w.\-]+) \(.*?\{\n(.*?)^\}", re.S | re.M)
_ENTRY_OP = re.compile(
    r"^\s+(?:ROOT )?(%[\w.\-]+) = .*? ([a-z][a-z\-]*)\(.*?(?:calls=(%[\w.\-]+))?"
    r"(?:, metadata|, backend_config|$)", re.M)


_PRODUCT = re.compile(r" (convolution|dot)\(")


def matmul_ops_of(text: str) -> list:
    """Names of the top-level operations of a compiled program's text
    that hold a matrix product: a `convolution` on a TPU (a `dot` on a
    CPU), as an operation of the ENTRY computation or inside a fusion it
    calls."""
    holds = {name for name, body in _COMPUTATION.findall(text)
             if _PRODUCT.search(body)}
    entry = text[text.index("ENTRY"):]
    return sorted({m.group(1) for m in _ENTRY_OP.finditer(entry)
                   if m.group(2) in ("convolution", "dot")
                   or m.group(3) in holds})


def matmul_ops(rec: "CtrStepRecorder") -> list:
    """`matmul_ops_of` the compiled step's own text: the program `rec`
    saw dispatched, compiled again for the same operands (a hit in the
    compile cache). A profiler trace names the step's operations as the
    compiled text does."""
    fn, operands = rec.called
    return matmul_ops_of(fn.lower(*operands).compile().as_text())


CLASSES = ("feat", "dense")


class CtrProbe:
    """Readings of the program's first steps over two length classes, then
    the comparison of `_probe.Probe`, per class: each loss; per class the
    first gradient's norm (the root of the accumulator columns' change
    after the FIRST step, a sum of g*g: the configuration starts them at
    0, where g*g registers at any size), the norm of the parameters'
    change after the last step and the norm of (program's change -
    reference's change), worst leaf. The leaves are the embedding tables
    and the dense network's tensors."""

    def __init__(self, cfg, n_steps: int, rows_of_class):
        self.cfg, self.sp, self.n_steps = cfg, spec(cfg), n_steps
        self.make_rows = dict(zip(CLASSES, rows_of_class))
        self.width = {"feat": cfg["embedding_dim"],
                      "dense": cfg["dense_row"]}
        self.lr = float(cfg["lr"])
        self.steps = []
        self.after_first, self.after_last = {}, {}

    def _touched(self, steps, cls) -> np.ndarray:
        return np.unique(np.concatenate(
            [rec["keys"][cls].ravel() for rec in steps]))

    def note_step(self, rec: dict, read_rows) -> None:
        """Called once per probe step, right after it; reads back what is
        compared of the touched rows (`read_rows(keys, cols)`)."""
        self.steps.append(dict(rec, loss=float(rec["loss"])))
        for cls in CLASSES:
            w = self.width[cls]
            keys = self._touched(self.steps, cls)
            if len(self.steps) == 1:
                self.after_first[cls] = (keys,
                                         read_rows(keys, slice(w, 2 * w)))
            if len(self.steps) == self.n_steps:
                self.after_last[cls] = (keys, read_rows(keys, slice(0, w)))

    def _leaf_of(self, cls):
        sp = self.sp
        if cls == "feat":
            return lambda ks: np.searchsorted(sp["table_first"], ks,
                                              side="right") - 1
        first = np.array([sp["where"][name][0]
                          for name, _, _ in sp["tensors"]])
        return lambda ks: np.searchsorted(first, ks - sp["n_feat"],
                                          side="right") - 1

    def follow(self, sinks: dict, dtype=np.float32) -> list:
        """The reference's steps from its own seeded rows; every row the
        steps name is held. Hands `sinks[cls].rows(which, keys, base,
        after)` the compared columns; returns the losses."""
        cfg, sp = self.cfg, self.sp
        cast = (lambda x: x) if dtype == np.float32 else \
            (lambda x: x.astype(dtype).astype(np.float32))
        state, seeded = {}, {}
        for cls in CLASSES:
            state[cls] = adagrad_np.RowState(2 * self.width[cls])
            state[cls].ensure(self._touched(self.steps, cls),
                              self.make_rows[cls])
            seeded[cls] = state[cls].rows.copy()
            state[cls].rows = cast(state[cls].rows)
        d, r = self.width["feat"], self.width["dense"]
        hot = cfg["multi_hot_sizes"]
        depth = (len(cfg["dense_arch_layer_sizes"]), cfg["dcn_num_layers"],
                 len(cfg["over_arch_layer_sizes"]))
        losses = []
        for i, rec in enumerate(self.steps):
            kf, kd = rec["keys"]["feat"], rec["keys"]["dense"]
            rf = state["feat"].get(kf.ravel()).reshape(kf.shape + (2 * d,))
            rd = state["dense"].get(kd)
            loss, g_feat, g = dlrm_np.loss_and_grads(
                rf[..., :d], dlrm_np.unpack(rd[:, :r], sp["tensors"], r),
                rec["x"], rec["y"], hot, *depth, dtype=dtype)
            g_dense = dlrm_np.pack(g, sp["tensors"], r)
            # dense rows by key: `kd` is every dense key in order
            upd = {"feat": dlrm_np.position_updates(
                       g_feat, rf[..., d:], self.lr, cfg["eps"]
                   ).reshape(-1, 2 * d),
                   "dense": dlrm_np.position_updates(
                       g_dense[kd - sp["n_feat"]], rd[:, r:], self.lr,
                       cfg["eps"])}
            state["feat"].add(kf.ravel(), upd["feat"])
            state["dense"].add(kd, upd["dense"])
            losses.append(loss)
            for cls in CLASSES:
                st, w = state[cls], self.width[cls]
                st.rows = cast(st.rows)
                if i == 0:
                    first = np.isin(st.keys,
                                    self._touched(self.steps[:1], cls))
                    sinks[cls].rows("first", st.keys[first],
                                    seeded[cls][first, w:],
                                    st.rows[first, w:])
        for cls in CLASSES:
            st, w = state[cls], self.width[cls]
            sinks[cls].rows("last", st.keys, seeded[cls][:, :w],
                            st.rows[:, :w])
        return losses

    def compare(self, checks, limits: dict, control: str = ""):
        """Adds the seven numbers to `checks`; returns (the program's
        losses, the reference's), or None where steps are missing."""
        if len(self.steps) != self.n_steps:
            checks.add("probe_steps_recorded", len(self.steps),
                       self.n_steps, ok=False)
            return None
        program = {cls: {"first": self.after_first[cls],
                         "last": self.after_last[cls]} for cls in CLASSES}
        prog_losses = [rec["loss"] for rec in self.steps]
        if control == "ref-bf16":
            # the control: the reference in the program's place, computed
            # in bfloat16
            import ml_dtypes
            keep = {cls: _Keep(program[cls]) for cls in CLASSES}
            prog_losses = self.follow(keep, ml_dtypes.bfloat16)
            program = {cls: keep[cls].kept for cls in CLASSES}
        n_leaf = {"feat": len(self.cfg["table_rows"]),
                  "dense": len(self.sp["tensors"])}
        sums = {cls: _LeafSums(program[cls], self._leaf_of(cls),
                               n_leaf[cls]) for cls in CLASSES}
        losses = self.follow(sums)
        checks.add("probe_loss_gap",
                   max(abs(p - q) / abs(q)
                       for p, q in zip(prog_losses, losses)),
                   limits["probe_loss_gap"])
        for cls in CLASSES:
            s = sums[cls]
            for name, value in (
                    ("probe_grad_norm_gap", s.worst("first")),
                    ("probe_update_norm_gap", s.worst("last")),
                    ("probe_update_diff_share", s.worst("last", diff=True))):
                checks.add(f"{name}.{cls}", value,
                           limits[f"{name}.{cls}"])
        # which leaf read the worst first-gradient gap, for the log: a
        # seed that reads high says where
        names = {"feat": [f"table{f}" for f in range(n_leaf["feat"])],
                 "dense": [name for name, _, _ in self.sp["tensors"]]}
        say("first-gradient gap, worst leaf: " + "; ".join(
            sums[cls].worst_leaf_text(cls, "first", names[cls])
            for cls in CLASSES))
        return prog_losses, losses
