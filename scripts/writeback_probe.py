"""The fused step's write-back in isolation, on the chip (ISSUE 25).

    chiprun --chips 1 -- python scripts/writeback_probe.py

At the KGE cell's shape (a float32 [1, 1172432, 2048] pool, donated, and
131,072 update rows) it times, for uniform slots and for Zipf(1.0)
slots: (a) today's `.at[sh, sl].add(mode="drop")`; (b) the same on
sorted slots with `indices_are_sorted=True`, the update rows permuted
beforehand, and that permutation alone; (c) on sorted, duplicate-free
slots with `unique_indices=True` too; (d) the gather of the same rows;
`ops/pallas_kernels.scatter_add_rows` (`kernel_R<rows>`: the sort, the
permutation of the 8 KB update rows and the kernel's plain form); and
`scatter_adagrad_rows` (`kernel_adagrad_R<rows>`, ISSUE 29: the sort, the
permutation of the two 4 KB halves and the kernel's AdaGrad form, which
is what the fused step runs); and the kernel ALONE
(`kernel_alone_R<rows>`, `kernel_alone_adagrad_R<rows>`: its calls on
codes sorted and operands permuted beforehand, which is what a step's
`_scatter_adagrad_sorted_rows` custom calls are). One line a reading:
`probe <name> <draw>: <ms> ms, <ns> ns a position, <ns> ns a row`: a
POSITION is one slot of the batch, landed or not; a ROW is a distinct
pool row the batch changes (none where every slot is outside the pool).

`--draw` names the draws (comma-separated): `uniform`, `zipf`, `unique`
(all positions distinct), `invalid` (every slot outside the pool: the
kernel's price for a position it does nothing with) and `ctr`, the CTR
cell's own member draw (`benchmarks/configs/dlrm-dcnv2-criteo1tb.json`:
a batch of 2,048 examples of 214 members, Zipf over each table's rows;
`--n` of its 438,272 sorted positions, a window from the middle, which
is what one kernel call of the cell sees; about 30% of them distinct).
At the CTR cell's shape: `--slots 6508400 --row 256 --n 131072 --draw
ctr,unique,invalid --only kernel_alone`. TPU only.
"""
from __future__ import annotations

import argparse
import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, default=1_172_432)
    ap.add_argument("--row", type=int, default=2048)
    ap.add_argument("--n", type=int, default=131_072)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debug this script without a chip (interpret-"
                         "mode kernel; pass tiny sizes; no device number)")
    ap.add_argument("--kernel-rows", default=[32],
                    type=lambda v: [int(x) for x in v.split(",")],
                    help="chunk_rows to time the kernel at: 16,32,64")
    ap.add_argument("--draw", default="uniform,zipf,unique",
                    help="comma-separated draws: uniform, zipf, unique, "
                         "invalid, ctr")
    ap.add_argument("--only", default="",
                    help="comma-separated reading names (default: all)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    cpu = args.rehearse_cpu
    tag = "platform=cpu | " if cpu else ""
    if dev.platform != ("cpu" if cpu else "tpu"):
        print(f"writeback_probe.py: no TPU ({dev.platform})",
              file=sys.stderr)
        return 2
    from adapm_tpu.ops import pallas_kernels, writeback
    scatter_add_rows = functools.partial(pallas_kernels.scatter_add_rows,
                                         interpret=cpu)
    scatter_adagrad_rows = functools.partial(
        pallas_kernels.scatter_adagrad_rows, interpret=cpu)
    N, L, n = args.slots, args.row, args.n
    only = set(filter(None, args.only.split(",")))
    rng = np.random.default_rng(25)

    def zipf():
        p = 1.0 / np.arange(1, N + 1)
        return rng.permutation(N)[rng.choice(N, n, p=p / p.sum())]

    makers = {
        "uniform": lambda: rng.integers(0, N, n),
        "zipf": zipf,
        "unique": lambda: rng.permutation(N)[:n],
        "invalid": lambda: N + rng.integers(0, N, n),
        "ctr": lambda: _ctr_slots(rng, N, n),
    }
    draws = {d: makers[d]().astype(np.int32)
             for d in filter(None, args.draw.split(","))}
    print(f"platform={dev.platform} device={dev.device_kind} "
          f"pool=f32[1,{N},{L}] n={n}")
    pool = jnp.zeros((1, N, L), jnp.float32)
    upd = jax.random.normal(jax.random.PRNGKey(0), (n, L), jnp.float32)
    sh = jnp.zeros((n,), jnp.int32)
    # the AdaGrad form's operands: gradients and gathered accumulators
    g, acc = upd[:, :L // 2], jnp.abs(upd[:, L // 2:])
    lr, eps = jnp.float32(0.1), jnp.float32(1e-10)

    def timed(name, draw, fn, *xs, on_pool=True):
        """ms a call of fn(pool, *xs) -> pool (the pool donated) or of
        fn(*xs) -> a value; per position of xs[0] and per distinct row."""
        nonlocal pool
        if only and not any(name.startswith(o) for o in only):
            return
        try:
            f = jax.jit(fn, donate_argnums=(0,) if on_pool else ())

            def call():
                nonlocal pool
                if on_pool:
                    pool = f(pool, *xs)
                    return pool
                return f(*xs)
            jax.block_until_ready(call())
            t0 = time.perf_counter()
            for _ in range(args.reps):
                out = call()
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / args.reps * 1e3
            rows = distinct[draw]
            print(f"{tag}probe {name} {draw}: {ms:.3f} ms, "
                  f"{ms * 1e6 / xs[0].shape[0]:.1f} ns a position, "
                  + (f"{ms * 1e6 / rows:.1f} ns a row" if rows else
                     "no row lands"), flush=True)
        except Exception as e:  # one reading failing must not lose the rest
            print(f"{tag}probe {name} {draw}: FAILED {type(e).__name__}: "
                  f"{str(e)[:300]}", flush=True)

    def xla_add(m, s, u, **flags):
        return m.at[sh[:s.shape[0]], s].add(u, mode="drop", **flags)

    # the kernel against XLA on a pool small enough to hold twice
    k = min(n, 8192)
    small = jnp.ones((1, min(N, 65_536), L), jnp.float32)
    sl = jnp.asarray(next(iter(draws.values()))[:k] % small.shape[1])
    want = xla_add(small, sl, upd[:k])
    got = scatter_add_rows(small[0], sl, upd[:k])[None]
    print(f"{tag}check kernel against XLA, n={k}: max abs difference "
          f"{float(jnp.max(jnp.abs(got - want))):.3g}", flush=True)
    g2 = g[:k] * g[:k]
    want = xla_add(small, sl, jnp.concatenate(
        [-lr * g[:k] * jax.lax.rsqrt(acc[:k] + g2 + eps), g2], axis=-1))
    got = scatter_adagrad_rows(small[0], sl, g[:k], acc[:k], lr, eps)[None]
    print(f"{tag}check AdaGrad kernel against XLA, n={k}: max abs "
          f"difference {float(jnp.max(jnp.abs(got - want))):.3g}", flush=True)
    del small, want, got

    distinct = {d: len(np.unique(s[s < N])) for d, s in draws.items()}
    for draw, sl_np in draws.items():
        sl = jnp.asarray(sl_np)
        print(f"{tag}draw {draw}: {n} positions, {distinct[draw]} distinct "
              f"rows ({distinct[draw] / n:.1%})", flush=True)
        order = np.argsort(sl_np, kind="stable").astype(np.int32)
        sl_sorted, perm = jnp.asarray(sl_np[order]), jnp.asarray(order)
        timed("a_scatter_add", draw, xla_add, sl, upd)
        timed("b_permute_upd", draw, lambda p_, u: u[p_], perm, upd,
              on_pool=False)
        timed("b_sorted", draw, functools.partial(
            xla_add, indices_are_sorted=True), sl_sorted, upd)
        if draw == "unique":
            timed("c_sorted_unique", draw, functools.partial(
                xla_add, indices_are_sorted=True, unique_indices=True),
                sl_sorted, upd)
            timed("c_unsorted_unique", draw, functools.partial(
                xla_add, unique_indices=True), sl, upd)
            timed("c_set_sorted_unique", draw,
                  lambda m, s, u: m.at[sh, s].set(
                      u, mode="drop", indices_are_sorted=True,
                      unique_indices=True), sl_sorted, upd)
            # nine rows of ten dropped: what a dropped row costs
            timed("a_scatter_add_90pct_dropped", draw, xla_add,
                  jnp.where(jnp.arange(n) % 10 == 0, sl, 2**30), upd)
        timed("sort_slots", draw, lambda s: jax.lax.sort(
            (s, jax.lax.iota(jnp.int32, n)), num_keys=1, is_stable=True),
            sl, on_pool=False)
        timed("d_gather", draw,
              lambda s, m: m.at[sh, s].get(mode="fill", fill_value=0),
              sl, pool, on_pool=False)
        for rows in args.kernel_rows:
            timed(f"kernel_R{rows}", draw,
                  lambda m, s, u, rows=rows: scatter_add_rows(
                      m[0], s, u, chunk_rows=rows)[None], sl, upd)
            timed(f"kernel_adagrad_R{rows}", draw,
                  lambda m, s, g_, a, rows=rows: scatter_adagrad_rows(
                      m[0], s, g_, a, lr, eps, chunk_rows=rows)[None],
                  sl, g, acc)
            # the kernel alone: codes sorted and operands permuted once,
            # outside the timed program
            slices = writeback.sorted_slices(sl, N, rows)
            codes = [c for c, _ in slices]
            for form, kernel, xs in (
                    ("", pallas_kernels.scatter_add_sorted_rows, (upd,)),
                    ("_adagrad", functools.partial(
                        pallas_kernels.scatter_adagrad_sorted_rows,
                        lr=lr, eps=eps), (g, acc))):
                ops = [[x[perm] for x in xs] for _, perm in slices]

                def alone(m, _counted, codes, ops, kernel=kernel,
                          rows=rows):  # `timed` counts xs[0]'s positions
                    m = m[0]
                    for c, o in zip(codes, ops):
                        m = kernel(m, c, *o, chunk_rows=rows, interpret=cpu)
                    return m[None]
                timed(f"kernel_alone{form}_R{rows}", draw, alone, xs[0],
                      codes, ops)
    return 0


def _ctr_slots(rng, n_slots: int, n: int):
    """`n` of the CTR cell's positions as one kernel call sees them: a
    batch's members (Zipf over each table's rows by a fixed permutation,
    as `benchmarks/drivers/_ctr.py draw_examples`), as slots of a pool
    that holds the tables one after the other, sorted, a window of `n`
    from the middle, in a random order."""
    import json
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                           "benchmarks", "configs",
                           "dlrm-dcnv2-criteo1tb.json")) as f:
        cfg = json.load(f)
    first = np.concatenate([[0], np.cumsum(cfg["table_rows"])])
    assert first[-1] <= n_slots, (first[-1], n_slots)
    slots = []
    for t, (rows, hot) in enumerate(zip(cfg["table_rows"],
                                        cfg["multi_hot_sizes"])):
        w = 1.0 / np.arange(1, rows + 1) ** cfg["assumed"]["zipf_exponent"]
        cdf = np.cumsum(w) / w.sum()
        r = np.minimum(np.searchsorted(
            cdf, rng.random(cfg["batch_size"] * hot), side="right"),
            rows - 1)
        slots.append(first[t] + rng.permutation(rows)[r])
    slots = np.sort(np.concatenate(slots))
    assert n <= len(slots), (n, len(slots))
    lo = (len(slots) - n) // 2
    return rng.permutation(slots[lo:lo + n])


if __name__ == "__main__":
    sys.exit(main())
