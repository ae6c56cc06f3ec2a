"""The per-chip step's exchange in isolation, on FOUR chips (ISSUE 43):
`ops/fused.py _away` + `_exchange` out and back over the kv axis, at the
two four-chip cells' shapes (the CTR step's 438,272 feature positions of
128 columns with 6% of them off the worker's chip; a KGE role's 4,096
positions of 1,024 columns with 4%), for several `EXCHANGE_BYTES`, beside
the whole-array sums that the exchange replaced. What it settles is the
chunk: a psum needs the other chips, so one chip cannot time it.

    chiprun --chips 4 -- python scripts/exchange_probe.py

Prints `probe <shape> <form>: <ms> ms a call` by the host's clock over
calls queued back to back (the device paces them). `--rehearse-cpu`
debugs the script here on four virtual devices and prints no device
number. TPU only otherwise."""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--bytes", type=int, nargs="*",
                    default=[1 << 20, 2 << 20, 4 << 20, 8 << 20, 16 << 20])
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
            " --xla_force_host_platform_device_count=4"
    sys.path.insert(0, ROOT)

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from adapm_tpu.ops import fused
    cpu = args.rehearse_cpu
    if jax.devices()[0].platform != ("cpu" if cpu else "tpu") \
            or len(jax.devices()) < 4:
        print("exchange_probe.py: no four TPU chips", file=sys.stderr)
        return 2
    tag = "platform=cpu | probe" if cpu else "probe"
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("kv",))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("kv"))
    slots = jnp.zeros((1, 8, 1))  # `_away` reads a block's slot count

    def hinted(x, away, axis):
        """`fused._exchange` with the positions promised ascending and
        distinct (the padding counts up from `n`)."""
        n, k = x.shape[0], away.rows
        order = jnp.where(away.order >= n, n + jax.lax.iota(
            jnp.int32, away.order.shape[0]), away.order)
        hint = dict(indices_are_sorted=True, unique_indices=True)

        def chunk(t, flat):
            idx = jax.lax.dynamic_slice(order, (t * k,), (k,))
            block = jax.lax.psum(flat.at[idx].get(
                mode="fill", fill_value=0, **hint), axis)
            return flat.at[idx].set(block, mode="drop", **hint)
        return jax.lax.fori_loop(0, away.chunks, chunk, x)

    def build(form):
        def body(x, sh):
            # worker 0's view: x is a chip's [1, n, dim] own rows
            x, sl = x[0], jnp.zeros_like(sh)
            worker = fused._here(jnp.int32(0), "kv")
            if form == "whole":
                got = jax.lax.psum(x, "kv")
                back = jax.lax.psum(jnp.where(worker, got * 2, 0), "kv")
            else:
                away = fused._away((sh, sl), slots, jnp.int32(0), "kv",
                                   x.shape[-1])
                if form == "sort":
                    return (x + away.order[:1, None])[None]
                sums = fused._exchange if form == "chunks" else hinted
                got = sums(x, away, "kv")
                back = sums(jnp.where(worker, got * 2, 0), away, "kv")
            return back[None]
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P("kv"), P()), out_specs=P("kv"),
            check_vma=False))

    shapes = [("ctr-feat", 438_272, 128, 0.0586), ("kge-role", 4096, 1024,
                                                   0.0364)]
    if cpu:
        shapes = [("ctr-feat", 4096, 8, 0.06), ("kge-role", 64, 32, 0.2)]
    rng = np.random.default_rng(0)
    for name, n, dim, share in shapes:
        sh = np.zeros(n, np.int32)
        off = rng.random(n) < share
        sh[off] = rng.integers(1, 4, off.sum())
        # a row lies on ONE chip and reads zeros on the others, as the
        # step's gather leaves it: the sums are exact in any order
        x = rng.normal(size=(n, dim)).astype(np.float32)
        x = jax.device_put(np.stack(
            [np.where((sh == c)[:, None], x, 0) for c in range(4)]), rows)
        sh_dev = jax.device_put(sh, rep)
        forms = [("whole", None), ("sort", None)] + \
            [(f, b) for b in args.bytes for f in ("chunks", "hinted")]
        want = None
        for form, nbytes in forms:
            if nbytes is not None:
                fused.EXCHANGE_BYTES = nbytes // (64 if cpu else 1)
            fn = build(form)
            out = jax.block_until_ready(fn(x, sh_dev))
            if form == "whole":
                want = np.asarray(out)[:, off]
            elif form != "sort":  # the rows off worker 0's chip agree
                assert np.array_equal(np.asarray(out)[:, off], want), \
                    "exchange differs"
            t0 = time.time()
            for _ in range(args.calls):
                out = fn(x, sh_dev)
            jax.block_until_ready(out)
            ms = (time.time() - t0) / args.calls * 1e3
            what = form if nbytes is None else \
                f"{form} of {min(n, fused.EXCHANGE_BYTES // (4 * dim))}"
            print(f"{tag} {name} [{n}, {dim}], {int(off.sum())} off the "
                  f"chip, {what}: {ms:.3f} ms a call", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
