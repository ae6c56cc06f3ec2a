#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts,
compiles and trains on the chip.

    python chip_smoke.py              # on a TPU machine; anything else exits 2
    python chip_smoke.py --rehearse-cpu   # explicit CPU rehearsal, tiny sizes

ONE process (a chip belongs to one process at a time): it imports jax
once and starts no child that needs the device. It drives the system's
main path through the entry points users call, on however many devices
jax reports (one chip, or the four chips of a host as four kv shards,
with the same arguments):

  kernels   ops/pallas_kernels.py gather_rows + adagrad_apply COMPILED
            (interpret=False) at the store's row width, against numpy;
            scatter_add_rows (the fused step's write-back on one chip)
            at rows of 2048 floats with a Zipf batch, against np.add.at
  contract  adapm_tpu.apps.simple: intent -> push -> clock -> sync round
            -> quiesce -> every worker's pull == main == pushed total
  trainer   adapm_tpu.apps.knowledge_graph_embeddings (open_run/train):
            ComplEx d=128, B=4096, N=32, 200k entities, 1k relations
            (rows of 512 f32), device routes, 3 epochs, once per-step and
            once --scan_steps 8, a pool-gather eval; then a timed loop
            of the same steps ending in block_until_ready and in a value
            fetch, side by side
  server    ServePlane(server).session().lookup on the trained store,
            bitwise equal to Worker.pull_sync; readiness; close/shutdown

Every part runs even if an earlier one failed (one chip run should say
everything it can), every failure is printed with its traceback, and
the exit code is 0 only if every part passed. The last line of stdout
is one JSON object: {"ok": true, "device": {"platform", "kind",
"count"}}. With no TPU (and no --rehearse-cpu) it prints no result and
exits 2.

On several devices the planner's programs compile once per power-of-two
batch bucket and each worker shard's fused step has a with-replicas and
a without-replicas variant, so a new variant may appear after the first
epoch: there the post-warm-up compilations are printed by name, and are
a failure only on one device, where nothing may compile after warm-up.

The rehearsal exists to debug this file without a chip: it pins
JAX_PLATFORMS=cpu, runs the Pallas kernels in interpret mode, shrinks
every size, and labels every line `platform=cpu`. It is never a
fallback, and nothing it prints is a device number. Set
XLA_FLAGS=--xla_force_host_platform_device_count=4 to rehearse the
four-device checks.
"""
from __future__ import annotations

import argparse
import contextlib
import faulthandler
import gc
import io
import json
import math
import os
import re
import sys
import time
import traceback

# full width of the one model this smoke drives
FULL = dict(E=200_000, R=1_000, dim=128, B=4096, N=32, triples=131_072,
            L=512)
# --rehearse-cpu only
TINY = dict(E=2_000, R=50, dim=8, B=64, N=4, triples=2_048, L=128)
EPOCHS = 3
TIMED_STEPS = 16
# dump all stacks and exit before the driver's 1200 s on one chip (every
# further device adds a worker whose fused step compiles separately)
DEADLINE_S_PER_DEVICE = 1100


class _Tee(io.TextIOBase):
    """stdout pass-through that keeps every (time, line) for the checks
    that read the apps' own log lines, and labels lines in rehearsal."""

    def __init__(self, out, label: str):
        self.out = out
        self.label = label
        self.lines = []
        self._buf = ""

    def write(self, s: str) -> int:
        self._buf += s
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            self.lines.append((time.perf_counter(), line))
            self.out.write(self.label + line + "\n")
        self.out.flush()
        return len(s)

    def flush(self) -> None:
        self.out.flush()


class _Compiles:
    """Every backend compile request jax makes, from jax.monitoring:
    (time, program name, seconds). A persistent-cache hit is still an
    event here (its seconds are the retrieval); hits are counted too."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring as mon
        self.events = []
        self.hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == self.EVENT:
            self.events.append((time.perf_counter(),
                                kw.get("fun_name", "?"), secs))

    def _event(self, event, **kw):
        if event == self.HIT:
            self.hits += 1

    def between(self, t0: float, t1: float):
        return [e for e in self.events if t0 < e[0] <= t1]

    def summary(self, since: int = 0, hits_since: int = 0) -> str:
        ev = self.events[since:]
        return (f"compiles={len(ev)} compile_s={sum(e[2] for e in ev):.1f} "
                f"cache_hits={self.hits - hits_since}")


def _check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def _print_mem(tag: str, devs, pool_bytes: int) -> list:
    """Prints and returns each device's peak_bytes_in_use (None where
    the backend reports nothing: CPU)."""
    peaks = []
    for d in devs:
        st = d.memory_stats()
        peaks.append(st.get("peak_bytes_in_use", 0) if st else None)
        if not st:
            print(f"  mem {tag} device {d.id}: not reported by this backend")
        else:
            print(f"  mem {tag} device {d.id}: "
                  f"in_use={st.get('bytes_in_use', 0) / 2**20:.0f} MiB "
                  f"peak={peaks[-1] / 2**20:.0f} MiB  (pool share "
                  f"{pool_bytes / len(devs) / 2**20:.0f} MiB, all pools "
                  f"{pool_bytes / 2**20:.0f} MiB)")
    return peaks


def _pools(srv):
    return [(cid, name, getattr(st, name))
            for cid, st in enumerate(srv.stores)
            for name in ("main", "cache", "delta")]


def _check_pool_layout(srv, devs) -> int:
    """Every pool is S shards of [1, slots, L] on S distinct devices;
    returns the bytes of all pools together."""
    S = len(devs)
    total = 0
    for cid, name, a in _pools(srv):
        total += a.nbytes
        shards = a.addressable_shards
        shapes = {tuple(s.data.shape) for s in shards}
        on = {s.device.id for s in shards}
        _check(len(shards) == S and len(on) == S and
               shapes == {(1,) + tuple(a.shape[1:])},
               f"pool {name}_{cid} {a.shape}: shards {sorted(shapes)} on "
               f"devices {sorted(on)}, expected {S} x "
               f"{(1,) + tuple(a.shape[1:])}")
    print(f"  pools: {len(_pools(srv))} arrays, each {S} x [1, slots, L] "
          f"on {S} distinct device(s), {total / 2**20:.0f} MiB in all")
    return total


def _check_peaks(peaks, pool_bytes: int) -> None:
    """On several devices no device may ever have held as much as all
    the pools together (a pool staged whole on one device, or a
    partitioner that all-gathers a pool, shows here)."""
    if len(peaks) == 1:
        return
    for i, peak in enumerate(peaks):
        _check(peak is None or peak < pool_bytes,
               f"device {i} peak {peak} B >= all pools {pool_bytes} B")


_SYNC_RE = re.compile(
    r"sync: rounds=(\d+) intents=(\d+) replicas\+=(\d+) -=(\d+) "
    r"relocations=(\d+) keys_shipped=(\d+)/considered=(\d+)")


def _sync_report(lines, tag: str) -> dict:
    for _, ln in reversed(lines):
        m = _SYNC_RE.search(ln)
        if m and tag in ln:
            names = ("rounds", "intents", "replicas_created",
                     "replicas_dropped", "relocations", "keys_shipped",
                     "keys_considered")
            return dict(zip(names, map(int, m.groups())))
    raise AssertionError(f"no parsable sync report line from {tag}")


# ------------------------------------------------------------------ parts

def part_kernels(ctx) -> None:
    """The Pallas kernels through the installed Mosaic compiler at the
    store's row width (adagrad also with a row count that is not a
    multiple of the block: the pl.cdiv edge; the write-back kernel at
    the benchmark's row widths, where the fused step uses it: 8 KB rows,
    L = 2048, and the CTR feature rows' 1 KB, L = 256)."""
    import jax.numpy as jnp
    import numpy as np

    from adapm_tpu.ops.pallas_kernels import (adagrad_apply, gather_rows,
                                              scatter_add_rows,
                                              scatter_adagrad_rows)
    from adapm_tpu.ops.writeback import MAX_POSITIONS
    interpret = ctx["rehearsal"]
    L = ctx["sz"]["L"]
    rng = np.random.default_rng(0)
    pool = rng.normal(size=(1024, L)).astype(np.float32)
    idx = rng.integers(0, 1024 // 8, 96).astype(np.int32)
    got = np.asarray(gather_rows(jnp.asarray(pool), jnp.asarray(idx),
                                 block_rows=8, interpret=interpret))
    ref = pool.reshape(-1, 8, L)[idx].reshape(-1, L)
    _check(got.tobytes() == ref.tobytes(),
           "gather_rows differs from the numpy block gather")
    print(f"  gather_rows interpret={interpret} L={L} blocks={len(idx)}: "
          f"bitwise equal")
    lr, eps = 0.1, 1e-10
    for n in (512, 1000):
        g = rng.normal(size=(n, L)).astype(np.float32)
        emb = rng.normal(size=(n, L)).astype(np.float32)
        acc = np.abs(rng.normal(size=(n, L))).astype(np.float32)
        new_emb, new_acc = adagrad_apply(
            jnp.asarray(g), jnp.asarray(emb), jnp.asarray(acc), lr, eps,
            interpret=interpret)
        ref_acc = acc + g * g
        ref_emb = emb - lr * g / np.sqrt(ref_acc + eps)
        _check(np.allclose(np.asarray(new_acc), ref_acc, rtol=1e-5),
               f"adagrad_apply acc mismatch at n={n}")
        _check(np.allclose(np.asarray(new_emb), ref_emb, rtol=1e-4,
                           atol=1e-5),
               f"adagrad_apply emb mismatch at n={n}")
        print(f"  adagrad_apply interpret={interpret} n={n} L={L} "
              f"block=256: matches numpy")
    # the write-back: Zipf slots (long runs of one slot, many rows of
    # one 8-row group), some outside the pool, n not a whole chunk; then
    # more rows than one kernel call takes (three calls on the pool)
    for N, Lw, n, per_call in (
            [(256, 128, 200, None), (256, 128, 200, 64)] if interpret else
            [(4096, 2048, 3000, None), (4096, 256, 3000, None),
             (4096, 128, 300_000, None)]):
        p = 1.0 / np.arange(1, N + 1)
        slots = rng.permutation(N)[rng.choice(N, n, p=p / p.sum())] \
            .astype(np.int32)
        slots[::97] = N + 5
        pool = rng.normal(size=(N, Lw)).astype(np.float32)
        upd = rng.normal(size=(n, Lw)).astype(np.float32)
        got = np.asarray(scatter_add_rows(
            jnp.asarray(pool), jnp.asarray(slots), jnp.asarray(upd),
            interpret=interpret, max_positions=per_call))
        keep = slots < N
        np.add.at(pool, slots[keep], upd[keep])
        _check(got.tobytes() == pool.tobytes(),
               f"scatter_add_rows differs from np.add.at at n={n} (max "
               f"abs {np.abs(got - pool).max():.3g})")
        print(f"  scatter_add_rows interpret={interpret} L={Lw} n={n} "
              f"({len(np.unique(slots[keep]))} slots, "
              f"{int((~keep).sum())} dropped, "
              f"{per_call or MAX_POSITIONS} positions a call): bitwise "
              f"equal to np.add.at")
    # its AdaGrad form, what the fused step runs: the update rows
    # [-lr g rsqrt(acc + g^2 + eps) | g^2] formed inside the kernel
    for N, Lw, n in ([(256, 256, 200)] if interpret else
                     [(4096, 2048, 3000), (4096, 256, 3000)]):
        H = Lw // 2
        slots = rng.integers(0, N + 8, n).astype(np.int32)  # some dropped
        pool = np.abs(rng.normal(size=(N, Lw))).astype(np.float32)
        g = rng.normal(size=(n, H)).astype(np.float32)
        acc = np.abs(rng.normal(size=(n, H))).astype(np.float32)
        got = np.asarray(scatter_adagrad_rows(
            jnp.asarray(pool), jnp.asarray(slots), jnp.asarray(g),
            jnp.asarray(acc), lr, eps, interpret=interpret))
        keep = slots < N
        g2 = g * g
        upd = np.concatenate([-np.float32(lr) * g / np.sqrt(
            acc + g2 + np.float32(eps)), g2], axis=1)
        mag = pool.copy()
        np.add.at(mag, slots[keep], np.abs(upd[keep]))
        np.add.at(pool, slots[keep], upd[keep])
        _check(got[:, H:].tobytes() == pool[:, H:].tobytes(),
               "scatter_adagrad_rows: the accumulator halves differ from "
               "np.add.at of g*g")
        worst = float((np.abs(got - pool) / np.spacing(mag)).max())
        _check(worst <= 4, f"scatter_adagrad_rows: embedding halves differ "
               f"from numpy by {worst:.1f} ulp of the summed magnitudes")
        print(f"  scatter_adagrad_rows interpret={interpret} L={Lw} n={n}: "
              f"accumulators bitwise equal, embeddings within {worst:.1f} ulp "
              f"of numpy's 1/sqrt")


def part_contract(ctx) -> None:
    """apps.simple: the PM contract, one worker per device."""
    from adapm_tpu.apps import simple
    mark = len(ctx["tee"].lines)
    rc = simple.main(["--iterations", "10"])
    lines = ctx["tee"].lines[mark:]
    _check(rc == 0 and any("[simple]" in ln and "PASSED" in ln
                           for _, ln in lines),
           f"apps.simple failed (rc={rc})")
    rep = _sync_report(lines, "[simple]")
    print(f"  apps.simple: {rep}")
    if ctx["S"] > 1:
        _check(rep["replicas_created"] > 0,
               f"{ctx['S']} workers contending for one key created no "
               f"replica: {rep}")


_EPOCH_RE = re.compile(r"\[kge\] epoch (\d+): loss=(\S+) time=([\d.]+)s")


def _open_trained_run(ctx, scan_steps: int):
    """open_run + train through the KGE app; returns the live run after
    all the checks on what the app printed and returned."""
    import jax

    from adapm_tpu.apps import knowledge_graph_embeddings as kge
    sz, tee, comp, devs, S = (ctx["sz"], ctx["tee"], ctx["compiles"],
                              ctx["devs"], ctx["S"])
    argv = ["--model", "complex", "--dim", str(sz["dim"]),
            "--batch_size", str(sz["B"]), "--neg_ratio", str(sz["N"]),
            "--synthetic_entities", str(sz["E"]),
            "--synthetic_relations", str(sz["R"]),
            "--synthetic_triples", str(sz["triples"]),
            "--epochs", str(EPOCHS), "--eval_every", str(EPOCHS),
            "--eval_triples", "64", "--scan_steps", str(scan_steps)]
    print(f"  argv: {' '.join(argv)}")
    c0, h0 = len(comp.events), comp.hits
    t0 = time.perf_counter()
    run = kge.open_run(kge.build_parser().parse_args(argv))
    jax.block_until_ready([a for _, _, a in _pools(run.srv)])
    print(f"  set-up {time.perf_counter() - t0:.1f} s "
          f"({run.num_workers} worker(s); {comp.summary(c0, h0)})")
    pool_bytes = _check_pool_layout(run.srv, devs)
    _check_peaks(_print_mem("after set-up", devs, pool_bytes), pool_bytes)

    mark = len(tee.lines)
    t0 = time.perf_counter()
    result = kge.train(run)
    lines = tee.lines[mark:]
    print(f"  train+eval {time.perf_counter() - t0:.1f} s "
          f"({comp.summary(c0, h0)})")

    epochs = [(t, int(m.group(1)), float(m.group(2)), float(m.group(3)))
              for t, ln in lines for m in [_EPOCH_RE.search(ln)] if m]
    _check([e[1] for e in epochs] == list(range(EPOCHS)),
           f"expected {EPOCHS} epoch report lines, got {epochs}")
    losses = [e[2] for e in epochs]
    _check(all(math.isfinite(x) for x in losses),
           f"non-finite epoch loss: {losses}")
    _check(losses[-1] < losses[0],
           f"loss did not fall: {losses}")
    steps = -(-sz["triples"] // sz["B"])
    _check(steps >= 16, f"only {steps} steps per epoch")
    last = (epochs[-1][3] - epochs[-2][3]) / steps
    in_last = comp.between(epochs[-2][0], epochs[-1][0])
    print(f"  {steps} steps/epoch, epoch losses {losses}; app loop, last "
          f"epoch: {last * 1e3:.2f} ms/step (ends in quiesce + loss "
          f"fetch; {len(in_last)} compilations, "
          f"{sum(e[2] for e in in_last):.1f} s, inside it) on {ctx['dev']}")
    late = comp.between(epochs[0][0], epochs[-1][0])
    print(f"  compilations after the warm-up epoch: {len(late)} "
          f"{sorted({e[1] for e in late})}")
    if S == 1:
        _check(not late, f"compiled after warm-up: {late}")
    for k in ("mrr", "hits10", "test_mrr", "test_hits10"):
        _check(k in result and 0.0 <= result[k] <= 1.0,
               f"eval result {k!r} missing or out of range: {result}")
    print(f"  eval: valid MRR={result['mrr']:.4f} "
          f"test MRR={result['test_mrr']:.4f} "
          f"(64 triples, both sides, all {sz['E']} candidates)")
    rep = _sync_report(lines, "[kge]")
    print(f"  sync report: {rep}")
    _check(rep["rounds"] > 0 and rep["intents"] > 0,
           f"planner never ran: {rep}")
    if S > 1:
        _check(rep["replicas_created"] + rep["relocations"] > 0 and
               rep["keys_shipped"] > 0,
               f"adaptive machinery not live on {S} devices: {rep}")
    _check_pool_layout(run.srv, devs)
    _check_peaks(_print_mem("after training", devs, pool_bytes),
                 pool_bytes)
    return run


def _timed_loops(ctx, run) -> None:
    """The app's per-step body, TIMED_STEPS times, ending once in
    block_until_ready and once in a value fetch (twice each,
    interleaved): the pair says whether block_until_ready is honest
    here, i.e. whether a timing still needs a slope."""
    import jax
    import numpy as np
    srv, w, comp = run.srv, run.workers[0], ctx["compiles"]
    runner = run.device_runner(w.shard)
    B, lr = ctx["sz"]["B"], run.args.lr
    tr = run.ds.train
    batches = []
    for i in range(4):
        t = tr[i * B:(i + 1) * B]
        roles = {"s": run.ekey(t[:, 0]), "r": run.rkey(t[:, 1]),
                 "o": run.ekey(t[:, 2])}
        batches.append((roles, np.unique(np.concatenate(
            list(roles.values())))))

    def loop(n):
        loss = None
        for i in range(n):
            nxt = batches[(i + 1) % len(batches)][1]
            w.intent(nxt, w.current_clock + 1, w.current_clock + 2)
            loss = runner(batches[i % len(batches)][0], None, lr)
            srv.drive_rounds(1)
            w.advance_clock()
        return loss

    jax.block_until_ready(loop(2))
    c0 = len(comp.events)
    out = {"block_until_ready": [], "value_fetch": []}
    for _ in range(2):
        t0 = time.perf_counter()
        loss = loop(TIMED_STEPS)
        jax.block_until_ready(loss)
        out["block_until_ready"].append(
            (time.perf_counter() - t0) / TIMED_STEPS)
        t0 = time.perf_counter()
        val = float(loop(TIMED_STEPS))
        out["value_fetch"].append((time.perf_counter() - t0) / TIMED_STEPS)
        _check(math.isfinite(val) and math.isfinite(float(loss)),
               f"non-finite step loss {val} / {float(loss)}")
    print(f"  timed per-step loop ({TIMED_STEPS} steps, B={B}) on "
          f"{ctx['dev']}: ends in block_until_ready "
          f"{[round(x * 1e3, 2) for x in out['block_until_ready']]} "
          f"ms/step | ends in value fetch "
          f"{[round(x * 1e3, 2) for x in out['value_fetch']]} ms/step")
    late = comp.events[c0:]
    print(f"  compilations inside the timed loops: {len(late)} "
          f"{sorted({e[1] for e in late})}")
    if ctx["S"] == 1:
        _check(not late, f"compiled inside the timed loop: {late}")


def _serve_and_close(ctx, run) -> None:
    """ServePlane on the trained store; then close and shutdown."""
    import numpy as np

    from adapm_tpu.serve import ServePlane
    srv, sz = run.srv, ctx["sz"]
    rng = np.random.default_rng(7)
    srv.quiesce()
    keys = np.concatenate([run.ekey(rng.integers(0, sz["E"], 256)),
                           run.rkey(rng.integers(0, sz["R"], 32))])
    main = np.asarray(srv.read_main(keys)).reshape(len(keys), -1)
    _check(np.isfinite(main).all(), "non-finite rows in the trained store")
    for w in run.workers:
        got = np.asarray(w.pull_sync(keys)).reshape(len(keys), -1)
        _check(got.tobytes() == main.tobytes(),
               f"worker {w.shard}: pull_sync != read_main after quiesce")
    print(f"  after quiesce: {len(run.workers)} worker(s) pull_sync == "
          f"read_main on {len(keys)} keys (bitwise)")
    plane = ServePlane(srv)
    sess = plane.session()
    w0 = run.workers[0]
    for b in range(3):
        keys = np.concatenate([run.ekey(rng.integers(0, sz["E"], 480)),
                               run.rkey(rng.integers(0, sz["R"], 32))])
        got = np.asarray(sess.lookup(keys, deadline_ms=30_000))
        ref = np.asarray(w0.pull_sync(keys))
        _check(got.shape == ref.shape and got.tobytes() == ref.tobytes(),
               f"serve lookup batch {b} differs from pull_sync")
    ready = plane.health.readiness()
    _check(ready["ready"], f"not ready: {ready['reasons']}")
    print(f"  serve: 3 lookups x {len(keys)} keys bitwise equal to "
          f"pull_sync; readiness ready={ready['ready']} "
          f"dispatchers={ready['dispatchers']}")
    t0 = time.perf_counter()
    plane.close()
    t_close = time.perf_counter() - t0
    t0 = time.perf_counter()
    srv.shutdown()
    t_shut = time.perf_counter() - t0
    print(f"  close {t_close:.2f} s, shutdown {t_shut:.2f} s")
    _check(t_close < 10 and t_shut < 10,
           f"close/shutdown took {t_close:.1f}/{t_shut:.1f} s")


def part_trainer_per_step(ctx) -> None:
    """KGE through the app with per-step dispatch, the two timed loops,
    and the server on the store it trained."""
    run = _open_trained_run(ctx, scan_steps=1)
    try:
        _timed_loops(ctx, run)
        _serve_and_close(ctx, run)
    finally:
        run.srv.shutdown()  # idempotent


def part_trainer_scan(ctx) -> None:
    """KGE through the app with --scan_steps 8 (one dispatch trains 8
    batches)."""
    _open_trained_run(ctx, scan_steps=8).srv.shutdown()


PARTS = [("kernels", part_kernels), ("contract", part_contract),
         ("trainer_per_step", part_trainer_per_step),
         ("trainer_scan", part_trainer_scan)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="explicit CPU rehearsal at tiny sizes (interpret-"
                         "mode kernels; every line labelled platform=cpu)")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    try:
        from adapm_tpu import native
        from adapm_tpu.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke.py: cannot import adapm_tpu ({e}); run it from "
              f"the root of a checkout", file=sys.stderr)
        return 2
    import jax
    cache_dir = enable_compile_cache()
    devs = jax.devices()
    faulthandler.dump_traceback_later(DEADLINE_S_PER_DEVICE * len(devs),
                                      exit=True)
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != ("cpu" if args.rehearse_cpu else "tpu"):
        print(f"chip_smoke.py: no TPU: jax.devices()[0].platform is "
              f"{dev['platform']!r} ({dev['count']} x {dev['kind']}). This "
              f"smoke runs on the chip only; --rehearse-cpu is the "
              f"explicit CPU rehearsal.", file=sys.stderr)
        return 2

    tee = _Tee(sys.stdout, "platform=cpu | " if args.rehearse_cpu else "")
    failed = []
    with contextlib.redirect_stdout(tee):
        from importlib import metadata
        try:
            libtpu = metadata.version("libtpu")
        except metadata.PackageNotFoundError:
            libtpu = "not installed"
        router = native.get_lib() is not None
        print(f"platform={dev['platform']} device_kind={dev['kind']} "
              f"devices={dev['count']} jax={jax.__version__} "
              f"jaxlib={metadata.version('jaxlib')} libtpu={libtpu}")
        print(f"compile_cache={cache_dir} "
              f"(entries at start: "
              f"{len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0}"
              f") native_router={'loaded' if router else 'NOT loaded'}")
        if not router and not os.environ.get("ADAPM_NO_NATIVE"):
            failed.append("native_router")
            print("FAILED native_router: the C++ router did not build/load "
                  "(see stderr) and ADAPM_NO_NATIVE is unset")
        ctx = {"rehearsal": args.rehearse_cpu, "tee": tee, "devs": devs,
               "sz": TINY if args.rehearse_cpu else FULL, "S": len(devs),
               "dev": f"{dev['count']} x {dev['kind']}",
               "compiles": _Compiles()}
        t_all = time.perf_counter()
        for name, fn in PARTS:
            print(f"== {name}")
            t0 = time.perf_counter()
            c0, h0 = len(ctx["compiles"].events), ctx["compiles"].hits
            try:
                fn(ctx)
            except Exception:  # a failed part fails the run, below
                failed.append(name)
                traceback.print_exc(file=sys.stdout)
                print(f"FAILED {name}")
            gc.collect()  # a finished part's pools go before the next
            print(f"== {name}: {'FAILED' if name in failed else 'ok'} "
                  f"in {time.perf_counter() - t0:.1f} s "
                  f"({ctx['compiles'].summary(c0, h0)})")
        print(f"total {time.perf_counter() - t_all:.1f} s, "
              f"{ctx['compiles'].summary()}; slowest compile requests: "
              + ", ".join(f"{n} {s:.1f}s" for _, n, s in sorted(
                  ctx["compiles"].events, key=lambda e: -e[2])[:6]))
    sys.stdout.flush()
    out = {"ok": not failed, "device": dev}
    if failed:
        out["failed"] = failed
    if args.rehearse_cpu:
        out["rehearsal"] = True
    print(json.dumps(out))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
