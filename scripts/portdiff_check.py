"""Port-differential + fused-bag guard (ISSUE 16 satellite; run by
scripts/run_tests.sh).

Three assertions about the device plane that a regression would break
silently:

1. **The two ports agree bitwise.** The SAME seeded 5-plane storm —
   training pulls, pushes, sets, serve-plane flat lookups, and bag
   lookups (sum AND mean, fused and host-pool dispatch alternating),
   over a TIERED server, maintenance kicked throughout — runs once
   against the jax DevicePort and once against the pure-NumPy
   reference port (device/refport.py). Every read the storm observes,
   and the full post-quiesce table, must be bit-identical between the
   two runs. The storm's tier keeps the fp32 cold wire: WHICH rows
   sit cold at read time depends on async maintenance timing, so a
   lossy wire would make the comparison race on residency, not on
   program correctness — the quantized wires are instead compared
   store-level below, where residency is a deterministic function of
   the slot index. The reference port is the executable spec: a
   device program that drifts from it (a changed accumulation order,
   a quantization shortcut, a donation bug corrupting a buffer) fails
   HERE, with a named op index, instead of surfacing as a flaky
   training loss three layers up. The fp16 and int8 wire programs
   (set-rows ingest, gather, fused gather_pool sum/mean over mixed
   hot/cold slots) get their own differential pass on standalone
   tiered stores, one per port, same inputs — bitwise again.

2. **The reference port stays confined.** device/refport.py must
   contain no jax import and no `apm-lint: disable` suppression — the
   APM008 device-API confinement story (docs/LINT.md): the reference
   implementation is trustworthy BECAUSE it cannot touch the device
   API it specifies, and it earns that status without silencing the
   analyzer.

3. **The fused bag read pays (or at worst breaks even on CPU).** The
   satellite bag workload — 8192 member rows x 128 wide pooled into
   256 bags (32 members/bag, the DLRM shape) — is timed store-level,
   fused `gather_pool` vs gather-then-host-pool, MEDIAN-pairwise per
   the exec_overlap_check.py convention. On an accelerator backend the
   fused program must win outright: median < 0.9 — its saving is wire
   bytes (nbags*L pooled rows cross instead of n*L member rows), a
   32x transfer reduction at this shape. A host-CPU multiplex moves
   those bytes with a memcpy, so the saving is invisible there and the
   honest pass bar is "within noise of host pooling": median < 1.25
   (observed CPU medians 0.84-1.05 across runs on this shared box).
   Override: ADAPM_BAG_RATIO_MAX. The structural failure mode this
   catches — a fused program that re-gathers per bag, or pools on a
   serialized side stream — costs a MULTIPLE on every backend.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    from xla_compat import mesh_flags
    os.environ["XLA_FLAGS"] = " ".join([_flags, mesh_flags(2)]).strip()

import numpy as np  # noqa: E402

NK = 2048
VLEN = 16
STEPS = 96            # storm ops per port (6-op cycle)
B = 48                # keys per storm op
NBAGS = 8             # bags per storm bag lookup
# the bag-ratio workload (module docstring, item 3)
RATIO_E = 20_000
RATIO_L = 128
RATIO_N = 8192
RATIO_NBAGS = 256
RATIO_REPEATS = 9


def storm(port) -> list:
    """One seeded 5-plane storm against `port`; returns every array
    the storm READ (op order) plus the post-quiesce full table."""
    import adapm_tpu
    from adapm_tpu.config import SystemOptions
    from adapm_tpu.device.port import set_default_port
    from adapm_tpu.serve import ServePlane

    set_default_port(port)
    try:
        srv = adapm_tpu.setup(NK, VLEN, opts=SystemOptions(
            sync_max_per_sec=0, prefetch=False,
            tier=True, tier_hot_rows=max(8, NK // 4)))
        w = srv.make_worker(0)
        rng = np.random.default_rng(7)
        w.wait(w.set(np.arange(NK),
                     rng.normal(size=(NK, VLEN)).astype(np.float32)))
        srv.block()
        plane = ServePlane(srv)
        sess = plane.session()
        rec = []
        for step in range(STEPS):
            keys = rng.integers(0, NK, B)
            op = step % 6
            if op == 0:
                w.wait(w.push(np.unique(keys),
                              rng.normal(size=(len(np.unique(keys)),
                                               VLEN))
                              .astype(np.float32) * 0.1))
            elif op == 1:
                rec.append(w.pull_sync(keys))
            elif op == 2:
                w.wait(w.set(np.unique(keys),
                             rng.normal(size=(len(np.unique(keys)),
                                              VLEN))
                             .astype(np.float32)))
            elif op == 3:
                rec.append(sess.lookup(keys))
            else:
                # bag plane: sum and mean, alternating the dispatch
                # between the fused program and the host-pool fallback
                # — the four combinations must all agree across ports
                srv.opts.serve_bags = (step % 2 == 0)
                bg = np.arange(0, B + 1, B // NBAGS)
                (pooled,) = sess.lookup_bags(
                    [keys], [bg], pooling="sum" if op == 4 else "mean")
                rec.append(pooled)
            if step % 16 == 0 and srv.tier is not None:
                srv.tier.engine.kick()
        plane.close()
        srv.block()
        rec.append(w.pull_sync(np.arange(NK)))
        srv.shutdown()
        return rec
    finally:
        set_default_port(None)


def wire_records(port, mode: str) -> list:
    """Deterministic quantized-wire differential: one standalone
    tiered store on `port` (residency = slot index, no async
    maintenance), ingest rows across the hot/cold boundary, then read
    them back flat and pooled. Returns every array read."""
    from adapm_tpu.core.store import OOB, ShardedStore
    from adapm_tpu.parallel.mesh import make_mesh

    ctx = make_mesh()
    hot = 16
    rows_total = 64
    L = 8
    st = ShardedStore(rows_total * ctx.num_shards, L, ctx,
                      tier_hot_rows=hot, tier_cold_dtype=mode,
                      port=port)
    rng = np.random.default_rng(11)
    S = ctx.num_shards
    n = rows_total * S
    o_sh = np.tile(np.arange(S, dtype=np.int32), rows_total)
    o_sl = np.repeat(np.arange(rows_total, dtype=np.int32), S)
    c_sh = o_sh.copy()
    c_sl = np.full(n, OOB, np.int32)
    use_c = np.zeros(n, bool)
    st.set_rows(o_sh, o_sl,
                rng.normal(size=(n, L)).astype(np.float32) * 3.0,
                c_sh, c_sl)
    rec = [np.asarray(st.gather(o_sh, o_sl, c_sh, c_sl, use_c))[:n]]
    nbags = 8
    seg = (np.arange(n) % nbags).astype(np.int32)  # hot+cold per bag
    for pooling in ("sum", "mean"):
        rec.append(np.asarray(st.gather_pool(
            o_sh, o_sl, c_sh, c_sl, use_c, seg, nbags,
            pooling=pooling))[:nbags])
    return rec


def bag_ratio() -> float:
    """Median-pairwise fused/host-pool ratio at the satellite
    workload, measured store-level (no serve-plane noise)."""
    from adapm_tpu.core.store import OOB, ShardedStore
    from adapm_tpu.parallel.mesh import make_mesh
    from adapm_tpu.serve.bags import pool_bags_host

    ctx = make_mesh()
    st = ShardedStore(RATIO_E, RATIO_L, ctx)
    rng = np.random.default_rng(0)
    S = ctx.num_shards
    for lo in range(0, RATIO_E, 50_000):
        hi = min(lo + 50_000, RATIO_E)
        ks = np.arange(lo, hi)
        st.set_rows((ks % S).astype(np.int32),
                    (ks // S).astype(np.int32),
                    rng.normal(size=(hi - lo, RATIO_L))
                    .astype(np.float32),
                    (ks % S).astype(np.int32),
                    np.full(hi - lo, OOB, np.int32))
    n, nbags = RATIO_N, RATIO_NBAGS
    seg = np.repeat(np.arange(nbags), n // nbags).astype(np.int32)
    c_sh = np.zeros(n, np.int32)
    c_sl = np.full(n, OOB, np.int32)
    use_c = np.zeros(n, bool)

    def mk():
        ks = rng.integers(0, RATIO_E, n)
        return (ks % S).astype(np.int32), (ks // S).astype(np.int32)

    o_sh, o_sl = mk()   # warm both bucket compiles
    np.asarray(st.gather_pool(o_sh, o_sl, c_sh, c_sl, use_c, seg,
                              nbags))
    np.asarray(st.gather(o_sh, o_sl, c_sh, c_sl, use_c))
    pairs = []
    for _ in range(RATIO_REPEATS):
        o_sh, o_sl = mk()
        t0 = time.perf_counter()
        r1 = np.asarray(st.gather_pool(o_sh, o_sl, c_sh, c_sl, use_c,
                                       seg, nbags))[:nbags]
        t1 = time.perf_counter()
        rows = np.asarray(st.gather(o_sh, o_sl, c_sh, c_sl,
                                    use_c))[:n]
        r2 = pool_bags_host(rows, seg, nbags, "sum")
        t2 = time.perf_counter()
        assert np.array_equal(r1, r2), \
            "fused gather_pool != gather + host pool (bitwise)"
        pairs.append((t1 - t0) / (t2 - t1))
    pairs.sort()
    return pairs[len(pairs) // 2]


def main() -> int:
    rc = 0

    # -- confinement: the reference port must stay jax-free -------------
    ref_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "adapm_tpu", "device",
        "refport.py")
    with open(ref_path) as f:
        src = f.read()
    jax_imports = [ln for ln in src.splitlines()
                   if ln.strip().startswith(("import jax",
                                             "from jax"))]
    suppressions = src.count("apm-lint: disable")
    if jax_imports or suppressions:
        print(f"[portdiff-check] FAILED: device/refport.py must not "
              f"import jax ({len(jax_imports)} found) or suppress the "
              f"linter ({suppressions} found) — the reference port is "
              f"the executable spec precisely because it cannot touch "
              f"the device API (APM008)", file=sys.stderr)
        rc = 1

    # -- the port-differential storm ------------------------------------
    import jax

    from adapm_tpu.device.jaxport import JaxDevicePort
    from adapm_tpu.device.refport import NumpyRefPort

    t0 = time.perf_counter()
    rec_jax = storm(JaxDevicePort())
    rec_ref = storm(NumpyRefPort())
    t_storm = time.perf_counter() - t0
    mismatches = []
    if len(rec_jax) != len(rec_ref):
        mismatches.append(f"record count {len(rec_jax)} vs "
                          f"{len(rec_ref)}")
    else:
        for i, (a, b) in enumerate(zip(rec_jax, rec_ref)):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                mismatches.append(f"op {i}")
    if mismatches:
        print(f"[portdiff-check] FAILED: jax port and NumPy reference "
              f"port diverged (bitwise) at: "
              f"{', '.join(mismatches[:8])} — a device program no "
              f"longer matches its executable spec "
              f"(device/refport.py)", file=sys.stderr)
        rc = 1

    # -- quantized-wire differential (deterministic, store-level) -------
    wire_bad = []
    for mode in ("fp16", "int8"):
        wj = wire_records(JaxDevicePort(), mode)
        wr = wire_records(NumpyRefPort(), mode)
        for i, (a, b) in enumerate(zip(wj, wr)):
            if not np.array_equal(a, b):
                wire_bad.append(f"{mode}/read{i}")
    if wire_bad:
        print(f"[portdiff-check] FAILED: quantized wire programs "
              f"diverged between ports at: {', '.join(wire_bad)} — "
              f"the fp16/int8 ingest+dequant (or the fused pool over "
              f"cold wire rows) no longer matches the NumPy spec",
              file=sys.stderr)
        rc = 1

    # -- the fused-bag ratio guard --------------------------------------
    backend = jax.default_backend()
    default_max = "0.9" if backend not in ("cpu",) else "1.25"
    ratio_max = float(os.environ.get("ADAPM_BAG_RATIO_MAX",
                                     default_max))
    median = bag_ratio()
    print(f"[portdiff-check] storm: 2 ports x {STEPS} ops "
          f"({len(rec_jax)} recorded reads + final table) in "
          f"{t_storm:.1f}s, {len(mismatches)} mismatches | bag ratio "
          f"({backend}): median fused/hostpool {median:.3f} over "
          f"{RATIO_REPEATS} pairs at {RATIO_N}x{RATIO_L}->"
          f"{RATIO_NBAGS} bags (guard: < {ratio_max:.2f})")
    if median >= ratio_max:
        print(f"[portdiff-check] FAILED: the fused gather_pool program "
              f"costs {median:.3f}x the gather-then-host-pool path — "
              f"structural regression (per-bag re-gather? pooling off "
              f"the dispatch stream?); on CPU relax via "
              f"ADAPM_BAG_RATIO_MAX if the box is just noisy",
              file=sys.stderr)
        rc = 1
    if rc == 0:
        print("[portdiff-check] OK")
    return rc


if __name__ == "__main__":
    sys.exit(main())
