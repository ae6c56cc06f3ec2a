"""Compression-plane drift + bytes guard (ISSUE 8 satellite; run by
scripts/run_tests.sh).

Three checks over the compression co-design (tier/quant.py,
core/store.py `_sync_replicas_compressed`, docs/MEMORY.md "Cold-row
numeric contract"):

1. BIT-IDENTITY PIN: with both features OFF (`--sys.tier.cold_dtype
   fp32`, `--sys.sync.compress off`) the randomized
   push/promote/demote/sync storm reads BIT-identically to an untiered
   fp32 shadow at every step and after quiesce — the pre-PR behavior,
   byte accounting recording full-width rows. A regression here means
   the compression plane leaked into the exact path.

2. DRIFT BOUND: the same storm at fp16 and int8 (quantized cold store
   + compressed sync, the worst case — every lossy surface at once)
   must keep every read within the documented contract bound: two grid
   steps of the row's max-abs (one for the at-rest rounding, one for a
   parked EF residual's worth of slack). The error-feedback loop is
   what makes this a BOUND rather than a random walk — without it,
   repeated promote/demote/sync cycles accumulate bias and the final
   read drifts past the bar.

3. BYTES/ROUND: across the storm's sync rounds the compressed server's
   shipped wire bytes must be <= 0.55x (fp16) / 0.30x (int8) of the
   fp32 shadow's for the SAME dirty population (ADAPM_COMPRESS_FP16_MAX
   / ADAPM_COMPRESS_INT8_MAX override). The expected ratios are the
   wire-format ratios themselves (0.5 / ~0.28); the failure mode — a
   path quietly shipping full-width rows — lands at 1.0.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    from xla_compat import mesh_flags
    os.environ["XLA_FLAGS"] = " ".join([_flags, mesh_flags(2)]).strip()

import numpy as np  # noqa: E402

E = 384
# value length matches the mgmt-phase workload the acceptance ratios
# are defined on: int8's fixed 2-byte scale column costs (L+2)/4L, i.e.
# 0.281x at L=16 but 0.3125x at L=8 — shorter rows dilute the format
L = 16
STEPS = 25


def _build(mode: str):
    """(tiered server in `mode`, untiered fp32 shadow). REPLICATION_ONLY
    + a cache pool sized for the whole replica set: the bytes/round
    comparison needs both servers shipping the SAME dirty population
    (relocation decisions and slot-capacity evictions would let the two
    storms diverge structurally)."""
    import adapm_tpu
    from adapm_tpu.base import MgmtTechniques
    from adapm_tpu.config import SystemOptions

    common = dict(sync_max_per_sec=0, prefetch=False,
                  techniques=MgmtTechniques.REPLICATION_ONLY,
                  cache_slots_per_shard=128)
    srv = adapm_tpu.setup(E, L, opts=SystemOptions(
        tier=True, tier_hot_rows=16, tier_cold_dtype=mode,
        sync_compress="off" if mode == "fp32" else mode, **common))
    ref = adapm_tpu.setup(E, L, opts=SystemOptions(**common))
    return srv, ref


def _grid_tol(mode: str, rows: np.ndarray) -> np.ndarray:
    """The documented per-row bound (docs/MEMORY.md): two grid steps of
    the row's max-abs."""
    from adapm_tpu.tier.quant import grid_step
    return 2.0 * grid_step(mode, rows) + 1e-6


def run_storm(mode: str):
    """Randomized push/promote/demote/sync storm vs the fp32 shadow.
    Returns (max observed drift, worst drift/bound ratio, shipped
    bytes, shadow full-width bytes). mode == "fp32" asserts bitwise
    equality instead of the bound."""
    from adapm_tpu.base import CLOCK_MAX

    srv, ref = _build(mode)
    w, wr = srv.make_worker(0), ref.make_worker(0)
    rng = np.random.default_rng(11)
    vals = rng.normal(size=(E, L)).astype(np.float32)
    w.set(np.arange(E), vals)
    wr.set(np.arange(E), vals)
    keys = np.arange(E)
    # long-lived replicas of non-local keys: the sync rounds must ship
    # real deltas for the bytes/round comparison to mean anything
    repl = keys[srv.ab.owner[keys] != w.shard][:64]
    for ww, ss in ((w, srv), (wr, ref)):
        ww.intent(repl, 0, CLOCK_MAX)
        ss.sync.run_round(force_intents=True, all_channels=True)
    b0 = sum(st.sync_bytes_shipped for st in srv.stores)
    f0 = sum(st.sync_bytes_shipped for st in ref.stores)
    worst_drift, worst_ratio = 0.0, 0.0
    for step in range(STEPS):
        op = rng.integers(0, 4)
        if op == 0:
            ks = np.concatenate([rng.integers(0, E, 16),
                                 rng.choice(repl, 8, replace=False)])
            v = rng.normal(size=(24, L)).astype(np.float32)
            w.push(ks, v)
            wr.push(ks, v)
        elif op == 1:
            srv.tier.promote_keys(rng.choice(E, 32, replace=False))
        elif op == 2:
            srv.tier.demote_keys(rng.choice(E, 32, replace=False))
            srv.tier.maintain()
        else:
            srv.sync.run_round(force_intents=True, all_channels=True)
            ref.sync.run_round(force_intents=True, all_channels=True)
        a = np.asarray(srv.read_main(keys)).reshape(E, L)
        b = np.asarray(ref.read_main(keys)).reshape(E, L)
        if mode == "fp32":
            if not np.array_equal(a, b):
                print(f"[compress-check] FAILED: fp32/off storm step "
                      f"{step} (op {op}) diverged from the untiered "
                      f"shadow — the exact path is no longer "
                      f"bit-identical to pre-PR behavior",
                      file=sys.stderr)
                srv.shutdown()
                ref.shutdown()
                sys.exit(1)
        else:
            drift = np.abs(a - b).max(axis=1)
            tol = _grid_tol(mode, b)
            worst_drift = max(worst_drift, float(drift.max()))
            worst_ratio = max(worst_ratio, float((drift / tol).max()))
            if (drift > tol).any():
                print(f"[compress-check] FAILED: {mode} storm step "
                      f"{step} (op {op}) drifted {drift.max():.3g} > "
                      f"contract bound {tol[drift.argmax()]:.3g} — the "
                      f"EF residual loop is not bounding the error "
                      f"(tier/quant.py / store."
                      f"_sync_replicas_compressed)", file=sys.stderr)
                srv.shutdown()
                ref.shutdown()
                sys.exit(1)
    # bytes measured BEFORE quiesce: the quiesce flush is exact
    # (full-width) BY DESIGN and would dilute the wire ratio
    shipped = sum(st.sync_bytes_shipped for st in srv.stores) - b0
    full = sum(st.sync_bytes_shipped for st in ref.stores) - f0
    # final read after quiesce stays under the same bound (fp32: exact)
    srv.quiesce()
    ref.quiesce()
    a = np.asarray(srv.read_main(keys)).reshape(E, L)
    b = np.asarray(ref.read_main(keys)).reshape(E, L)
    if mode == "fp32":
        if not np.array_equal(a, b):
            print("[compress-check] FAILED: fp32/off post-quiesce read "
                  "diverged", file=sys.stderr)
            sys.exit(1)
    else:
        drift = np.abs(a - b).max(axis=1)
        tol = _grid_tol(mode, b)
        worst_drift = max(worst_drift, float(drift.max()))
        if (drift > tol).any():
            print(f"[compress-check] FAILED: {mode} final read drifted "
                  f"{drift.max():.3g} past the contract bound",
                  file=sys.stderr)
            sys.exit(1)
    srv.shutdown()
    ref.shutdown()
    return worst_drift, worst_ratio, shipped, full


def main() -> int:
    caps = {"fp16": float(os.environ.get("ADAPM_COMPRESS_FP16_MAX",
                                         "0.55")),
            "int8": float(os.environ.get("ADAPM_COMPRESS_INT8_MAX",
                                         "0.30"))}

    # -- 1. both features off: bit-identical to pre-PR ---------------------
    run_storm("fp32")
    print(f"[compress-check] fp32/off: {STEPS}-step storm + quiesce "
          f"bit-identical to the untiered shadow (pre-PR pin)")

    # -- 2+3. quantized storms: drift bound + bytes/round ------------------
    for mode in ("fp16", "int8"):
        drift, ratio, shipped, full = run_storm(mode)
        byte_ratio = shipped / full if full else None
        print(f"[compress-check] {mode}: worst drift {drift:.3g} "
              f"({ratio:.2f}x of the contract bound), sync bytes "
              f"{shipped}/{full} = {byte_ratio:.4f}x fp32 "
              f"(cap {caps[mode]})")
        if full == 0:
            print(f"[compress-check] FAILED: {mode} storm shipped no "
                  f"sync bytes — the rounds never exercised the "
                  f"compressed program", file=sys.stderr)
            return 1
        if byte_ratio > caps[mode]:
            print(f"[compress-check] FAILED: {mode} sync shipped "
                  f"{byte_ratio:.4f}x of the fp32 shadow's bytes "
                  f"(cap {caps[mode]}) — a path is shipping "
                  f"full-width rows under compression", file=sys.stderr)
            return 1
    print("[compress-check] OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
