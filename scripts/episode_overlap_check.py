"""Episodic-execution guard (ISSUE 14 satellite; run by
scripts/run_tests.sh — the exec_overlap_check pattern applied to the
episode/episode_commit stream pair).

Three assertions a regression would break silently:

1. **Idle dispatches nothing.** After the episodic runs settle, the
   executor must start ZERO programs and the stores must dispatch ZERO
   gathers over an idle second — episode prep work exists only while
   `EpisodicRunner.run` drives it; nothing polls.

2. **Episodic keeps up with sequential.** A beyond-hot-capacity zipf
   fused-step workload (every batch carries cold rows, so each
   sequential step pays its forced promotion inline) must run
   episodically at least as fast as plain sequential runner calls,
   within noise. Methodology: MEDIAN-pairwise ratio — (episodic,
   sequential) timed back to back per repeat, guard on the median
   episodic/sequential wall ratio < 1.35 (ADAPM_EPISODE_RATIO_MAX).
   The structural failure mode — a commit joined before the next prep
   starts, a prep blocking on device execution, or the episode streams
   serializing behind a held lock — costs a MULTIPLE, pushing every
   pair well above 1; on this shared 2-core container individual pairs
   swing with scheduler noise, so the guard is on the median and sized
   for that noise (recorded medians < 1.0: prep genuinely overlaps).

3. **Overlap is real.** The episodic server must record
   exec.overlap_fraction > 0 — prep (`episode` stream) genuinely ran
   while a commit (`episode_commit`) was active.
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

NK = 8192
D = 8                # embedding dim; row length 2*D
B = 128              # keys per role per batch
BATCHES = 32         # per timed repeat
EPISODE = 4          # batches per episode
REPEATS = 5
SKEW = 8


def build():
    import jax
    import jax.numpy as jnp

    import adapm_tpu
    from adapm_tpu.config import SystemOptions
    from adapm_tpu.ops import DeviceRoutedRunner

    jax.config.update("jax_platforms", "cpu")
    S = len(jax.devices())

    def loss_fn(embs, aux):
        return jnp.mean(jnp.sum(embs["a"] * embs["b"], axis=-1))

    srv = adapm_tpu.setup(NK, 2 * D, opts=SystemOptions(
        sync_max_per_sec=0, prefetch=False,
        tier=True, tier_hot_rows=max(8, NK // 4 // S),
        episode_batches=EPISODE))
    w = srv.make_worker(0)
    init = np.random.default_rng(1).normal(
        size=(NK, 2 * D)).astype(np.float32)
    init[:, D:] = np.abs(init[:, D:]) + 1e-3
    w.wait(w.set(np.arange(NK), init))
    srv.block()
    runner = DeviceRoutedRunner(srv, loss_fn, {"a": 0, "b": 0},
                                {"a": D, "b": D}, shard=0, seed=5)
    return srv, runner


def schedule(rng, n):
    def keys():
        return (NK * rng.random(B) ** SKEW).astype(np.int64) \
            .clip(0, NK - 1)
    return [{"a": keys(), "b": keys()} for _ in range(n)]


def run_episodic(srv, ep, batches) -> float:
    t0 = time.perf_counter()
    losses = ep.run(batches, lr=1e-3)
    float(losses[-1])
    srv.exec.drain("episode_commit", timeout=60)
    srv.block()
    return time.perf_counter() - t0


def run_sequential(srv, runner, batches) -> float:
    t0 = time.perf_counter()
    loss = None
    for b in batches:
        loss = runner(b, None, 1e-3)
    float(loss)
    srv.block()
    return time.perf_counter() - t0


def main() -> int:
    from adapm_tpu.device import EpisodicRunner
    ratio_max = float(os.environ.get("ADAPM_EPISODE_RATIO_MAX", "1.35"))
    rng = np.random.default_rng(7)

    srv_e, run_e = build()
    srv_s, run_s = build()
    ep = EpisodicRunner(run_e)

    # warm both (compiles the step variants + tier paths)
    warm = schedule(rng, 8)
    run_episodic(srv_e, ep, warm)
    run_sequential(srv_s, run_s, warm)

    pairs = []
    for _ in range(REPEATS):
        batches = schedule(rng, BATCHES)
        t_epi = run_episodic(srv_e, ep, batches)
        t_seq = run_sequential(srv_s, run_s, batches)
        pairs.append(t_epi / t_seq)
    overlap_frac = srv_e.exec.overlap_fraction()

    # -- idle guard: nothing polls between runs -------------------------
    time.sleep(0.1)
    p0 = srv_e.exec.stats()["programs_started"]
    g0 = sum(s.gathers for s in srv_e.stores)
    time.sleep(1.0)
    p1 = srv_e.exec.stats()["programs_started"]
    g1 = sum(s.gathers for s in srv_e.stores)
    idle_ok = (p1 == p0) and (g1 == g0)

    srv_e.shutdown()
    srv_s.shutdown()
    pairs.sort()
    median = pairs[len(pairs) // 2]
    print(f"[episode-check] {BATCHES} batches x {REPEATS} pairs, "
          f"episodes of {EPISODE}, beyond-hot-capacity zipf: "
          f"episodic/sequential ratios min {pairs[0]:.3f} / median "
          f"{median:.3f} / max {pairs[-1]:.3f} (guard: median < "
          f"{ratio_max:.2f}) | overlap_fraction {overlap_frac:.3f} | "
          f"idle: programs {p1 - p0:+d}, gathers {g1 - g0:+d}")
    rc = 0
    if median >= ratio_max:
        print("[episode-check] FAILED: episodic execution no longer "
              "keeps up with sequential — check that commits are "
              "submitted BEFORE the next episode's prep runs and that "
              "prep enqueues promotions without blocking on device "
              "execution", file=sys.stderr)
        rc = 1
    if overlap_frac <= 0.0:
        print("[episode-check] FAILED: exec.overlap_fraction stayed 0 "
              "— the episode and episode_commit streams never ran "
              "simultaneously; double-buffering is broken",
              file=sys.stderr)
        rc = 1
    if not idle_ok:
        print("[episode-check] FAILED: an idle server started programs "
              "or dispatched gathers after the episodic runs settled",
              file=sys.stderr)
        rc = 1
    if rc == 0:
        print("[episode-check] OK")
    return rc


if __name__ == "__main__":
    sys.exit(main())
