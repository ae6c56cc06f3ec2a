"""Intent-driven prefetch pipeline + routing-plan cache (r6 tentpole).

Tier-1 coverage for core/intent.py's PrefetchScheduler/PlanCache and the
Server._topology_mutation discipline they revalidate against:

  - staged-hit correctness: a pull served from a pre-gathered staged
    buffer is BIT-identical to the plain pull it replaced;
  - read-your-writes through a staged buffer (push/set between staging
    and consumption invalidates + re-stages);
  - staleness invalidation when a relocation lands between staging and
    consumption (topology_version revalidation at take time);
  - plan-cache hits for repeated batches and invalidation on a
    topology_version bump;
  - the addressbook-mutation discipline assertion (ADVICE r5 #1);
  - staging-pool bounds and the auto pull-gating;
  - control-plane payload framing (ADVICE r5 #2).
"""
import numpy as np
import pytest

from adapm_tpu import Server, SystemOptions, make_mesh


@pytest.fixture(scope="module")
def ctx():
    return make_mesh(8)


def make_server(ctx, num_keys=64, vlen=4, **kw):
    opts = kw.pop("opts", None) or SystemOptions(prefetch_pull="always")
    return Server(num_keys, vlen, opts=opts, ctx=ctx, **kw)


def _seed(w, keys, base=0.0):
    vals = (np.arange(len(keys) * 4, dtype=np.float32)
            .reshape(len(keys), 4) + base)
    w.wait(w.set(keys, vals))
    return vals


def _stage(s, w, keys, horizon=50):
    """Declare intent for `keys` now and wait for the pipeline to stage."""
    w.intent(keys, w.current_clock, w.current_clock + horizon)
    s.prefetch.flush()


def test_staged_pull_bit_identical(ctx):
    s = make_server(ctx)
    w = s.make_worker(0)
    keys = np.unique(np.array([1, 5, 9, 17, 33]))
    vals = _seed(w, keys)
    _stage(s, w, keys)
    assert s.prefetch.report()["live"] == 1
    got = w.pull_sync(keys)
    assert s.prefetch.stats["hits"] == 1
    # bit-identical, not merely close: the staged gather is the same
    # program over the same pools the plain pull would have run
    assert (got == vals).all()
    # a second pull has no staged entry left: plain path, same values
    assert (w.pull_sync(keys) == vals).all()
    s.shutdown()


def test_read_your_writes_through_staged(ctx):
    s = make_server(ctx)
    w = s.make_worker(0)
    keys = np.unique(np.array([2, 10, 18]))
    vals = _seed(w, keys)
    _stage(s, w, keys)
    # overlapping push AFTER staging: the staged buffer must not serve
    # the pre-write values
    w.wait(w.push(keys, np.ones((3, 4), np.float32)))
    assert s.prefetch.stats["invalidated_write"] >= 1
    s.prefetch.flush()  # the pipeline re-stages in the background
    got = w.pull_sync(keys)
    assert (got == vals + 1.0).all()
    s.shutdown()


def test_set_invalidates_staged(ctx):
    s = make_server(ctx)
    w = s.make_worker(0)
    keys = np.unique(np.array([3, 11]))
    _seed(w, keys)
    _stage(s, w, keys)
    new = np.full((2, 4), 7.5, np.float32)
    w.wait(w.set(keys, new))
    s.prefetch.flush()
    assert (w.pull_sync(keys) == new).all()
    s.shutdown()


def test_disjoint_write_keeps_staged(ctx):
    s = make_server(ctx)
    w = s.make_worker(0)
    keys = np.unique(np.array([4, 12]))
    vals = _seed(w, keys)
    _stage(s, w, keys)
    w.wait(w.push(np.array([40, 48]), np.ones((2, 4), np.float32)))
    assert s.prefetch.report()["live"] == 1  # disjoint: entry survives
    assert (w.pull_sync(keys) == vals).all()
    assert s.prefetch.stats["hits"] == 1
    s.shutdown()


def test_relocation_between_stage_and_pull(ctx):
    """A relocation landing between staging and consumption must fail the
    staged buffer's revalidation (the moved row may fold in a stale
    replica base); the pull then replans and returns current values."""
    s = make_server(ctx)
    w = s.make_worker(0)
    keys = np.unique(np.array([1, 9, 25]))  # home shard 1
    vals = _seed(w, keys)
    _stage(s, w, keys)
    assert s.prefetch.report()["live"] == 1
    moved = s._relocate_to(keys, 3)
    assert moved == len(keys)
    got = w.pull_sync(keys)
    assert (got == vals).all()
    assert s.prefetch.stats["invalidated_topology"] >= 1
    assert s.prefetch.stats["hits"] == 0
    s.shutdown()


def test_plan_cache_hits_and_topology_invalidation(ctx):
    s = make_server(ctx)
    w = s.make_worker(0)
    keys = np.unique(np.array([6, 14, 22]))
    vals = _seed(w, keys)
    h0 = s._plan_cache.hits
    assert (w.pull_sync(keys) == vals).all()
    assert (w.pull_sync(keys) == vals).all()  # same batch: cached plan
    assert s._plan_cache.hits > h0
    st0 = s._plan_cache.stale
    s._relocate_to(keys, 5)  # topology bump invalidates the entry
    assert (w.pull_sync(keys) == vals).all()
    assert s._plan_cache.stale > st0
    s.shutdown()


def test_plan_cache_push_routes(ctx):
    s = make_server(ctx)
    w = s.make_worker(0)
    keys = np.unique(np.array([7, 15]))
    _seed(w, keys, base=0.0)
    one = np.ones((2, 4), np.float32)
    for _ in range(3):  # repeated push batches ride the cached skeleton
        w.wait(w.push(keys, one))
    expect = (np.arange(8, dtype=np.float32).reshape(2, 4) + 3.0)
    assert (w.pull_sync(keys) == expect).all()
    s.shutdown()


def test_plan_cache_collision_is_exact(ctx):
    """Same-length different-key batches must never share a plan."""
    s = make_server(ctx)
    w = s.make_worker(0)
    a = np.unique(np.array([8, 16, 24]))
    b = np.unique(np.array([9, 17, 25]))
    va = _seed(w, a, base=0.0)
    vb = _seed(w, b, base=100.0)
    for _ in range(2):
        assert (w.pull_sync(a) == va).all()
        assert (w.pull_sync(b) == vb).all()
    s.shutdown()


def test_topology_mutation_discipline(ctx):
    """An addressbook mutation outside _topology_mutation() is caught by
    the discipline assertion (ADVICE r5 #1)."""
    s = make_server(ctx)
    with s._lock:
        with s._topology_mutation():
            cs = s.ab.add_replicas(np.array([1]), 0)  # paired: fine
            assert len(cs) == 1
        v = s.topology_version
        s.ab.add_replicas(np.array([2]), 0)  # UNPAIRED mutation
        with pytest.raises(AssertionError, match="outside"):
            with s._topology_mutation():
                pass
        assert s.topology_version == v  # the failed section did not bump
    s.shutdown()


def test_topology_mutation_cancel(ctx):
    s = make_server(ctx)
    v = s.topology_version
    with s._topology_mutation() as tm:
        tm.cancel()  # mutated nothing
    assert s.topology_version == v
    with s._topology_mutation():
        pass  # uncancelled: bumps even without ab mutations (restore path)
    assert s.topology_version == v + 1
    s.shutdown()


def test_staging_pool_bounds_memory(ctx):
    opts = SystemOptions(prefetch_pull="always", prefetch_staging_rows=4)
    s = make_server(ctx, opts=opts)
    w = s.make_worker(0)
    keys = np.arange(32)  # bucket of 32 rows > 4-row budget
    vals = _seed(w, keys)
    _stage(s, w, keys)
    assert s.prefetch.report()["live"] == 0
    assert s.prefetch.stats["pool_full"] >= 1
    assert (w.pull_sync(keys) == vals).all()  # plain path, still right
    s.shutdown()


def test_prefetch_pull_auto_gating(ctx):
    """auto mode stages only for workers that actually use the Pull API
    (fused-runner loops never pull; staging for them is wasted work)."""
    s = make_server(ctx, opts=SystemOptions())  # prefetch_pull="auto"
    w = s.make_worker(0)
    keys = np.unique(np.array([5, 13]))
    vals = _seed(w, keys)
    _stage(s, w, keys)
    assert s.prefetch.report()["live"] == 0  # never pulled: not staged
    assert (w.pull_sync(keys) == vals).all()
    _stage(s, w, keys)  # now a known Pull user
    assert s.prefetch.report()["live"] == 1
    assert (w.pull_sync(keys) == vals).all()
    s.shutdown()


def test_staged_entry_expires_with_clock(ctx):
    s = make_server(ctx)
    w = s.make_worker(0)
    keys = np.unique(np.array([20, 28]))
    vals = _seed(w, keys)
    w.intent(keys, w.current_clock, w.current_clock)  # end = now
    s.prefetch.flush()
    w.advance_clock()  # window passed
    w.advance_clock()
    s.prefetch.pump(0)  # wake the expiry sweep
    s.prefetch.flush()
    assert s.prefetch.report()["live"] == 0
    assert (w.pull_sync(keys) == vals).all()
    s.shutdown()


def test_drive_rounds_delegates_planner(ctx):
    """drive_rounds with the pipeline on runs planner rounds on the
    background thread: intents still get acted on (replication or
    relocation makes the keys local to the worker's shard)."""
    s = make_server(ctx)
    w = s.make_worker(0)
    keys = np.unique(np.array([3, 11, 19]))  # home shard 3
    _seed(w, keys)
    assert not s.ab.is_local(keys, w.shard).any()
    w.intent(keys, w.current_clock, w.current_clock + 10)
    s.drive_rounds()
    s.prefetch.flush()
    assert s.ab.is_local(keys, w.shard).all()
    assert s.prefetch.stats["rounds_driven"] >= 1
    s.shutdown()


def test_runner_staged_keys(ctx):
    """DeviceRoutedRunner.prefetch_keys: staged uploads feed the step;
    a handle for a different batch is rejected."""
    from adapm_tpu.models import make_kge_loss
    from adapm_tpu.ops import DeviceRoutedRunner

    s = make_server(ctx, num_keys=40, vlen=8)
    w = s.make_worker(0)
    w.wait(w.set(np.arange(40),
                 np.full((40, 8), 0.1, np.float32)))
    runner = DeviceRoutedRunner(
        s, make_kge_loss("complex"),
        role_class={"s": 0, "r": 0, "o": 0, "neg": 0},
        role_dim={k: 4 for k in ("s", "r", "o", "neg")})
    rng = np.random.default_rng(0)
    roles = {k: rng.integers(0, 40, 8).astype(np.int64)
             for k in ("s", "r", "o", "neg")}
    stg = runner.prefetch_keys(roles)
    loss = runner(roles, None, 0.1, staged=stg)
    assert np.isfinite(float(loss))
    other = {k: (v + 1) % 40 for k, v in roles.items()}
    with pytest.raises(ValueError, match="staged keys differ"):
        runner(other, None, 0.1, staged=stg)
    s.shutdown()


def test_fused_step_invalidates_staged(ctx):
    """The fused step is a batched Push in PM terms: it must invalidate
    staged pull buffers covering the trained keys (review finding r6)."""
    from adapm_tpu.models import make_kge_loss
    from adapm_tpu.ops import DeviceRoutedRunner

    s = make_server(ctx, num_keys=40, vlen=8)  # row = [emb 4 | acc 4]
    w = s.make_worker(0)
    w.wait(w.set(np.arange(40), np.full((40, 8), 0.1, np.float32)))
    runner = DeviceRoutedRunner(
        s, make_kge_loss("complex"),
        role_class={"s": 0, "r": 0, "o": 0, "neg": 0},
        role_dim={k: 4 for k in ("s", "r", "o", "neg")}, shard=w.shard)
    uk = np.unique(np.array([1, 2, 3, 4]))
    _stage(s, w, uk)
    assert s.prefetch.report()["live"] == 1
    runner({"s": uk, "r": uk, "o": uk, "neg": uk}, None, 0.5)
    assert s.prefetch.stats["invalidated_write"] >= 1
    got = w.pull_sync(uk)
    expect = s.read_main(uk).reshape(4, 8)
    assert (got == expect).all()
    assert not np.allclose(got, 0.1)  # the step really moved the rows
    s.shutdown()


def test_control_payload_framing():
    """ADVICE r5 #2: dtype/shape ride the payload; mismatches raise."""
    from adapm_tpu.parallel.control import _pack_array, _unpack_array

    arr = np.arange(6, dtype=np.float64).reshape(2, 3)
    out = _unpack_array(_pack_array(arr), arr, "t")
    assert out.dtype == arr.dtype and (out == arr).all()
    out[0, 0] = -1  # writable copy

    # byte-order-free dtypes whose .str BEGINS with '|' (bool, uint8):
    # the header separator must not collide with them
    for dt in (np.bool_, np.uint8):
        a = np.array([1, 0, 1, 1]).astype(dt)
        got = _unpack_array(_pack_array(a), a, "t")
        assert got.dtype == a.dtype and (got == a).all()

    # same nbytes, different dtype: the silent-reinterpret case
    as_int = arr.astype(np.int64)
    with pytest.raises(ValueError, match="disagree"):
        _unpack_array(_pack_array(as_int), arr, "t")
    # same dtype, different shape
    with pytest.raises(ValueError, match="disagree"):
        _unpack_array(_pack_array(arr.reshape(3, 2)), arr, "t")
    # truncated body
    with pytest.raises(ValueError, match="bytes"):
        _unpack_array(_pack_array(arr)[:-8], arr, "t")


def test_prefetch_config_knobs():
    import argparse

    from adapm_tpu.config import SystemOptions as SO

    p = argparse.ArgumentParser()
    SO.add_arguments(p)
    args = p.parse_args([
        "--sys.prefetch", "0", "--sys.prefetch.max_batches", "2",
        "--sys.prefetch.staging_rows", "1024",
        "--sys.prefetch.pull", "always", "--sys.plan_cache", "16"])
    opts = SO.from_args(args)
    assert opts.prefetch is False  # the kill switch
    assert opts.prefetch_max_batches == 2
    assert opts.prefetch_staging_rows == 1024
    assert opts.prefetch_pull == "always"
    assert opts.plan_cache_entries == 16
    # defaults: pipeline on
    d = p.parse_args([])
    assert SO.from_args(d).prefetch is True


def test_kill_switch_disables_pipeline(ctx):
    s = make_server(ctx, opts=SystemOptions(prefetch=False,
                                            plan_cache_entries=0))
    assert s.prefetch is None and s._plan_cache is None
    w = s.make_worker(0)
    keys = np.unique(np.array([1, 2, 3]))
    vals = _seed(w, keys)
    w.intent(keys, w.current_clock, w.current_clock + 5)
    assert (w.pull_sync(keys) == vals).all()
    s.drive_rounds()  # inline fallback
    s.shutdown()
