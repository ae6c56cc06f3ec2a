"""Bring-up contract (ISSUE 21): the pieces that decide whether a run
can pass without the chip, checked on the CPU.

- the placeable compile cache (utils/compile_cache.py): an outside
  JAX_COMPILATION_CACHE_DIR is left alone; otherwise one fixed directory
  inside the checkout, whatever the working directory;
- pools are allocated IN their sharding (device/jaxport.py alloc_pool);
- `chip_smoke.py` refuses a CPU by name, and the explicit rehearsal
  still runs end to end;
- a failed native-router build is reported, not silent.
"""
import json
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_argv, env_extra=None, drop=(), cwd=REPO, timeout=300):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    for k in drop:
        env.pop(k, None)
    env.update(env_extra or {})
    argv = [sys.executable, "-c", code_or_argv] \
        if isinstance(code_or_argv, str) else [sys.executable, *code_or_argv]
    return subprocess.run(argv, env=env, cwd=cwd, capture_output=True,
                          text=True, timeout=timeout)


_CACHE_CODE = ("import jax; "
               "from adapm_tpu.utils.compile_cache import "
               "enable_compile_cache as e; "
               "print(e()); print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_outside_dir_left_alone(tmp_path):
    outside = str(tmp_path / "outside_cache")
    p = _run(_CACHE_CODE, {"JAX_COMPILATION_CACHE_DIR": outside})
    assert p.returncode == 0, p.stderr
    assert p.stdout.split() == [outside, outside]


def test_compile_cache_default_is_fixed_in_checkout(tmp_path):
    """Two different working directories, one path: <repo>/.jax_cache."""
    seen = set()
    for name in ("a", "b"):
        cwd = tmp_path / name
        cwd.mkdir()
        p = _run(_CACHE_CODE, drop=("JAX_COMPILATION_CACHE_DIR",),
                 cwd=str(cwd))
        assert p.returncode == 0, p.stderr
        seen.update(p.stdout.split())
    assert seen == {os.path.join(REPO, ".jax_cache")}


def test_alloc_pool_is_sharded_at_birth():
    """Every pool of a fresh server is 8 shards of [1, slots, L] on the
    8 devices of the virtual mesh, zero-filled."""
    import jax

    import adapm_tpu
    lens = np.full(96, 16, dtype=np.int64)
    lens[64:] = 3
    srv = adapm_tpu.setup(96, lens)
    try:
        S = len(jax.devices())
        assert S == 8 and srv.num_shards == S
        for st in srv.stores:
            for name in ("main", "cache", "delta"):
                a = getattr(st, name)
                shards = a.addressable_shards
                assert len(shards) == S
                assert {s.device.id for s in shards} == set(range(S))
                assert {tuple(s.data.shape) for s in shards} == \
                    {(1,) + tuple(a.shape[1:])}
                assert not np.asarray(a).any()
    finally:
        srv.shutdown()


def test_chip_smoke_refuses_cpu_without_rehearsal_flag():
    p = _run(["chip_smoke.py"])
    assert p.returncode not in (0, None)
    assert "no TPU" in p.stderr and "'cpu'" in p.stderr
    # no result line of any kind
    assert "{" not in p.stdout


def test_chip_smoke_rehearsal_runs_end_to_end():
    """The explicit CPU rehearsal (one device, tiny sizes, interpret-mode
    kernels) keeps the smoke itself from rotting between chip runs."""
    p = _run(["chip_smoke.py", "--rehearse-cpu"], {"XLA_FLAGS": ""})
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert out == {"ok": True, "rehearsal": True,
                   "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert all(ln.startswith("platform=cpu | ") for ln in lines[:-1])


def test_native_build_failure_is_reported(tmp_path):
    """A broken toolchain (no g++ on PATH, empty build cache) yields the
    numpy path AND one report carrying the compiler command."""
    p = _run("from adapm_tpu import native; "
             "print(native.get_lib(), native.get_lib())",
             {"PATH": str(tmp_path), "ADAPM_NATIVE_CACHE": str(tmp_path)},
             drop=("ADAPM_NO_NATIVE",))
    assert p.returncode == 0, p.stderr
    assert p.stdout.split() == ["None", "None"]
    assert p.stderr.count("router library unavailable") == 1
    assert "g++" in p.stderr
