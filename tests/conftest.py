"""Test harness: multi-device without a cluster.

The reference tests launch N server processes + a scheduler on localhost via
tracker/dmlc_local.py (SURVEY.md §4). Here "multi-node" = an 8-device virtual
CPU mesh (XLA host-platform device count), which exercises the same sharded
programs the TPU path compiles. Must run before jax is imported anywhere.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    from xla_compat import mesh_flags

    # 8-virtual-device mesh + the XLA CPU collective watchdog timeouts
    os.environ["XLA_FLAGS"] = " ".join([flags, mesh_flags(8)]).strip()
# XLA's CPU client runs each device's part of a program on a thread of
# ONE pool, sized to the cores (here as many as the mesh has devices),
# and a part sits on its thread until every device has joined its
# collective: with nothing to spare, `tests/test_apps.py::test_mf_app`
# hung for good in half the whole runs under six workers (PR 38, the
# parent's tree too). More threads than devices, as a chip's host has
os.environ.setdefault("PJRT_NPROC", "32")

from adapm_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

# persistent compilation cache: amortize XLA compiles across pytest
# sessions (JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache)
enable_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _drop_lockorder_sentinel():
    """The lock-order sentinel (lint/lockorder.py) is process-global:
    any test that builds a --sys.lint.lockorder server installs it.
    Tear it down after EVERY test so a sentinel enabled (or a storm
    that failed before its own disable call) never leaks acquisition
    edges into unrelated tests."""
    yield
    from adapm_tpu.lint import lockorder
    lockorder.disable_sentinel()


@pytest.fixture
def compiled_in_process():
    """The test's programs are compiled by its own process; none is
    loaded from jax's persistent compilation cache. On this image's XLA
    CPU client the 8-shard KGE step of
    `test_kge_lowrank_reaches_truth_ceiling_fraction`, LOADED from the
    cache, aborts within a few dozen steps in a collective's rendezvous
    (`rendezvous.h: Check failed: id < num_threads (8 vs. 8)`, SIGABRT or
    SIGSEGV, the xdist worker goes down), every time; compiled in
    process it never has, and a seed's run is then the same to the last
    digit, alone and beside busy processes (PR 44; ROADMAP C0)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture
def kernel_cache(tmp_path):
    """jax's compilation cache directory, where `ops/writeback.py` keeps
    the exported write-back kernel, pointed at an empty one; yields the
    kernels' directory in it."""
    import jax

    from adapm_tpu.ops import writeback
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    writeback.exported_kernel.cache_clear()
    yield tmp_path / "adapm_kernels"
    writeback.exported_kernel.cache_clear()
    jax.config.update("jax_compilation_cache_dir", before)
