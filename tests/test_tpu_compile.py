"""Compile for a v5e that is described, not attached: what the chip's
compiler (Mosaic included) accepts and how much memory the program
wants, at the benchmark cells' own sizes, with no chip time. Nothing
runs, so nothing here is a result or a time. The topology is described
inside a fixture (never at import: only one process may load the TPU's
library, and every pytest worker imports this file), and all such tests
live in this one file."""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

L = 2048  # both cells: rows of 8 KB


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


@pytest.fixture(autouse=True)
def _no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one (it would warn every time)
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("adagrad, n_slots, n, row", [
    (False, 1_172_432, 131_072, L),  # the plain form at the KGE cell's shape
    # the AdaGrad form at every size the one-chip training cells call it
    (True, 1_172_432, 131_072, L), (True, 1_172_432, 4096, L),  # negs; s, r, o
    (True, 1_618_688, 40_960, L), (True, 1_618_688, 8192, L),  # noise; ctr, ctx
    # the CTR cell's feature rows of 1 KB: 438,272 sorted positions are
    # three calls of 131,072 and one of 45,056
    (True, 6_508_400, 131_072, 256), (True, 6_508_400, 45_056, 256),
])
def test_writeback_kernel_compiles_in_place(adagrad, n_slots, n, row, shape,
                                            kernel_cache):
    """The manual-DMA write-back as the step takes it (exported once
    under its form's name, read back from the cache directory by the
    next process), in its plain form (the rows given) and its AdaGrad
    form (the update rows formed in VMEM from two half-row operands,
    `lr` and `eps` SMEM operands), at rows of 8 KB and of 1 KB: Mosaic
    takes it, and the donated pool is updated in place (no pool-sized
    copy)."""
    from adapm_tpu.ops import writeback
    rows = writeback.chunk_rows_for(row)
    made = writeback.exported_kernel(n_slots, row, n, rows, adagrad=adagrad)
    (kept,) = kernel_cache.iterdir()
    assert kept.name.startswith("scatter_adagrad_sorted_rows-" if adagrad
                                else "scatter_add_sorted_rows-")
    writeback.exported_kernel.cache_clear()  # as a later process
    read = writeback.exported_kernel(n_slots, row, n, rows, adagrad=adagrad)
    assert read is not made
    assert read.mlir_module_serialized == made.mlir_module_serialized
    assert [f.name for f in kernel_cache.iterdir()] == [kept.name]
    f32 = lambda *dims: shape(dims, jnp.float32)  # noqa: E731
    operands = (f32(n, row // 2), f32(n, row // 2), f32(), f32()) \
        if adagrad else (f32(n, row),)
    compiled = jax.jit(read.call, donate_argnums=(0,)).lower(
        f32(n_slots, row), shape((n,), jnp.int32), *operands).compile()
    assert compiled.as_text().count(
        "custom_call_target=\"tpu_custom_call\"") == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == n_slots * row * 4
    assert mem.temp_size_in_bytes < (64 << 20)


def _kernel_calls(text: str) -> int:
    """The write-back kernel's custom calls in a compiled program's
    text. The benchmark's `writeback_kernel_device_ms` sums the
    operations of opcode `custom-call` on the XLA Ops line of a
    `jit_step`, its top-level operations: besides the kernel's a
    compiled step holds there only the compiler's own `ConcatBitcast`
    (slices of one buffer seen as one array: no data moves)."""
    entry = text[text.index("\nENTRY "):]
    targets = re.findall(r" custom-call\(.*?custom_call_target=\"(\w+)\"",
                         entry[:entry.index("\n}")])
    assert set(targets) <= {"tpu_custom_call", "ConcatBitcast"}, set(targets)
    assert targets.count("tpu_custom_call") == text.count(
        "custom_call_target=\"tpu_custom_call\"")
    return targets.count("tpu_custom_call")


@pytest.mark.parametrize("n_slots, row, batch, calls", [
    (1_172_432, L, (8192, 32), 2),      # the KGE cell at twice its batch
    (1 << 20, 256, ((1 << 20) + 40,), 9),  # 1M rows and a last short call
])
def test_writeback_of_any_batch_compiles(n_slots, row, batch, calls, shape,
                                         kernel_cache, monkeypatch):
    """The step's write-back of one role with more rows than one kernel
    call takes (writeback.MAX_POSITIONS: the codes of a call are one
    SMEM operand, and 262,144 of them no longer fit a v5e's): as many
    calls as slices, the pool still updated in place."""
    from adapm_tpu.ops import fused
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(fused._kernel_writeback, donate_argnums=(0,)).lower(
        shape((1, n_slots, row), jnp.float32), shape(batch, jnp.int32),
        shape(batch, jnp.int32), shape((*batch, row // 2), jnp.float32),
        shape((*batch, row // 2), jnp.float32), shape((), jnp.float32),
        shape((), jnp.float32)).compile()
    assert compiled.as_text().count("custom_call_target=\"tpu_custom_call\"") \
        == calls
    assert compiled.memory_analysis().alias_size_in_bytes == \
        n_slots * row * 4


@pytest.mark.parametrize("kernel", [True, False])
def test_adagrad_pass_is_in_the_xla_variant_only(kernel, shape,
                                                 kernel_cache, monkeypatch):
    """The lowered replica-free step: with the write-back kernel no
    operation carries the scope `adapm_adagrad` (the kernel forms the
    update rows; `_adagrad_update` is not traced), without it (as on a
    CPU, several shards or with replicas) the pass is still there."""
    from adapm_tpu.models.sgns import sgns_loss
    from adapm_tpu.ops import fused
    if kernel:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    roles = {"center": 0, "ctx": 0, "neg": 0}
    B, N, keys, row = 64, 5, 4096, 256
    body = fused._build_device_routed_body(
        sgns_loss, roles, {r: row // 2 for r in roles}, (), "neg", (B, N),
        True, True)
    small = shape((1, 8, row), jnp.float32)
    lowered = jax.jit(body, donate_argnums=(0,)).lower(
        ((shape((1, keys, row), jnp.float32), small, small),),
        shape((4,), jnp.int32),
        tuple(shape((keys,), jnp.int32) for _ in range(2))
        + (shape((), jnp.int32),),
        {r: shape((B,), jnp.int32) for r in roles if r != "neg"}, None,
        (shape((keys,), jnp.float32), shape((keys,), jnp.int32),
         shape((keys,), jnp.int32)),
        shape((2,), jnp.uint32), None, shape((), jnp.float32),
        shape((), jnp.float32))
    text = lowered.as_text(debug_info=True)
    assert "adapm_scatter_add" in text and "adapm_loss_grad" in text
    assert ("adapm_adagrad" in text) is (not kernel)
    assert ("tpu_custom_call" in text) is kernel


# (model, main pools' slots, keys, batch, negatives a row, the parent's
# temporaries in bytes: SGNS by the v5e compile of PR 28's step, PERF.md
# section 4 (PR 29's read 1.015e9, PR 31's 0.842e9); KGE by PR 31's own
# reading: `memory_analysis` says 3.256e9 for any form of the step that
# hands the sampled rows on sample-major or masks the embedding half
# (PR 29's step read 2.87e9), while XLA's buffer assignment of the same
# compile FELL, 2.866e9 -> 2.790e9 preallocated; 9.6 GB of table beside
# either leaves 2.8 GB of the chip's 15.75 GiB: PERF.md section 6)
CELLS = {
    "kge-wikidata5m": ((1_172_432, 840), 1_149_443, 4096, 32, 3.26e9),
    "w2v-1bw": ((1_618_688,), 1_586_942, 8192, 5, 1.02e9),
}


_COMPILED = {}  # cell -> its compiled step, for the tests of one worker


def _cell_step(cell, shape, monkeypatch):
    """The replica-free fused step of a training cell at its own sizes,
    built as on a TPU (the write-back kernel, exported) and compiled
    for the described chip, and how many roles it writes back."""
    if cell in _COMPILED:
        return _COMPILED[cell]
    from adapm_tpu.ops import fused
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, num_keys, B, N, _ = CELLS[cell]
    if cell == "kge-wikidata5m":
        from adapm_tpu.models.kge import make_kge_loss
        loss, roles = make_kge_loss("complex", 0.0, 0.0), \
            {"s": 0, "r": 1, "o": 0, "neg": 0}
        alias = None
    else:
        from adapm_tpu.models.sgns import sgns_loss
        loss, roles = sgns_loss, {"center": 0, "ctx": 0, "neg": 0}
        alias = (shape((793_471,), jnp.float32),
                 shape((793_471,), jnp.int32), shape((793_471,), jnp.int32))
    body = fused._build_device_routed_body(
        loss, roles, {r: L // 2 for r in roles}, (), "neg", (B, N),
        True, alias is not None)
    small = shape((1, 8, L), jnp.float32)
    pools = tuple((shape((1, n, L), jnp.float32), small, small)
                  for n in slots)
    compiled = jax.jit(body, donate_argnums=(0,)).lower(
        pools, shape((4,), jnp.int32),
        tuple(shape((num_keys,), jnp.int32) for _ in range(2))
        + (shape((), jnp.int32),),
        {r: shape((B,), jnp.int32) for r in roles if r != "neg"},
        # uniform draws search a local index; alias draws read their
        # snap table (the last of `alias`) and take none
        (shape((1 << 21,), jnp.int32), shape((), jnp.int32))
        if alias is None else None, alias,
        shape((2,), jnp.uint32), None, shape((), jnp.float32),
        shape((), jnp.float32)).compile()
    _COMPILED[cell] = compiled, len(roles)
    return _COMPILED[cell]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_step_with_kernel_has_no_pool_sized_temporary(cell, shape,
                                                      kernel_cache,
                                                      monkeypatch):
    """The replica-free fused step of each training cell, built as on a
    TPU (the write-back kernel, exported): the pools stay aliased and
    the temporaries do not grow by more than 64 MB over the parent's."""
    slots, _, _, _, parent_temp = CELLS[cell]
    compiled, n_roles = _cell_step(cell, shape, monkeypatch)
    assert _kernel_calls(compiled.as_text()) >= n_roles
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(slots) * L * 4
    assert mem.temp_size_in_bytes <= parent_temp + (64 << 20)


_HLO_OP = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([a-z][a-z\-]*)\(")
_F32 = re.compile(r"f32\[([\d,]+)\]")


def _entry_ops(text: str):
    """(name, opcode, [dims of each float32 result]) of the top-level
    operations of a compiled program: the ENTRY computation's lines."""
    entry = text[text.index("\nENTRY "):]
    for line in entry[1:entry.index("\n}")].split("\n")[1:]:
        m = _HLO_OP.match(line)
        if m:
            yield m.group(1), m.group(3), [
                tuple(int(d) for d in dims.split(","))
                for dims in _F32.findall(m.group(2))]


def _row_copies(ops, rows: int):
    """The top-level `reshape`, `copy` and `transpose` operations among
    `ops` (`_entry_ops`) over `rows` rows, whole or half."""
    return [(name, op, res) for name, op, res in ops
            if op in ("reshape", "copy", "transpose") and any(
                dims[-1] in (L // 2, L) and math.prod(dims[:-1]) == rows
                for dims in res)]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_step_copies_no_sampled_rows(cell, shape, kernel_cache,
                                     monkeypatch):
    """Between the gather and its readers the compiled step neither
    copies the sampled role's rows (a top-level `reshape`, `copy` or
    `transpose` over all B * N of them, of whole or half rows: the
    batch-major `[B, 5, .]` was padded to 8 and copied three times a
    step) nor masks whole rows (a `select` fusion of row width)."""
    _, _, B, N, _ = CELLS[cell]
    compiled, n_roles = _cell_step(cell, shape, monkeypatch)
    ops = list(_entry_ops(compiled.as_text()))
    assert sum(op == "custom-call" for _, op, _ in ops) >= n_roles
    assert not _row_copies(ops, B * N)
    masks = [(name, res) for name, op, res in ops
             if op == "fusion" and "select" in name
             and any(dims[-1] == L for dims in res)]
    assert not masks, masks



# the MF cell (mf-10mx1m): main pool slots, keys, batch
MF_SLOTS, MF_KEYS, MF_B = 1_402_504, 1_375_000, 8192


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_step_routes_each_role_by_one_lookup(cell, shape, kernel_cache,
                                             monkeypatch):
    """The compiled replica-free step gathers ONE word a position out of
    a `[num_keys]` table, the key's place (`fused.decode_place`), where
    it gathered an owner and a slot before PR 47: as many look-ups as
    roles, and no division to split the word."""
    compiled, n_roles = _cell_step(cell, shape, monkeypatch)
    assert _route_lookups(compiled.as_text(), CELLS[cell][1]) == \
        (n_roles, [])


def _mf_operands(shape):
    small = shape((1, 8, L), jnp.float32)
    pools = ((shape((1, MF_SLOTS, L), jnp.float32), small, small),)
    tables = tuple(shape((MF_KEYS,), jnp.int32) for _ in range(2)) \
        + (shape((), jnp.int32),)
    keys = {r: shape((MF_B,), jnp.int32) for r in ("w", "h")}
    return pools, tables, keys


def test_mf_cell_programs_fit_beside_the_table(shape, kernel_cache,
                                               monkeypatch, capsys):
    """The three programs of the MF cell at its own sizes (an 11.49 GB
    pool of 1,402,504 slots, 16,384 rows a step): the replica-free step
    with the write-back kernel keeps the pool aliased and under 1 GB of
    temporaries; the gather-only score program and the L2 reduction over
    the pool's factor columns read the pool where it lies (no pool-sized
    temporary: neither the sliced columns nor their squares are an
    array)."""
    from adapm_tpu.apps.matrix_factorization import _masked_sq_sum
    from adapm_tpu.models.mf import make_mf_loss, mf_sq_error
    from adapm_tpu.ops import fused
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pools, tables, keys = _mf_operands(shape)
    pool_bytes = MF_SLOTS * L * 4
    roles = {"w": 0, "h": 0}
    dims = {r: L // 2 for r in roles}
    f32, x = shape((), jnp.float32), shape((MF_B,), jnp.float32)

    body = fused._build_device_routed_body(
        make_mf_loss(0.01), roles, dims, (), None, None, True, False)
    step = jax.jit(body, donate_argnums=(0,)).lower(
        pools, shape((4,), jnp.int32), tables, keys, None, None,
        shape((2,), jnp.uint32), x, f32, f32).compile()
    assert _kernel_calls(step.as_text()) >= 2
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 1 << 30
    sizes = {"step": mem.temp_size_in_bytes}

    score = fused.make_device_routed_score(
        mf_sq_error, roles, dims, roles, no_replicas=True).lower(
        pools, tables, keys, (x, shape((), jnp.int32)), f32).compile()
    assert "tpu_custom_call" not in score.as_text()
    sizes["score"] = score.memory_analysis().temp_size_in_bytes
    assert sizes["score"] < 256 << 20

    sq = jax.jit(_masked_sq_sum, static_argnums=2).lower(
        pools[0][0], shape((1, MF_SLOTS), jnp.bool_), L // 2).compile()
    sizes["sq_sum"] = sq.memory_analysis().temp_size_in_bytes
    assert sizes["sq_sum"] < 256 << 20
    with capsys.disabled():
        print(f"\nmf-10mx1m v5e compile: pool {pool_bytes / 1e9:.3f} GB, "
              f"temporaries (MB) " + ", ".join(
                  f"{k} {v / 1e6:.1f}" for k, v in sizes.items()))


# the DLRM cell (dlrm-dcnv2-criteo1tb): the two main pools' slots, keys of
# both classes, members an example, batch
DLRM_SLOTS, DLRM_KEYS, DLRM_M, DLRM_B = (6_508_400, 15_992), \
    6_380_781 + 15_676, 214, 2048


def _dlrm_step(shape, monkeypatch):
    """The DLRM cell's replica-free step at its own sizes, compiled for
    the described chip: (compiled, bytes of both pools)."""
    from adapm_tpu.models import dlrm
    from adapm_tpu.ops import fused
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layout = dlrm.DenseLayout(dlrm.dense_tensors(
        13, 128, 26, [512, 256, 128], [1024, 1024, 512, 256, 1], 3, 512),
        1024)
    assert (layout.num_rows, layout.num_params) == (15_676, 16_044_545)
    hot = [3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100,
           27, 10, 3, 1, 1]
    assert sum(hot) == DLRM_M
    loss = dlrm.make_dlrm_loss(layout, hot, 3, 3, 5)
    pools = tuple((shape((1, n, row), jnp.float32),
                   shape((1, 8, row), jnp.float32),
                   shape((1, 8, row), jnp.float32))
                  for n, row in zip(DLRM_SLOTS, (256, L)))
    tables = tuple(shape((DLRM_KEYS,), jnp.int32) for _ in range(2)) \
        + (shape((), jnp.int32),)
    keys = {"feat": shape((DLRM_M, DLRM_B), jnp.int32),
            "dense": shape((layout.num_rows,), jnp.int32)}
    aux = (shape((DLRM_B, 13), jnp.float32), shape((DLRM_B,), jnp.float32))
    f32 = shape((), jnp.float32)
    body = fused._build_device_routed_body(
        loss, {"feat": 0, "dense": 1}, {"feat": 128, "dense": 1024}, (),
        None, None, True, False)
    compiled = jax.jit(body, donate_argnums=(0,)).lower(
        pools, shape((4,), jnp.int32), tables, keys, None, None,
        shape((2,), jnp.uint32), aux, f32, f32).compile()
    return compiled, sum(n * row * 4 for n, row in zip(DLRM_SLOTS, (256, L)))


def test_dlrm_cell_step_fits_beside_the_tables(shape, kernel_cache,
                                               monkeypatch, capsys):
    """The DLRM cell's step at its own sizes (6,508,400 slots of 1 KB and
    15,992 of 8 KB; 438,272 feature positions and 15,676 dense rows a
    step): both pools stay aliased, the feature role is written back by
    FOUR kernel calls at L = 256 (438,272 positions, 131,072 a call) and
    the dense role by one at L = 2,048, the matrix products are
    convolutions of the compiled step, and pools + temporaries stay
    under 15.0 GiB."""
    compiled, pool_bytes = _dlrm_step(shape, monkeypatch)
    text = compiled.as_text()
    assert _kernel_calls(text) == 5
    # one look-up of a place word for each of the two roles
    assert _route_lookups(text, DLRM_KEYS) == (2, [])
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    fullest = pool_bytes + mem.temp_size_in_bytes
    with capsys.disabled():
        print(f"\ndlrm-dcnv2-criteo1tb v5e compile: pools "
              f"{pool_bytes / 1e9:.3f} GB, the step's temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.3f} GB, fullest program "
              f"{fullest / 2**30:.2f} GiB")
    assert fullest < 15.0 * 2**30
    assert mem.temp_size_in_bytes < 4 << 30


# the four-shard cell (kge-wikidata5m-kv4): a shard's main and replica
# pools' slots, keys, batch, negatives a triple
KV4_SLOTS, KV4_CACHE, KV4_KEYS, KV4_B, KV4_N = \
    1_194_784, 32_768, 4_595_309, 4096, 32
_ALL_REDUCE = re.compile(r"= (\(.*?\)|\S+) all-reduce(?:-start)?\(")
_HLO_LINE = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([a-z][a-z\-]*)\(([^)]*)\)"
    r"(?:.*?op_name=\"([^\"]*)\")?")


def _ops_by_name(text: str):
    """name -> (opcode, result, operands' names, op_name) of every
    operation of a compiled program, whatever computation it is in (a
    `while` body's too)."""
    out = {}
    for line in text.split("\n"):
        m = _HLO_LINE.match(line)
        if m:
            name, res, op, operands, op_name = m.groups()
            out[name] = (op, res, re.findall(r"%([\w.\-]+)", operands),
                         op_name or "")
    return out


def _route_lookups(text: str, num_keys: int):
    """What a compiled program's routing costs: (its gathers out of a
    `[num_keys]` int32 table, the route mirrors; the divisions among the
    operations of its `adapm_route` scope, of which there are to be
    none: a place word is split by a shift and a mask)."""
    ops = _ops_by_name(text)
    table = f"s32[{num_keys}]"
    lookups = [name for name, (op, _, operands, op_name) in ops.items()
               if op == "fusion" and op_name.endswith("/gather")
               and operands
               and ops.get(operands[0], ("", ""))[1].startswith(table)]
    divisions = [name for name, (op, _, _, op_name) in ops.items()
                 if op in ("divide", "remainder")
                 and "adapm_route" in op_name]
    return len(lookups), divisions


def _rows(result: str, width: int):
    """Rows of the float32 arrays of `width` columns in an operation's
    result (`f32[4096,32,2048]` is 131,072 rows of 2,048)."""
    return [math.prod(dims[:-1]) for dims in (
        tuple(int(d) for d in found.split(","))
        for found in _F32.findall(result)) if dims[-1] == width]


def _replica_variant_reads_and_writes(text: str):
    """What the compiled replica variant of the four-shard step does
    with its pools: (rows of each gather from main, rows of each gather
    from a replica pool, rows of the widest array that `_adagrad_update`
    or a concatenation forms)."""
    ops = _ops_by_name(text)
    from_main, from_replica_pools, update_rows = [], [], [0]
    for op, res, operands, op_name in ops.values():
        if op == "fusion" and op_name.endswith("/gather") and operands:
            pool = ops.get(operands[0], ("", "", [], ""))[1]
            if pool.startswith(f"f32[1,{KV4_SLOTS},{L}]"):
                from_main += _rows(res, L)
            elif pool.startswith(f"f32[1,{KV4_CACHE},{L}]"):
                from_replica_pools += _rows(res, L)
        if "adapm_adagrad" in op_name or "concatenate" in op_name:
            update_rows += _rows(res, L) + _rows(res, L // 2)
    return sorted(from_main), sorted(from_replica_pools), max(update_rows)


# dlrm-dcnv2-criteo1tb-serve: 26,033,545 slots of 128 floats (one cache
# slot asked for: the store holds 8), and the coalesced bag batches of 1,
# 4 (`serve.max_batch`) and 8 requests of 700 samples x 214 members in 26
# bags, as the store pads them
BAGS_SLOTS, BAGS_DIM = 26_033_545, 128
BAGS_BATCHES = [(1, 262_144, 32_768, True), (4, 1_048_576, 131_072, True),
                (8, 2_097_152, 262_144, False)]


@pytest.mark.parametrize("requests, members, bags, fits", BAGS_BATCHES)
def test_bags_fullest_gather_pool_fits_beside_the_table(
        requests, members, bags, fits, shape, capsys):
    """The serve plane's fused bag program (`jaxport._gather_pool`) at
    the serving cell's table and its fullest buckets: the chip's
    compiler takes it; its temporaries are the member rows gathered
    THREE times (from main, cache and delta: a one-shard store reads
    two pools of 8 slots for every member); the fullest batch the
    configuration allows (`serve.max_batch` 4) leaves 1.6 GiB beside
    the 13.33 GB table, and twice that does not fit (the chip refused
    to load it: RESOURCE_EXHAUSTED, my chip run, PR 37)."""
    from adapm_tpu.core.store import bucket_size
    from adapm_tpu.device import jaxport
    assert members == bucket_size(requests * 700 * 214)
    assert bags == bucket_size(requests * 700 * 26)
    i32 = lambda: shape((members,), jnp.int32)  # noqa: E731
    row = lambda n: shape((1, n, BAGS_DIM), jnp.float32)  # noqa: E731
    compiled = jaxport._gather_pool.lower(
        row(BAGS_SLOTS), row(8), row(8), i32(), i32(), i32(), i32(),
        shape((members,), jnp.bool_), i32(), nbags=bags,
        pooling="sum").compile()
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    with capsys.disabled():
        print(f"\ndlrm-dcnv2-criteo1tb-serve v5e compile, {requests} "
              f"requests ({members} members, {bags} bags): temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.3f} GB, live "
              f"{live / 2**30:.2f} GiB of 15.75")
    gathered = members * BAGS_DIM * 4
    assert 3 * gathered <= mem.temp_size_in_bytes < \
        3 * gathered + (256 << 20)
    assert (live < 14.25 * 2**30) if fits else (live > 15.5 * 2**30)


# dlrm-dcnv2-criteo1tb-serve-tier: the hot pool at each candidate cache
# share of the 51,046,153-key share (the sum of ceil(share x rows) a
# table, as the store rounds it), and the verdict of the sizing rule the
# configuration's file states
TIER_POOLS = [(0.5, 25_523_080, False), (0.4, 20_418_472, True),
              (0.3, 15_313_856, True)]


@pytest.mark.parametrize("share, hot_rows, fits", TIER_POOLS)
def test_tier_fullest_cold_bag_program_beside_the_hot_pool(
        share, hot_rows, fits, shape, capsys):
    """The tiered serving cell's sizing rule: the cold twin of the bag
    program (`jaxport._gather_pool_cold`: `_gather_pool` with the staged
    cold rows as one more row-wide operand) at the fullest buckets
    `serve.max_batch` 4 allows (the `-k bags` cases' second batch),
    beside the hot pool at each candidate share. The line is the one
    those cases draw: under 14.25 GiB live fits (14.01 loaded and ran,
    15.60 was refused by the chip: PR 37), counted with ONE MORE staged
    operand: the dispatcher uploads the next batch's while this
    program is in flight. The select of the staged rows fuses into the
    first gather, so the temporaries are the untiered program's (the
    member rows three times); the staged operand is 0.54 GB beside the
    pool. 0.5 is 14.27 GiB alone and 14.77 with the next operand; 0.4
    (11.83 and 12.33) is the largest share under the line."""
    from adapm_tpu.device import jaxport
    requests, members, bags, _ = BAGS_BATCHES[1]
    assert requests == 4
    i32 = lambda: shape((members,), jnp.int32)  # noqa: E731
    flag = lambda: shape((members,), jnp.bool_)  # noqa: E731
    row = lambda n: shape((1, n, BAGS_DIM), jnp.float32)  # noqa: E731
    compiled = jaxport._gather_pool_cold.lower(
        row(hot_rows), row(8), row(8), i32(), i32(), i32(), i32(), flag(),
        shape((members, BAGS_DIM), jnp.float32), flag(), i32(),
        nbags=bags, pooling="sum").compile()
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    with capsys.disabled():
        print(f"\ndlrm-dcnv2-criteo1tb-serve-tier v5e compile, cache share "
              f"{share} ({hot_rows} hot rows, "
              f"{hot_rows * BAGS_DIM * 4 / 1e9:.2f} GB): temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.3f} GB, operands beside "
              f"the pool "
              f"{(mem.argument_size_in_bytes - hot_rows * BAGS_DIM * 4) / 1e9:.3f}"
              f" GB, live {live / 2**30:.2f} GiB of 15.75 "
              f"({(live + members * BAGS_DIM * 4) / 2**30:.2f} with the "
              f"next batch's staged operand)")
    gathered = members * BAGS_DIM * 4
    assert 3 * gathered <= mem.temp_size_in_bytes < \
        3 * gathered + (256 << 20)
    assert (live + gathered < 14.25 * 2**30) == fits


@pytest.mark.parametrize("no_replicas", [True, False])
def test_four_shard_step_is_a_per_chip_program(no_replicas, topo,
                                               kernel_cache, monkeypatch,
                                               capsys):
    """The four-shard cell's step at its own sizes, compiled for the
    described v5e 2x2 as the runner builds it (`make_device_routed_step`:
    pools of four shards make it the per-chip program): each chip's
    block keeps the write-back kernel's custom call, one for each role
    (131,072 negatives are one call); what is summed over the chips is
    the loss and blocks of one exchange chunk (`fused.EXCHANGE_BYTES`:
    `[1024, 1024]`) of the named roles' halves, never a role's whole
    `[4096, 1024]` nor an array of the negatives' 131,072 rows; the pools
    stay aliased and the
    temporaries far under a pool's size. In the replica variant every
    role is gathered ONCE from main at all its positions; the cache and
    delta pools are read by gathers of one side-path chunk
    (`fused.SIDE_ROWS` rows) and no others; update rows
    (`_adagrad_update`, a concatenation) exist for a chunk at a time,
    never for the 131,072 negatives; and all three pools are aliased."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from adapm_tpu.models.kge import make_kge_loss
    from adapm_tpu.ops import fused
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(topo.devices[:4]), ("kv",))

    def shape(dims, dtype, spec=P()):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, spec))
    pool = tuple(shape((4, n, L), jnp.float32, P("kv"))
                 for n in (KV4_SLOTS, KV4_CACHE, KV4_CACHE))
    roles = {"s": 0, "r": 0, "o": 0, "neg": 0}
    step = fused.make_device_routed_step(
        make_kge_loss("complex", 0.0, 0.0), roles,
        {r: L // 2 for r in roles}, (), "neg", (KV4_B, KV4_N), no_replicas)
    compiled = step.lower(
        (pool,), shape((11,), jnp.int32),  # a runner's: 6, 1 + a role
        tuple(shape((KV4_KEYS,), jnp.int32) for _ in range(2))
        + (shape((), jnp.int32),),
        {r: shape((KV4_B,), jnp.int32) for r in roles if r != "neg"},
        (shape((1 << 21,), jnp.int32), shape((), jnp.int32)), None,
        shape((2,), jnp.uint32), None, shape((), jnp.float32),
        shape((), jnp.float32)).compile()
    assert list(step._forms) == [mesh]
    text = compiled.as_text()
    assert _kernel_calls(text) == 4
    summed = [tuple(int(d) for d in dims.split(","))
              for res in _ALL_REDUCE.findall(text)
              for dims in _F32.findall(res)]
    # (the loss, a scalar, has no dims for _F32 to find)
    chunk = fused.EXCHANGE_BYTES // (4 * (L // 2))
    assert chunk < KV4_B and set(summed) == {(chunk, L // 2)}, summed
    # a role's route: its place word, and with replicas its cache row
    assert _route_lookups(text, KV4_KEYS) == \
        (len(roles) * (1 if no_replicas else 2), [])
    mem = compiled.memory_analysis()
    pool_bytes = KV4_SLOTS * L * 4
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 2
    if not no_replicas:
        from_main, from_replica_pools, update_rows = \
            _replica_variant_reads_and_writes(text)
        assert from_main == [KV4_B] * 3 + [KV4_B * KV4_N], from_main
        # cache and delta, once each for every role's chunk
        assert from_replica_pools == [fused.SIDE_ROWS] * 8, \
            from_replica_pools
        assert 0 < update_rows <= fused.SIDE_ROWS
        assert mem.alias_size_in_bytes >= \
            (KV4_SLOTS + 2 * KV4_CACHE) * L * 4
        # the parent's replica variant read 4.536 GB (v5e compile, PR 36)
        assert mem.temp_size_in_bytes < pool_bytes // 3
        # sample-major like the replica-free variant: no top-level copy
        # of the negatives' rows, whole or half
        assert not _row_copies(_entry_ops(text), KV4_B * KV4_N)
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    with capsys.disabled():
        print(f"\nkge-wikidata5m-kv4 v5e 2x2 compile, no_replicas="
              f"{no_replicas}: a chip's temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.3f} GB, live "
              f"{live / 2**30:.2f} GiB of 15.75")
    assert live < 15.0 * 2**30


# the four-shard CTR cell (dlrm-dcnv2-criteo1tb-kv4): a shard's main and
# replica slots of the feature class and of the dense class, keys
CTR4_FEAT, CTR4_DENSE, CTR4_KEYS = (6_763_632, 131_072), (4_160, 15_680), \
    25_523_124 + 15_676
# a chip's six pools: main, cache and delta of both classes (rows of 256
# and of L floats)
CTR4_POOL_BYTES = sum((main + 2 * cache) * row * 4 for (main, cache), row
                      in ((CTR4_FEAT, 256), (CTR4_DENSE, L)))


def test_four_shard_ctr_step_walks_two_classes_of_replicas(
        topo, kernel_cache, monkeypatch, capsys):
    """The four-shard CTR cell's replica variant at its own sizes,
    compiled for the described v5e 2x2: two length classes in one
    per-chip step, each with its main, cache and delta block. The
    write-back kernel's calls are the one-chip step's five (four for the
    438,272 feature positions, one for the dense rows); what is summed
    over the chips is the loss and, out and back, blocks of one
    exchange chunk of each role (`fused.EXCHANGE_BYTES`: 8,192 feature
    positions' embedding halves, 1,024 dense rows'), never a role's
    whole array; each class's cache and delta
    pools are read a side-path chunk at a time and all six pools stay
    aliased; the accumulator has a pair of entries a class beside its
    six, then the exchange's (the positions, and one a role)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from adapm_tpu.models import dlrm
    from adapm_tpu.ops import fused
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(topo.devices[:4]), ("kv",))

    def shape(dims, dtype, spec=P()):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, spec))
    layout = dlrm.DenseLayout(dlrm.dense_tensors(
        13, 128, 26, [512, 256, 128], [1024, 1024, 512, 256, 1], 3, 512),
        1024)
    hot = [3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100,
           27, 10, 3, 1, 1]
    pools = tuple(tuple(shape((4, n, row), jnp.float32, P("kv"))
                        for n in (main, cache, cache))
                  for (main, cache), row in ((CTR4_FEAT, 256),
                                             (CTR4_DENSE, L)))
    roles = {"feat": 0, "dense": 1}
    assert fused._classes_counted(roles) == [0, 1]
    step = fused.make_device_routed_step(
        dlrm.make_dlrm_loss(layout, hot, 3, 3, 5), roles,
        {"feat": 128, "dense": 1024}, (), None, None, False)
    compiled = step.lower(
        pools, shape((13,), jnp.int32),
        tuple(shape((CTR4_KEYS,), jnp.int32) for _ in range(2))
        + (shape((), jnp.int32),),
        {"feat": shape((DLRM_M, DLRM_B), jnp.int32),
         "dense": shape((layout.num_rows,), jnp.int32)},
        None, None, shape((2,), jnp.uint32),
        (shape((DLRM_B, 13), jnp.float32), shape((DLRM_B,), jnp.float32)),
        shape((), jnp.float32), shape((), jnp.float32)).compile()
    text = compiled.as_text()
    assert _kernel_calls(text) == 5
    summed = {tuple(int(d) for d in dims.split(","))
              for res in _ALL_REDUCE.findall(text)
              for dims in _F32.findall(res)}
    assert summed == {(fused.EXCHANGE_BYTES // (4 * dim), dim)
                      for dim in (128, 1024)}, summed
    assert _route_lookups(text, CTR4_KEYS) == (2 * len(roles), [])
    mem = compiled.memory_analysis()
    pool_bytes = CTR4_POOL_BYTES
    assert mem.alias_size_in_bytes >= pool_bytes
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    with capsys.disabled():
        print(f"\ndlrm-dcnv2-criteo1tb-kv4 v5e 2x2 compile, the replica "
              f"variant: a chip's pools {pool_bytes / 1e9:.3f} GB, "
              f"temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB, live "
              f"{live / 2**30:.2f} GiB of 15.75")
    assert mem.temp_size_in_bytes < 2 << 30 and live < 12.0 * 2**30


# sha256 of the lowered text of the one-chip cells' programs as PR 48
# lowered them (this jax). PR 34 first recorded them, of ITS parent
# (commit 21ae0c6): it made the fused programs adapt to their pools'
# shard count and left pools of one shard their program. PR 47 changed
# the one-chip programs BY DESIGN (a role's route is one look-up of a
# place word and its decode, `fused.decode_place`, where it was two
# look-ups) and recorded its own text; what that text has to keep is
# held by `test_one_chip_programs_look_up_one_table_a_role`. PR 48
# changed the four programs that write back through the kernel (not
# `mf.jit_score`, which writes nothing), again by design and outside
# the Mosaic body too: `sort_slots` packs the kernel's decisions into
# the codes (a running maximum a chunk, the buffer place in bits 24-28)
# and the exported call counts each chunk's copies and the chunks to
# visit (`writeback.chunk_meta`, a second scalar-prefetch operand). A
# PR that means to change a one-chip program records its own text here
# (`_one_chip_lowered` under `pytest -s` prints what it finds when a
# hash differs).
PARENT_LOWERED = {
    "kge.jit_step":
        "9ef64f8f5cccc02f527d35e6e4604d792d4fc051bb2e912f765eddf444945e4f",
    "kge.jit_scan":
        "a742a1995cbf5a5f1130d3370e96d2ca6db3f704b655cf2d47128a8ce43aa21b",
    "sgns.jit_step":
        "3e5a5cc4ae46496c368c28355f63ec5c3d6eaf311b48c113d818c6d34559e99c",
    "mf.jit_step":
        "211bf4c549e6b6604f37bfded997342e796970b2ba66992a18256084de70e59c",
    "mf.jit_score":
        "ea6c2bd33d2801bd1178b691207a3f70e093c4c48abc779a225344799474956e",
}


_MOSAIC_BODY = re.compile(r'\\22body\\22: \\22[A-Za-z0-9+/=]*\\22')


@pytest.mark.parametrize("num_keys, rung", [
    (KV4_KEYS, "first"), (KV4_KEYS, "last"),
    (CTR4_KEYS, "first"), (CTR4_KEYS, "last")])
def test_route_patch_is_each_chip_for_itself(num_keys, rung, topo):
    """The program that patches a router's two table mirrors
    (`jaxport._patch_routes`), at both four-shard hosts' key counts and
    the first and the last rung of the widths it is called at there
    (`fused.patch_rungs` of the journal's bound: 16,384 to 2^19 entries
    at the KGE host's 4.6 M keys, to 2^21 at the click model's 25.5 M),
    compiled for the described v5e 2x2 with everything
    replicated: every chip sets the entries of its own copies, nothing
    crosses the chips, and what it holds beside the two new tables is
    under one table. At the click model's sizes one call's new tables,
    operand and temporaries fit beside the pools, the four runners'
    tables and a step's temporaries on a chip."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from adapm_tpu.device import jaxport
    from adapm_tpu.ops import fused
    rungs = fused.patch_rungs(max(4096, num_keys // 16))
    assert rungs[0] == fused.PATCH_KEYS and len(rungs) <= 8
    assert rungs[-1] == (2 ** 19 if num_keys == KV4_KEYS else 2 ** 21)
    entries = rungs[0] if rung == "first" else rungs[-1]
    everywhere = NamedSharding(
        Mesh(np.asarray(topo.devices[:4]), ("kv",)), P())
    table = jax.ShapeDtypeStruct((num_keys,), jnp.int32,
                                 sharding=everywhere)
    patch = jax.ShapeDtypeStruct((3, entries), jnp.int32,
                                 sharding=everywhere)
    compiled = jaxport._patch_routes.lower(table, table, patch).compile()
    text = compiled.as_text()
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute"):
        assert collective not in text, collective
    assert all(s == everywhere for s in compiled.output_shardings)
    mem = compiled.memory_analysis()
    print(f"route patch at {num_keys} keys, {entries} entries: "
          f"{mem.temp_size_in_bytes / 1e6:.1f} MB of temporaries beside "
          f"{mem.output_size_in_bytes / 1e6:.1f} MB of tables")
    assert mem.output_size_in_bytes >= 2 * 4 * num_keys
    assert mem.temp_size_in_bytes < 4 * num_keys
    if num_keys == CTR4_KEYS:
        # what the configuration holds on a chip whatever the patch does
        # (benchmarks/configs/dlrm-dcnv2-criteo1tb-kv4.json `memory`):
        # the pools, four runners' tables (three each when that file
        # was written, two since PR 47), a step's temporaries (under
        # 2 GiB: the test of the step above)
        held = CTR4_POOL_BYTES + 4 * 2 * 4 * num_keys + (2 << 30)
        call = (mem.output_size_in_bytes + 3 * 4 * entries
                + mem.temp_size_in_bytes)
        assert held + call < 15.75 * 2**30, (held, call)


def test_precompile_on_several_shards_leaves_no_rung_to_compile():
    """After `DeviceRoutedRunner.precompile` on four shards (virtual CPU
    devices: the ladder is the host's, whatever the backend) a patch of
    any admissible size, from one key to all the journal holds, finds
    its rung compiled: the port's program gains no entry."""
    import numpy as np

    import adapm_tpu
    from adapm_tpu.config import SystemOptions
    from adapm_tpu.device import jaxport
    from adapm_tpu.ops import DeviceRoutedRunner, fused
    num_keys = 16 * 70_000      # a ladder of four rungs, to 131,072
    srv = adapm_tpu.setup(num_keys, 2, num_shards=4, opts=SystemOptions(
        sync_max_per_sec=0, cache_slots_per_shard=32, main_over_alloc=1.5))
    try:
        rungs = fused.patch_rungs(srv.ab.journal_limit)
        assert rungs == [16_384, 32_768, 65_536, 131_072]
        runner = DeviceRoutedRunner(
            srv, lambda embs, aux: (embs["a"] ** 2).mean(),
            role_class={"a": 0}, role_dim={"a": 1}, shard=1)
        runner.precompile({"a": np.zeros(8, np.int64)})
        compiled = jaxport._patch_routes._cache_size()
        patches = srv.obs.find("fused.route_patch_calls_total")
        movable = np.flatnonzero(np.arange(num_keys) % 4 != 0)
        at = 0
        for changed in (1, 16_384, 16_385, 65_537, srv.ab.journal_limit):
            before = patches.snap()
            srv._relocate_to(movable[at:at + changed], 0)
            at += changed
            with srv._lock:
                runner.router.refresh()
            assert patches.snap() == before + 1, changed
        assert jaxport._patch_routes._cache_size() == compiled
    finally:
        srv.shutdown()


_LOWERED = {}  # the lowered texts, for the tests of one worker


def _one_chip_lowered(shape, monkeypatch):
    """name -> lowered text (StableHLO, no locations) of the replica-free
    programs the one-chip cells run, at the cells' own sizes, built as on
    a TPU (the write-back kernel, exported): `jit_step` of KGE, SGNS, MF
    and DLRM, KGE's `jit_scan` of 8 steps, MF's `jit_score`."""
    if _LOWERED:
        return _LOWERED
    from adapm_tpu.models.kge import make_kge_loss
    from adapm_tpu.models.mf import make_mf_loss, mf_sq_error
    from adapm_tpu.models.sgns import sgns_loss
    from adapm_tpu.ops import fused
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    i32, f32 = shape((), jnp.int32), shape((), jnp.float32)
    small = shape((1, 8, L), jnp.float32)
    out = {}

    def pools_tables(slots, num_keys):
        return (tuple((shape((1, n, L), jnp.float32), small, small)
                      for n in slots), shape((4,), jnp.int32),
                tuple(shape((num_keys,), jnp.int32) for _ in range(2))
                + (i32,))

    slots, num_keys, B, N, _ = CELLS["kge-wikidata5m"]
    roles = {"s": 0, "r": 1, "o": 0, "neg": 0}
    kge = (make_kge_loss("complex", 0.0, 0.0), roles,
           {r: L // 2 for r in roles}, (), "neg", (B, N), True, False)
    local_index = (shape((1 << 21,), jnp.int32), i32)
    keys = {r: shape((B,), jnp.int32) for r in roles if r != "neg"}
    out["kge.jit_step"] = fused.make_device_routed_step(*kge).lower(
        *pools_tables(slots, num_keys), keys, local_index, None,
        shape((2,), jnp.uint32), None, f32, f32)
    out["kge.jit_scan"] = fused.make_device_routed_scan(
        *kge, has_aux=False).lower(
        *pools_tables(slots, num_keys),
        {r: shape((8, B), jnp.int32) for r in keys}, local_index, None,
        shape((8, 2), jnp.uint32), None, f32, f32)

    slots, num_keys, B, N, _ = CELLS["w2v-1bw"]
    roles = {"center": 0, "ctx": 0, "neg": 0}
    alias = (shape((793_471,), jnp.float32), shape((793_471,), jnp.int32),
             shape((793_471,), jnp.int32))
    out["sgns.jit_step"] = fused.make_device_routed_step(
        sgns_loss, roles, {r: L // 2 for r in roles}, (), "neg", (B, N),
        True, True).lower(
        *pools_tables(slots, num_keys),
        {r: shape((B,), jnp.int32) for r in roles if r != "neg"}, None,
        alias, shape((2,), jnp.uint32), None, f32, f32)

    pools, tables, keys = _mf_operands(shape)
    roles = {"w": 0, "h": 0}
    dims = {r: L // 2 for r in roles}
    x = shape((MF_B,), jnp.float32)
    out["mf.jit_step"] = fused.make_device_routed_step(
        make_mf_loss(0.01), roles, dims, (), None, None, True,
        False).lower(pools, shape((4,), jnp.int32), tables, keys, None,
                     None, shape((2,), jnp.uint32), x, f32, f32)
    out["mf.jit_score"] = fused.make_device_routed_score(
        mf_sq_error, roles, dims, roles, no_replicas=True).lower(
        pools, tables, keys, (x, i32), f32)
    # the kernel's Mosaic body carries its source's path (the locations
    # of pallas_kernels.py, which no program here changes): left out
    _LOWERED.update({name: _MOSAIC_BODY.sub("", lowered.as_text())
                     for name, lowered in out.items()})
    return _LOWERED


def test_one_chip_programs_lower_as_on_the_parent(shape, kernel_cache,
                                                  monkeypatch, capsys):
    """Pools of one shard bypass the per-chip form entirely: the
    one-chip cells' programs lower to the text recorded in
    `PARENT_LOWERED`, to the character. (PR 47 re-recorded it: the
    programs' route changed by design, one look-up a role for two.
    PR 48 re-recorded the four that write back: the kernel's codes and
    its `chunk_meta` operand are made in plain XLA, outside the Mosaic
    body that the comparison leaves out.)"""
    import hashlib
    texts = _one_chip_lowered(shape, monkeypatch)
    for name in ("kge.jit_step", "sgns.jit_step", "mf.jit_step"):
        assert "@jit_step" in texts[name]
    assert "@jit_scan" in texts["kge.jit_scan"]
    assert "@jit_score" in texts["mf.jit_score"]
    got = {name: hashlib.sha256(text.encode()).hexdigest()
           for name, text in texts.items()}
    if got != PARENT_LOWERED:
        with capsys.disabled():
            print("\nlowered one-chip programs:", got)
    assert got == PARENT_LOWERED


# program -> (keys of its route mirrors, roles it routes)
ONE_CHIP_ROUTES = {
    "kge.jit_step": (CELLS["kge-wikidata5m"][1], 4),
    "kge.jit_scan": (CELLS["kge-wikidata5m"][1], 4),
    "sgns.jit_step": (CELLS["w2v-1bw"][1], 3),
    "mf.jit_step": (MF_KEYS, 2),
    "mf.jit_score": (MF_KEYS, 2),
}
_GATHER_OPERAND = re.compile(
    r'"stablehlo\.gather"\(.*?: \(tensor<(\d+)xi32>, ')


@pytest.mark.parametrize("program", sorted(ONE_CHIP_ROUTES))
def test_one_chip_programs_look_up_one_table_a_role(program, shape,
                                                    kernel_cache,
                                                    monkeypatch):
    """In the lowered text of each one-chip program every role gathers
    from exactly ONE `[num_keys]` int32 table, its keys' place words (a
    scan's body, traced once, stands for its eight steps): a second
    look-up a role, the `owner[keys]` and `slot[keys]` that PR 47 took
    out, cannot come back unseen behind a re-recorded hash."""
    text = _one_chip_lowered(shape, monkeypatch)[program]
    num_keys, n_roles = ONE_CHIP_ROUTES[program]
    tables = [int(n) for n in _GATHER_OPERAND.findall(text)]
    assert tables.count(num_keys) == n_roles, tables
