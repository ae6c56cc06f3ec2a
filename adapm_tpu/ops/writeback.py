"""The fused step's write-back through the Pallas kernel
(`pallas_kernels.scatter_adagrad_sorted_rows`, and its plain form
`scatter_add_sorted_rows`): what the step needs of it without importing
`jax.experimental.pallas`.

The slots are sorted here, in plain XLA, and cut into slices of at most
`MAX_POSITIONS` sorted positions, one kernel call each (the kernel keeps
a call's codes in SMEM, so its size must not grow with the batch). The
kernel itself is always taken as a `jax.export.Exported`: traced and
lowered once for its sizes and kept beside the compiled programs in
jax's persistent compilation cache directory, so a later process that
finds the compiled step there does not import Pallas and does not trace
the kernel either (1.2 s of imports and 0.3 s of tracing on a v5e host,
in every process, for a program that comes from the cache). With no
cache directory the kernel is exported anew in each process.
"""
from __future__ import annotations

import functools
import hashlib
import os

import jax
import jax.numpy as jnp

INVALID_SLOT = jnp.iinfo(jnp.int32).max
GROUP = 8          # rows of one (8, 128) float32 tile: the kernel's unit
SLOT_MASK = (1 << 29) - 1  # a code: slot | OPENS | CLOSES, or -1
OPENS, CLOSES = 1 << 29, 1 << 30
# sorted positions one kernel call takes: its codes are one SMEM operand
# (scalar prefetch), 512 KB of the v5e's 1 MB at this many; twice as many
# no longer compile
MAX_POSITIONS = 1 << 17


def chunk_rows_for(row_length: int) -> int:
    """Positions a chunk: 32 at rows of up to 2048 floats (three group
    buffers of 2 MB; on the chip 16, 32 and 64 ran alike), fewer for
    longer rows so that the buffers stay a few MB of VMEM."""
    return max(GROUP, min(32, (1 << 16) // row_length // GROUP * GROUP))


def sort_slots(slots: jnp.ndarray, n_slots: int, chunk_rows: int = 32,
               slice_positions: int = MAX_POSITIONS):
    """(codes, perm) for `scatter_add_sorted_rows`: the flattened slots
    sorted stably and padded to whole chunks, and the batch position of
    each. A code is the slot with two flags above it: the position opens
    (bit 29) or closes (bit 30) a run of positions in one 8-row group,
    the unit the kernel copies. Every `slice_positions` positions a run
    is closed and opened anew, so each such slice can be one call of the
    kernel. Slots outside the pool (negative ones wrap first, as jnp
    indexing does) sort last and carry the code -1, as padding positions
    do; their `perm` is clamped to the batch."""
    assert n_slots <= SLOT_MASK, n_slots
    n = slots.shape[0]
    slots = slots.astype(jnp.int32)
    slots = jnp.where(slots < 0, slots + n_slots, slots)
    slots = jnp.where((slots >= 0) & (slots < n_slots), slots,
                      INVALID_SLOT)
    pad = -n % chunk_rows
    slot_sorted, perm = jax.lax.sort(
        (jnp.pad(slots, (0, pad), constant_values=INVALID_SLOT),
         jax.lax.iota(jnp.int32, n + pad)), num_keys=1, is_stable=True)
    # the invalid slot's group is no valid slot's, so a run before the
    # invalid tail closes by the same comparison
    group = slot_sorted // GROUP
    none = jnp.full((1,), -1, jnp.int32)
    opens = group != jnp.concatenate([none, group[:-1]])
    closes = group != jnp.concatenate([group[1:], none])
    if n + pad > slice_positions:
        at = jax.lax.iota(jnp.int32, n + pad) % slice_positions
        opens |= at == 0
        closes |= at == slice_positions - 1
    codes = jnp.where(slot_sorted == INVALID_SLOT, -1,
                      slot_sorted | jnp.where(opens, OPENS, 0)
                      | jnp.where(closes, CLOSES, 0))
    return codes, jnp.minimum(perm, n - 1)


def sorted_slices(slots: jnp.ndarray, n_slots: int, chunk_rows: int,
                  max_positions: int = None):
    """`sort_slots` cut into the kernel's calls: a list of (codes, perm)
    of at most `max_positions` (MAX_POSITIONS unless given) positions
    each, whole chunks all. Calling the kernel on them in turn, each
    call on the pool the call before returned, with the update rows of
    `perm`, is `pool.at[slots].add(rows, mode="drop")`, additions in the
    batch's order: a run cut by a slice's end is written by one call and
    read again by the next."""
    step = MAX_POSITIONS if max_positions is None else max_positions
    step = max(chunk_rows, step // chunk_rows * chunk_rows)
    codes, perm = sort_slots(slots, n_slots, chunk_rows, step)
    return [(codes[lo:lo + step], perm[lo:lo + step])
            for lo in range(0, codes.shape[0], step)]


@functools.lru_cache(maxsize=None)
def _sources_sha() -> bytes:
    """A hash of everything the exported module is made from: the kernel
    (pallas_kernels.py) and this file, whose code layout (GROUP, the
    flags) is baked into it and whose `sort_slots` must stay its pair."""
    h = hashlib.sha256()
    for name in ("pallas_kernels.py", "writeback.py"):
        with open(os.path.join(os.path.dirname(__file__), name), "rb") as f:
            h.update(f.read())
    return h.digest()


@functools.lru_cache(maxsize=None)
def exported_kernel(n_slots: int, row_length: int, n: int, chunk_rows: int,
                    platform: str = "tpu", adagrad: bool = False):
    """The write-back kernel for a float32 [n_slots, row_length] pool
    and `n` sorted positions, as a `jax.export.Exported` for one
    platform (anything but a TPU gets the kernel in interpret mode: the
    tests), in one of its two forms. Plain
    (`scatter_add_sorted_rows`): `.call(pool, codes, upd_sorted)`.
    AdaGrad (`scatter_adagrad_sorted_rows`, what the fused step takes):
    `.call(pool, codes, g_sorted, acc_sorted, lr, eps)`, the halves
    [n, row_length / 2] and the two rates float32 scalars, operands and
    no statics. Inside a jitted program either is the kernel's custom
    call (the pool aliased to the result as in the kernel). Read from
    `<compile cache>/adapm_kernels/` where a process before this one
    left it (the file's name carries the form and a hash of the
    operands' shapes, jax's version and both source files), else made
    and left; a file that cannot be read is made anew."""
    from jax import export
    shape = jax.ShapeDtypeStruct
    f32 = functools.partial(shape, dtype=jnp.float32)
    operands = (f32((n, row_length // 2)), f32((n, row_length // 2)),
                f32(()), f32(())) if adagrad else (f32((n, row_length)),)
    name = "scatter_adagrad_sorted_rows" if adagrad else \
        "scatter_add_sorted_rows"
    path = None
    if jax.config.jax_compilation_cache_dir:
        key = hashlib.sha256(_sources_sha() + repr(
            (n_slots, row_length, n, chunk_rows, platform, jax.__version__,
             jax.lib.__version__, name,
             [x.shape for x in operands])).encode()).hexdigest()[:24]
        path = os.path.join(jax.config.jax_compilation_cache_dir,
                            "adapm_kernels", f"{name}-{key}.jaxexport")
        try:
            with open(path, "rb") as f:
                return export.deserialize(f.read())
        except Exception:  # absent, cut short or another jax's: make it
            pass
    from . import pallas_kernels
    exported = export.export(getattr(pallas_kernels, name),
                             platforms=(platform,))(
        f32((n_slots, row_length)), shape((n,), jnp.int32), *operands,
        chunk_rows=chunk_rows, interpret=platform != "tpu")
    if path is not None:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            f.write(exported.serialize())
        os.replace(tmp, path)  # whole or not at all
    return exported


def kernel(n_slots: int, row_length: int, n: int, chunk_rows: int,
           adagrad: bool = False):
    """The kernel at these sizes, for a program being traced for jax's
    default backend: `f(pool, codes, upd_sorted) -> pool`, or in the
    AdaGrad form `f(pool, codes, g_sorted, acc_sorted, lr, eps) ->
    pool`."""
    return exported_kernel(n_slots, row_length, n, chunk_rows,
                           jax.default_backend(), adagrad).call
