"""The fused step's write-back through the Pallas kernel
(`pallas_kernels.scatter_adagrad_sorted_rows`, and its plain form
`scatter_add_sorted_rows`): what the step needs of it without importing
`jax.experimental.pallas`.

The slots are sorted here, in plain XLA, and cut into slices of at most
`MAX_POSITIONS` sorted positions, one kernel call each (the kernel keeps
a call's codes in SMEM, so its size must not grow with the batch). What
the kernel does at a position is decided here as well, vector work over
all positions at once where the kernel's scalar loop paid 65 ns a
position for it (`sort_slots`'s codes, `chunk_meta`). The
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
# A code, one int32 a sorted position, holds everything the kernel's loop
# would otherwise decide: the slot (24 bits: 2^24 slots of at least 1 KB
# are the 16 GB of a v5e's HBM), the chunk position of the group buffer
# the position sums into (5 bits), whether it opens or closes a run of
# positions in one 8-row group, and in the sign that its slot is invalid
SLOT_MASK = (1 << 24) - 1
TGT_SHIFT, TGT_MASK = 24, 31
OPENS, CLOSES = 1 << 29, 1 << 30
# sorted positions one kernel call takes: its codes are one SMEM operand
# (scalar prefetch), 512 KB of the v5e's 1 MB at this many; twice as many
# no longer compile
MAX_POSITIONS = 1 << 17


def chunk_rows_for(row_length: int) -> int:
    """Positions a chunk: 32 at rows of up to 2048 floats (three group
    buffers of 2 MB; on the chip 16, 32 and 64 ran alike), fewer for
    longer rows so that the buffers stay a few MB of VMEM. Never more
    than a code's 5 bits of buffer position name."""
    return max(GROUP, min(TGT_MASK + 1,
                          (1 << 16) // row_length // GROUP * GROUP))


def sort_slots(slots: jnp.ndarray, n_slots: int, chunk_rows: int = 32,
               slice_positions: int = MAX_POSITIONS):
    """(codes, perm) for `scatter_add_sorted_rows`: the flattened slots
    sorted stably and padded to whole chunks, and the batch position of
    each. A code is the slot with the kernel's decisions above it, made
    here for all positions at once: the position opens (bit 29) or
    closes (bit 30) a run of positions in one 8-row group, the unit the
    kernel copies, and (bits 24-28) the place in its chunk's group
    buffer of the group it sums into: the chunk position of the run's
    opener, 0 where the run continues from the chunk before. Every
    `slice_positions` positions a run is closed and opened anew, so each
    such slice can be one call of the kernel. Slots outside the pool
    (negative ones wrap first, as jnp indexing does) sort last, so the
    valid positions of every slice are a prefix of it, and carry a
    NEGATIVE code, as padding positions do: no flag, row 0 of the buffer
    place of their own chunk position, which no run has (the kernel sums
    a chunk's positions without asking, and never writes that place
    back); their `perm` is clamped to the batch."""
    assert n_slots <= SLOT_MASK + 1 and chunk_rows <= TGT_MASK + 1, \
        (n_slots, chunk_rows)
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
    at = jax.lax.iota(jnp.int32, n + pad)
    if n + pad > slice_positions:
        opens |= at % slice_positions == 0
        closes |= at % slice_positions == slice_positions - 1
    at %= chunk_rows
    tgt = jax.lax.cummax(jnp.where(opens, at, 0).reshape(-1, chunk_rows),
                         axis=1).reshape(-1)
    codes = jnp.where(slot_sorted == INVALID_SLOT,
                      jnp.iinfo(jnp.int32).min | (at << TGT_SHIFT),
                      slot_sorted | (tgt << TGT_SHIFT)
                      | jnp.where(opens, OPENS, 0)
                      | jnp.where(closes, CLOSES, 0))
    return codes, jnp.minimum(perm, n - 1)


def chunk_meta(codes: jnp.ndarray, chunk_rows: int) -> jnp.ndarray:
    """What the kernel needs to know of a call's chunks before it walks
    them, int32 [chunks + 1]: of each chunk the groups it reads (its
    positions that open a run) and, 8 bits up, the groups it writes
    (those that close one): the copies the kernel waits for; and in the
    last word the chunks that hold a valid position at all, a prefix
    (`sort_slots`): the kernel visits no other, so a call whose slice
    is all dropped or padding does one loop test."""
    flags = codes.reshape(-1, chunk_rows)
    count = functools.partial(jnp.sum, axis=1, dtype=jnp.int32)
    return jnp.concatenate([
        count((flags & OPENS) != 0) | (count((flags & CLOSES) != 0) << 8),
        -(-jnp.sum(codes >= 0, dtype=jnp.int32)[None] // chunk_rows)])


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
