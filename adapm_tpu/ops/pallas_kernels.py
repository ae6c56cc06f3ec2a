"""Pallas TPU kernels: data-plane primitives.

Status (jax 0.9.0 / libtpu 0.0.34, v5e): all three compile under the
installed Mosaic compiler and match numpy on the chip — `chip_smoke.py`,
part "kernels", checks that on every chip run; the CPU tests run them in
interpret mode only.

The write-back kernel is on the main path, in two forms of ONE body
(`_scatter_add_kernel`). `scatter_adagrad_sorted_rows` is the fused
step's replica-free write-back on one chip (ops/fused.py
`writeback_uses_kernel`, `_kernel_writeback`): the step sorts the slots,
brings gradients and gathered accumulators into that order, and the
kernel forms each chunk's AdaGrad update rows
`[-lr * g * rsqrt(acc + g*g + eps) | g*g]` in VMEM and adds them to
their pool rows, so the update rows are never written to HBM nor read
back (PR 29; `lr`, `eps` are SMEM operands). `scatter_add_rows` /
`scatter_add_sorted_rows` is the plain form: rows given, added
(`chip_smoke.py`, `scripts/writeback_probe.py`, the tests). The step
takes the kernel through ops/writeback.py, exported once, so that a
process whose step comes from the compile cache does not import this
module or Pallas at all; one call for each 131,072 sorted positions,
because a call's codes are one SMEM operand. It is the first manual-DMA
kernel here (`make_async_copy` from HBM refs, own semaphores, copies of
three chunks in flight). Its loop over positions decides nothing: what
a position does (which buffer place it sums into, whether it starts a
read or a write) is decided for all positions at once in
`writeback.sort_slots` and rides in the position's code, and a call
visits only the chunks that hold a valid position (PR 48: the loop's
branches and carried scalars, not bytes, were 65 ns of the 72 a
position cost at 1 KB rows; PERF.md section 6). What it could NOT be
is a row-wise copy: the pool's layout is XLA's (8, 128) tiling, where
an 8 KB row is 16 pieces of 512 B, and Mosaic refuses a one-row slice
of a tiled memref ("must be aligned to tiling (8)"), in HBM and in VMEM
alike. So it moves whole 8-row groups. Measured on the chip (PERF.md
section 6, PR 25): 141-189 ns a row with the sort and the permutation
against 280 for XLA's scatter-add, bound by HBM bandwidth at 8 rows
moved for each one changed.

`gather_rows` and `adagrad_apply` use only the BlockSpec subset (grid
pipelines + scalar prefetch, compiler-generated double-buffered DMA, no
manual semaphores) and are templates, reached only from cost
calibration (ops/costs.py): XLA's native gather was the fastest
primitive for random row reads when they were last timed (2026-07,
before the chip's ledger: PERF.md section 7, "Not measured"), and a
manual-DMA gather meets the same tiling rule (ROADMAP A3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .writeback import (CLOSES, GROUP, OPENS, SLOT_MASK, TGT_MASK, TGT_SHIFT,
                        chunk_meta, sorted_slices)


def _copy_kernel(idx_ref, blk_ref, o_ref):
    o_ref[:] = blk_ref[:]


# apm-lint: disable=APM008 standalone Pallas TPU kernel (inherently
# backend-specific by definition): benchmarked in isolation, never
# dispatched by the PM planes — porting it IS writing a new backend
@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def gather_rows(pool: jnp.ndarray, block_idx: jnp.ndarray,
                block_rows: int = 8, interpret: bool = False) -> jnp.ndarray:
    """Gather `block_rows`-row blocks from a [slots, L] pool.

    block_idx[i] selects block i (rows block_idx[i]*block_rows ..+block_rows).
    The block index map is driven by the scalar-prefetched indices, so the
    pipeline overlaps each block's DMA with the previous block's copy-out —
    the canonical Pallas embedding-gather shape.
    """
    n = block_idx.shape[0]
    L = pool.shape[1]
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[pl.BlockSpec((block_rows, L),
                                   lambda i, idx_ref: (idx_ref[i], 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((block_rows, L),
                                   lambda i, idx_ref: (i, 0),
                                   memory_space=pltpu.VMEM)),
        out_shape=jax.ShapeDtypeStruct((n * block_rows, L), pool.dtype),
        interpret=interpret,
    )(block_idx, pool)


def _adagrad_kernel(g_ref, emb_ref, acc_ref, lr_ref, eps_ref,
                    new_emb_ref, new_acc_ref):
    g = g_ref[:]
    g2 = g * g
    acc = acc_ref[:] + g2
    new_acc_ref[:] = acc
    new_emb_ref[:] = emb_ref[:] - lr_ref[0] * g * jax.lax.rsqrt(
        acc + eps_ref[0])


# apm-lint: disable=APM008 standalone Pallas TPU kernel, same rationale
# as gather_rows above
@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def adagrad_apply(grads: jnp.ndarray, emb: jnp.ndarray, acc: jnp.ndarray,
                  lr: float, eps: float = 1e-10, block: int = 256,
                  interpret: bool = False):
    """Blocked AdaGrad transform over gathered rows: emb' = emb - lr * g /
    sqrt(acc + g^2 + eps); acc' = acc + g^2 (the update rule every
    bundled app uses — reference apps/mf/update.h:23-79). One VMEM-blocked
    pass; XLA fuses the same chain automatically, kept as a template."""
    n, L = grads.shape
    grid = pl.cdiv(n, block)
    lr_arr = jnp.full((1,), lr, jnp.float32)
    eps_arr = jnp.full((1,), eps, jnp.float32)
    spec = pl.BlockSpec((block, L), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    sspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _adagrad_kernel,
        grid=(grid,),
        in_specs=[spec, spec, spec, sspec, sspec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct((n, L), emb.dtype),
                   jax.ShapeDtypeStruct((n, L), acc.dtype)),
        interpret=interpret,
    )(grads, emb, acc, lr_arr, eps_arr)


# ---------------------------------------------------------------------------
# scatter_add_rows: the fused step's write-back as a sorted, pipelined
# read-modify-write with many copies in flight (manual DMA)
# ---------------------------------------------------------------------------

_GROUP_BUFFERS = 3  # chunk c's groups live in buffer c % 3
_AHEAD = _GROUP_BUFFERS - 1  # chunks whose reads are in flight


def _scatter_add_kernel(code_ref, meta_ref, _pool_in, *refs, rows: int,
                        n_chunks: int, adagrad: bool):
    """One pass over the chunks of `rows` sorted positions that hold a
    valid one (`meta_ref[n_chunks]` of the call's `n_chunks`: invalid
    slots sort last, and the tail they fill is not visited).

    code_ref, meta_ref: `sort_slots`'s codes and their `chunk_meta`,
    SMEM (scalar prefetch). `_pool_in` is the same HBM buffer as
    `pool_hbm` (input_output_aliases), the one name the kernel reads and
    writes the pool by. A pool of (8, 128)-tiled rows can only be copied
    in whole groups of 8 rows (Mosaic refuses a one-row slice of a tiled
    memref), so the unit read, summed into and written back is the 8-row
    group, 8 * L * 4 contiguous bytes. gbuf: [3, rows, 8, L] VMEM, one
    group per position that opens a run of its group; ubuf: [2, rows,
    L], the chunk's update rows.

    Where a chunk's update rows come from is the one thing the kernel's
    two forms differ in (`adagrad`, a static). Plain: `refs` starts with
    `upd_hbm` [n, L], the rows themselves in sorted order, copied to
    ubuf. AdaGrad: with `g_hbm`, `acc_hbm` ([n, L/2] each: gradients and
    accumulators as gathered, in sorted order) and `hyper` (SMEM,
    float32 [lr, eps]): the two halves are copied side by side into ubuf
    and the chunk's rows [-lr * g * rsqrt(acc + g*g + eps) | g*g] formed
    there in place, once a chunk, so the update rows are never in HBM.

    Only the first position of a run of one group reads it and only the
    last writes it, so no two copies in flight touch one group. A run
    that crosses a chunk boundary moves its group, as summed so far, to
    place 0 of the next chunk's buffer. The loop over a chunk's
    positions decides none of this: a position's code says where its
    group lives in the buffer, whether it opens a run (start the read,
    two chunks ahead) and whether it closes one (start the write), and
    `meta_ref` how many copies each chunk waits for, so the loop carries
    nothing from position to position, is unrolled, and its only
    branches are the starts of copies. A position is summed whether
    valid or not: the invalid ones of the last visited chunk name a
    place of the buffer that is never written back."""
    nb = _GROUP_BUFFERS
    if adagrad:
        g_hbm, acc_hbm, hyper, pool_hbm, gbuf, ubuf, gsem, usem, wsem = refs
        half = ubuf.shape[2] // 2
    else:
        upd_hbm, pool_hbm, gbuf, ubuf, gsem, usem, wsem = refs
    n_visit = meta_ref[n_chunks]

    def group_copy(code, b, j, sem, to_pool: bool):
        g0 = pl.multiple_of(code & (SLOT_MASK & ~(GROUP - 1)), GROUP)
        hbm = pool_hbm.at[pl.ds(g0, GROUP)]
        buf = gbuf.at[b, j]
        return pltpu.make_async_copy(buf, hbm, sem) if to_pool else \
            pltpu.make_async_copy(hbm, buf, sem)

    def upd_copies(c):
        """Chunk c's copies into ubuf[c % 2], all on usem[c % 2]."""
        s = c % _AHEAD
        at = pl.ds(pl.multiple_of(c * rows, rows), rows)
        if not adagrad:
            return [pltpu.make_async_copy(upd_hbm.at[at], ubuf.at[s],
                                          usem.at[s])]
        return [pltpu.make_async_copy(
            src.at[at], ubuf.at[s, :, pl.ds(lane, half)], usem.at[s])
            for src, lane in ((g_hbm, 0), (acc_hbm, half))]

    def form_chunk(c):
        """Wait for chunk c's copies; ubuf[c % 2] then holds its update
        rows (the AdaGrad form computes them from the two halves, all
        `rows` positions at once, dropped and padding ones too: their
        rows are never written back)."""
        s = c % _AHEAD
        for copy in upd_copies(c):
            copy.wait()
        if adagrad:
            g = ubuf[s, :, pl.ds(0, half)]
            g2 = g * g
            ubuf[s, :, pl.ds(0, half)] = -hyper[0] * g * jax.lax.rsqrt(
                ubuf[s, :, pl.ds(half, half)] + g2 + hyper[1])
            ubuf[s, :, pl.ds(half, half)] = g2

    def start_read(c, j, go=True):
        """Position j of chunk c: the read of its group, if it opens a
        run (a run that continues from the chunk before is moved, not
        read)."""
        code = code_ref[c * rows + j]
        b = c % nb

        @pl.when(go & ((code & OPENS) != 0))
        def _():
            group_copy(code, b, j, gsem.at[b], to_pool=False).start()

    def wait_all(count, b, sem):
        """Wait for `count` group copies on `sem`, reads into gbuf[b] or
        writes out of it: a DMA semaphore counts bytes, so one wait is
        good for any number of copies' worth, and the count's bits (at
        most `rows` copies a chunk) are at most six waits where a wait
        a copy was up to `rows`. The descriptor is never started: it
        only says how many bytes."""
        k = rows
        while k:
            @pl.when((count & k) != 0)
            def _(k=k):
                some = gbuf.at[b, pl.ds(0, k)]
                pltpu.make_async_copy(some, some, sem).wait()
            k //= 2

    def first_reads(c, carry):
        """The reads of chunk c < 2, before the walk: a loop, not hot."""
        for copy in upd_copies(c):
            copy.start()

        def one(j, carry):
            start_read(c, j)
            return carry
        return jax.lax.fori_loop(0, rows, one, carry)
    jax.lax.fori_loop(0, jnp.minimum(_AHEAD, n_visit), first_reads, None)

    def chunk(c, carry):
        b, s, base = c % nb, c % _AHEAD, c * rows
        form_chunk(c)
        wait_all(meta_ref[c] & 255, b, gsem.at[b])

        # place 0 continues the previous chunk's last run: take over its
        # group as summed so far
        @pl.when((code_ref[base] & OPENS) == 0)
        def _():
            gbuf[b, 0] = gbuf[(c + nb - 1) % nb,
                              (code_ref[base - 1] >> TGT_SHIFT) & TGT_MASK]

        # chunk c + 2 reads into the buffer of chunk c - 1
        @pl.when(c >= 1)
        def _():
            pb = (c + nb - 1) % nb
            wait_all(meta_ref[c - 1] >> 8, pb, wsem.at[pb])
        ahead = c + _AHEAD
        go = ahead < n_visit
        ahead = jnp.where(go, ahead, c)  # codes that exist, flags unread

        def position(j):
            code = code_ref[base + j]
            sub, tgt = code & (GROUP - 1), (code >> TGT_SHIFT) & TGT_MASK
            gbuf[b, tgt, pl.ds(sub, 1), :] = (
                gbuf[b, tgt, pl.ds(sub, 1), :] + ubuf[s, pl.ds(j, 1), :])

            @pl.when((code & CLOSES) != 0)
            def _():
                group_copy(code, b, tgt, wsem.at[b], to_pool=True).start()
            start_read(ahead, j, go)

        for j in range(rows):  # unrolled: 5 ns a position on a v5e
            position(j)

        # ubuf[s] is summed: chunk c + 2's update rows may land there
        @pl.when(go)
        def _():
            for copy in upd_copies(ahead):
                copy.start()
        return carry
    jax.lax.fori_loop(0, n_visit, chunk, None)

    @pl.when(n_visit > 0)
    def _():
        lb = (n_visit - 1) % nb
        wait_all(meta_ref[n_visit - 1] >> 8, lb, wsem.at[lb])


def _sorted_rows_call(pool, codes, *operands, chunk_rows: int,
                      interpret: bool, adagrad: bool):
    """The one `pallas_call` of `_scatter_add_kernel`: `operands` are
    (upd_sorted,) or, in the AdaGrad form, (g_sorted, acc_sorted,
    hyper)."""
    n_slots, L = pool.shape
    assert n_slots % GROUP == 0 and L % (256 if adagrad else 128) == 0 \
        and chunk_rows % GROUP == 0 and \
        codes.shape[0] % chunk_rows == 0, (pool.shape, chunk_rows)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_scatter_add_kernel, rows=chunk_rows,
                          n_chunks=codes.shape[0] // chunk_rows,
                          adagrad=adagrad),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[hbm, hbm, hbm, pl.BlockSpec(memory_space=pltpu.SMEM)]
            if adagrad else [hbm, hbm],
            out_specs=hbm,
            scratch_shapes=[
                pltpu.VMEM((_GROUP_BUFFERS, chunk_rows, GROUP, L),
                           pool.dtype),
                pltpu.VMEM((_AHEAD, chunk_rows, L), pool.dtype),
                pltpu.SemaphoreType.DMA((_GROUP_BUFFERS,)),
                pltpu.SemaphoreType.DMA((_AHEAD,)),
                pltpu.SemaphoreType.DMA((_GROUP_BUFFERS,))]),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=(_GROUP_BUFFERS * GROUP + _AHEAD) * chunk_rows
            * L * 4 + (4 << 20)),
        interpret=interpret,
    )(codes, chunk_meta(codes, chunk_rows), pool, *operands)


# apm-lint: disable=APM008 Pallas TPU kernel (backend-specific by
# definition; the fused step calls it only where writeback_uses_kernel
# holds); the jit makes roles of one shape share one trace of the kernel
@functools.partial(jax.jit, static_argnames=("chunk_rows", "interpret"))
def scatter_add_sorted_rows(pool: jnp.ndarray, codes: jnp.ndarray,
                            upd_sorted: jnp.ndarray, chunk_rows: int = 32,
                            interpret: bool = False) -> jnp.ndarray:
    """Add `upd_sorted[i]` to the row of `codes[i]` of a float32
    [slots, L] pool in HBM (slots a multiple of 8, L of 128), with many
    copies in flight; `codes` from `sort_slots`, the rows in its order.

    Duplicates are adjacent: a run of equal slots is summed in VMEM in
    the batch's own order (the sort is stable) onto the pool row, which
    is read once and written once, so every row ends as
    `pool + u1 + u2 + ...` with the u's in batch order. The pool is
    updated in place when the caller donates it."""
    return _sorted_rows_call(pool, codes, upd_sorted, chunk_rows=chunk_rows,
                             interpret=interpret, adagrad=False)


# apm-lint: disable=APM008 the same kernel, same rationale
@functools.partial(jax.jit, static_argnames=("chunk_rows", "interpret"))
def scatter_adagrad_sorted_rows(pool: jnp.ndarray, codes: jnp.ndarray,
                                g_sorted: jnp.ndarray,
                                acc_sorted: jnp.ndarray, lr, eps,
                                chunk_rows: int = 32,
                                interpret: bool = False) -> jnp.ndarray:
    """`scatter_add_sorted_rows` of the AdaGrad update rows
    `[-lr * g * rsqrt(acc + g*g + eps) | g*g]` of value rows
    [emb | accumulator] (L a multiple of 256: both halves whole lanes),
    formed inside the kernel from `g_sorted` and `acc_sorted` ([n, L/2]
    float32, in the order of `codes`). `acc_sorted` is the accumulator
    AS GATHERED, not the pool's: a key written before in the same step
    (another role of the pool, a call before this one) is still updated
    from the value its gradient was computed at. `lr`, `eps`: traced
    float32 scalars."""
    hyper = jnp.stack([jnp.asarray(lr, jnp.float32),
                       jnp.asarray(eps, jnp.float32)])
    return _sorted_rows_call(pool, codes, g_sorted, acc_sorted, hyper,
                             chunk_rows=chunk_rows, interpret=interpret,
                             adagrad=True)


def _slice_by_slice(kernel, pool, slots, rows, chunk_rows, max_positions):
    """`kernel(pool, codes, *rows in sorted order) -> pool`, one call for
    each `max_positions` (writeback.MAX_POSITIONS unless given) sorted
    positions of `slots`, each on the pool the call before returned."""
    for codes, perm in sorted_slices(slots, pool.shape[0], chunk_rows,
                                     max_positions):
        pool = kernel(pool, codes, *(r[perm] for r in rows))
    return pool


def scatter_add_rows(pool: jnp.ndarray, slots: jnp.ndarray,
                     upd: jnp.ndarray, chunk_rows: int = 32,
                     interpret: bool = False,
                     max_positions: int = None) -> jnp.ndarray:
    """`pool.at[slots].add(upd, mode="drop")` through
    `scatter_add_sorted_rows`: sort the slots, bring the update rows
    into that order (a gather), add."""
    return _slice_by_slice(
        functools.partial(scatter_add_sorted_rows, chunk_rows=chunk_rows,
                          interpret=interpret),
        pool, slots, (upd,), chunk_rows, max_positions)


def scatter_adagrad_rows(pool: jnp.ndarray, slots: jnp.ndarray,
                         g: jnp.ndarray, acc: jnp.ndarray, lr, eps,
                         chunk_rows: int = 32, interpret: bool = False,
                         max_positions: int = None) -> jnp.ndarray:
    """`scatter_add_rows` of the AdaGrad update rows of gradients `g`
    and gathered accumulators `acc` ([n, L/2] each), formed in the
    kernel (`scatter_adagrad_sorted_rows`): the fused step's write-back
    of one role (ops/fused.py `_kernel_writeback`)."""
    return _slice_by_slice(
        functools.partial(scatter_adagrad_sorted_rows, lr=lr, eps=eps,
                          chunk_rows=chunk_rows, interpret=interpret),
        pool, slots, (g, acc), chunk_rows, max_positions)
