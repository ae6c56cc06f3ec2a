"""Pallas TPU kernels: alternative data-plane primitives.

Status: both kernels compile under the installed Mosaic compiler (jax
0.9.0 / libtpu 0.0.34) and match numpy on a v5e at the store's row width
— `chip_smoke.py`, part "kernels", checks that on every chip run; the
CPU tests run them in interpret mode only. Their SPEED has not been
measured on the current installation. On an earlier one (2026-07, see
docs/PERF.md "Pallas findings") XLA's native gather/scatter was the
fastest primitive for the random ~2 KB row accesses that dominate this
framework — the scalar-prefetch index-map gather below reached ~0.7x of
XLA's row rate — so the fused training step rides XLA (ops/fused.py) and
these are kept as working templates, reached only from cost calibration
(ops/costs.py). Manual-DMA kernels (make_async_copy from HBM refs) have
never been tried on this compiler (ROADMAP A3, C5).

The kernels use only the BlockSpec subset: grid pipelines + scalar
prefetch (compiler-generated, double-buffered DMA), no manual
semaphores.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


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
