"""Pallas TPU kernel: gather selected chunks into the dense wire buffer.

CSC's pack step (Fig 17) is a row gather: wire[j] = pool_chunks[idx[j]].
The kernel is pure data movement, HBM to HBM: the chunk ids sit in SMEM,
and a loop issues one DMA per selected chunk straight from the pool to its
slot of the wire buffer, keeping ``_DEPTH`` copies in flight. No chunk
passes through VMEM and there is no compute — which is exactly what the
pack step should be (it sits on the critical path between backward and
the allreduce). Chunks are whole multiples of ``tiling.GRANULE``
elements, so every DMA starts and ends on Mosaic's 1-D tile grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling

# DMAs kept in flight (one semaphore each).
_DEPTH = 8


def _kernel(idx_ref, src_ref, out_ref, sems, *, k: int, chunk: int):
    def copy(j):
        src = pl.multiple_of(idx_ref[j] * chunk, chunk)
        dst = pl.multiple_of(j * chunk, chunk)
        return pltpu.make_async_copy(src_ref.at[pl.ds(src, chunk)],
                                     out_ref.at[pl.ds(dst, chunk)],
                                     sems.at[j % _DEPTH])

    def issue(j, carry):
        @pl.when(j >= _DEPTH)
        def _():
            copy(j - _DEPTH).wait()  # frees semaphore j % _DEPTH

        copy(j).start()
        return carry

    def drain(j, carry):
        copy(j).wait()
        return carry

    jax.lax.fori_loop(0, k, issue, 0)
    jax.lax.fori_loop(max(k - _DEPTH, 0), k, drain, 0)


@functools.partial(jax.jit, static_argnames=("chunk_elems", "interpret"))
def csc_compact(pool: jax.Array, idx: jax.Array, chunk_elems: int,
                interpret: bool = True) -> jax.Array:
    """pool: (C*chunk,), idx: (k,) i32 -> wire buffer (k*chunk,)."""
    n = pool.shape[0]
    assert n % chunk_elems == 0, (n, chunk_elems)
    k = idx.shape[0]
    return pl.pallas_call(
        functools.partial(_kernel, k=k, chunk=chunk_elems),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=tiling.out_struct((k * chunk_elems,), pool.dtype, pool),
        scratch_shapes=[pltpu.SemaphoreType.DMA((_DEPTH,))],
        interpret=interpret,
    )(idx.astype(jnp.int32), pool)
