"""Pallas TPU kernel: per-chunk L1 norms over the gradient pool.

CSC's selection census (Fig 18) reads the whole pool once per step. As
separate XLA ops (abs → reshape → reduce) this costs extra HBM round trips;
the kernel does one streaming pass: each grid step loads a (rows, chunk)
tile of the pool into VMEM, reduces |x| along the chunk axis, and writes
``rows`` norms.

Tiling: the pool is viewed as (C, chunk_elems); block = (ROWS, chunk_elems)
where ROWS is a multiple of 8 chosen so the tile is ~512KiB — comfortably
inside VMEM (~16MiB/core) with double-buffering headroom. The norms leave
lane-dense: grid step i fills lanes [0, ROWS) of row i of a (T, 1, R)
array (R = ROWS rounded up to 128), which the wrapper flattens.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


def _kernel(pool_ref, out_ref):
    x = pool_ref[...].astype(jnp.float32)      # (rows, chunk)
    rows = x.shape[0]
    out_ref[0, 0, pl.ds(0, rows)] = jnp.sum(jnp.abs(x), axis=1)


def _pick_rows(num_chunks: int, chunk_elems: int, dtype) -> int:
    """Chunks per grid step: ~512KiB of pool, a multiple of 8 sublanes
    (or every chunk, when the pool has fewer)."""
    bytes_per_row = chunk_elems * jnp.dtype(dtype).itemsize
    rows = max(8, tiling.TILE_TARGET_BYTES // bytes_per_row // 8 * 8)
    return min(rows, num_chunks)


def lane_dense_norms(rows: int, num_tiles: int, like):
    """Out shape/spec of a per-tile norms block: grid step i writes its
    ``rows`` norms into lanes [0, rows) of row i of a (T, 1, R) array,
    R = rows rounded up to 128 lanes (a rank-1 (rows,) block is refused
    unless rows is a multiple of 128)."""
    r = tiling.round_up(rows, tiling.LANES)
    return (tiling.out_struct((num_tiles, 1, r), jnp.float32, like),
            pl.BlockSpec((1, 1, r), lambda i: (i, 0, 0)))


def unpack_norms(out: jax.Array, rows: int, num_chunks: int) -> jax.Array:
    """(T, 1, R) lane-dense norms -> f32[num_chunks]."""
    return out[:, 0, :rows].reshape(-1)[:num_chunks]


@functools.partial(jax.jit, static_argnames=("chunk_elems", "interpret"))
def chunk_l1norm(pool: jax.Array, chunk_elems: int,
                 interpret: bool = True) -> jax.Array:
    """pool: (C*chunk_elems,) any float dtype -> f32[C]."""
    n = pool.shape[0]
    assert n % chunk_elems == 0, (n, chunk_elems)
    c = n // chunk_elems
    rows = _pick_rows(c, chunk_elems, pool.dtype)
    grid = -(-c // rows)  # the last block may be ragged; its tail is dropped
    out_shape, out_spec = lane_dense_norms(rows, grid, pool)
    out = pl.pallas_call(
        _kernel,
        out_shape=out_shape,
        grid=(grid,),
        in_specs=[pl.BlockSpec((rows, chunk_elems), lambda i: (i, 0))],
        out_specs=out_spec,
        interpret=interpret,
    )(pool.reshape(c, chunk_elems))
    return unpack_norms(out, rows, c)
