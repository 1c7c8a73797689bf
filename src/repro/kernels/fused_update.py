"""Pallas TPU kernel: fused CSC-masked momentum-SGD update (Algorithm 1).

The update step touches five pool-sized HBM buffers (master, grads,
momentum, mask, optional LARS scale) and writes two. As discrete XLA ops
(add, mul, where, sub ...) the pool streams through HBM several times; at
~400M+ f32 elements (a 7B model's local shard) this memory-bound pass is
worth exactly one read+write of each operand — which is what a single
fused kernel achieves. Blocks are 1-D ranges of the pool sized to a few
hundred KiB of VMEM per operand.

``update_unpack`` below is the streaming tiled variant of the same
update: instead of writing a new master *pool* it DMAs each tile's
updated segments straight out to the per-tensor leaf buffers (grid
kernel in ``pool_unpack``; math shared via ``update_math``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


def update_math(master, grads, mom, mask, lr, *, momentum, weight_decay,
                scale=None):
    """The CSC-masked momentum-SGD step (Algorithm 1) on one tile/pool of
    values — the single elementwise pass every update kernel shares
    (``fused_update`` here, the streaming ``pool_unpack`` kernel, and the
    jnp oracles in ``ref.py`` compute exactly this)."""
    g = grads + weight_decay * master
    if scale is not None:
        g = g * scale
    u = momentum * mom + lr * g
    new_mom = jnp.where(mask, u, mom)
    new_master = jnp.where(mask, master - u, master)
    return new_master, new_mom


def _kernel(lr_ref, master_ref, grads_ref, mom_ref, mask_ref, scale_ref,
            new_master_ref, new_mom_ref, *, momentum, weight_decay,
            has_scale):
    new_master_ref[...], new_mom_ref[...] = update_math(
        master_ref[...], grads_ref[...], mom_ref[...], mask_ref[...],
        lr_ref[0], momentum=momentum, weight_decay=weight_decay,
        scale=scale_ref[...] if has_scale else None)


def _pick_block(n: int) -> int:
    blk = 128 * 1024  # 512KiB f32 per operand
    while n % blk:
        blk //= 2
        if blk < 1024:
            return n  # tiny/odd pools: single block
    return blk


@functools.partial(jax.jit, static_argnames=("momentum", "weight_decay",
                                             "interpret"))
def fused_update(master, grads, momentum_buf, mask, *, lr, momentum,
                 weight_decay, scale: Optional[jax.Array] = None,
                 interpret: bool = True) -> Tuple[jax.Array, jax.Array]:
    n = master.shape[0]
    blk = _pick_block(n)
    has_scale = scale is not None
    if scale is None:
        scale = jnp.ones((1,), jnp.float32)  # dummy operand, never read

    lr_arr = jnp.asarray(lr, jnp.float32).reshape(1)
    vec = pl.BlockSpec((blk,), lambda i: (i,))
    one = pl.BlockSpec((1,), lambda i: (0,))  # broadcast to every block
    scale_spec = vec if has_scale else one
    kern = functools.partial(_kernel, momentum=momentum,
                             weight_decay=weight_decay, has_scale=has_scale)
    return pl.pallas_call(
        kern,
        grid=(n // blk,),
        in_specs=[one, vec, vec, vec, vec, scale_spec],
        out_specs=(vec, vec),
        out_shape=(tiling.out_struct((n,), master.dtype, master),
                   tiling.out_struct((n,), momentum_buf.dtype, momentum_buf)),
        interpret=interpret,
    )(lr_arr, master, grads, momentum_buf, mask, scale)


def update_unpack(master, grads, momentum_buf, mask, offsets, sizes, *,
                  lr, momentum, weight_decay, scale=None, ratios=None,
                  tile_elems: int = 0, interpret: bool = True):
    """Tiled streaming variant of the update: the same Algorithm-1 math as
    ``fused_update`` (shared via ``update_math``), but instead of emitting
    a new master *pool* it streams each tile's updated values straight out
    to the per-tensor leaf buffers via the static segment table — the
    optimizer step and the pool→pytree unravel become ONE pass whose peak
    VMEM is O(tile) at every pool size. Implemented by the grid kernel in
    ``pool_unpack`` (the DMA-out mirror of ``pool_pack``); per-tensor LARS
    ``ratios`` expand to a per-element scale inside the tile, so no
    pool-sized scale buffer ever exists on this path.

    Returns (updated 1-D leaves in segment-table order, new momentum)."""
    from repro.kernels import pool_unpack as _pu
    return _pu.pool_unpack_update(
        master, grads, momentum_buf, mask, tuple(offsets), tuple(sizes),
        lr=lr, momentum=momentum, weight_decay=weight_decay, scale=scale,
        ratios=ratios, tile_elems=tile_elems, interpret=interpret)
