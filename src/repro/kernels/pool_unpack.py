"""Pallas TPU kernel: streaming tiled pool unpack + momentum-SGD update.

The DMA-out mirror of ``pool_pack``: the grid walks ~512KiB tiles of the
pool, each step computes the CSC-masked momentum-SGD update (Algorithm 1,
shared ``fused_update.update_math``) on the tile's slice of the
master/grads/momentum/mask operands — all streamed in by Pallas' block
pipeline — and then DMAs each updated *segment* of the tile straight out
to its own per-tensor leaf buffer via the static segment table. The new
master pool is never materialized in HBM and peak VMEM is O(tile),
independent of pool size; this retires the whole-pool-in-VMEM variant and
its 4M-element ref fallback (``ref.pool_unpack_update`` remains as the
correctness oracle and the shard_map/interpret fallback only).

Double buffering runs on the *output* side here: tile t's updated values
are kept in VMEM buffer ``t % 2``, moved (static vector stores) into
granule-aligned windows of stage slot ``t % 2``, and the windows' DMAs to
the (granule-padded) leaves started at step t but waited on at step t+1
— the copies drain while the next tile computes. A DMA moves only whole
1-D granules, so every granule of a leaf belongs to exactly one window,
owned by the tile holding its last element; the part of it in the
previous tile comes from that tile's still-resident buffer
(``tiling.unpack_stages``). The final tile may be ragged; windows only
carry pool elements, so no garbage edge lane reaches a leaf's real
elements, and the granule padding is sliced off.

LARS rides along without its pool-sized scale buffer: pass the per-tensor
``ratios`` vector (O(num_tensors), SMEM-resident) and each tile expands it
to a per-element scale in VMEM from the same static schedule — padding
ranges scale by 1.0, matching the ref twin's expanded-scale semantics.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling
from repro.kernels.fused_update import update_math


def _kernel(*refs, plan: tiling.TilePlan, staged: tiling.StagedPlan,
            n_leaves, momentum, weight_decay, has_scale, has_ratios):
    (lr_ref, master_ref, grads_ref, mom_ref, mask_ref, scale_ref,
     ratios_ref) = refs[:7]
    new_mom_ref = refs[7]
    leaf_refs = refs[8:8 + n_leaves]
    buf0, buf1, stage0, stage1, sems, scale_scratch = refs[8 + n_leaves:]
    bufs, stages = (buf0, buf1), (stage0, stage1)
    i = pl.program_id(0)
    last = plan.num_tiles - 1

    if has_ratios:
        # Expand the per-tensor ratios to a per-element scale tile: one
        # static ranged fill per segment in this tile, 1.0 for padding.
        for t, st in enumerate(staged.tiles):
            @pl.when(i == t)
            def _(st=st):
                for c in st.copies:
                    scale_scratch[pl.ds(c.dst_lo, c.elems)] = jnp.full(
                        (c.elems,), ratios_ref[c.leaf], scale_scratch.dtype)
                for f in st.fills:
                    scale_scratch[pl.ds(f.dst_lo, f.elems)] = jnp.ones(
                        (f.elems,), scale_scratch.dtype)

    scale = None
    if has_scale:
        scale = scale_ref[...]
    elif has_ratios:
        scale = scale_scratch[...]
    new_master, new_mom = update_math(
        master_ref[...], grads_ref[...], mom_ref[...], mask_ref[...],
        lr_ref[0], momentum=momentum, weight_decay=weight_decay,
        scale=scale)
    new_mom_ref[...] = new_mom

    def dma(w):
        return pltpu.make_async_copy(
            stages[w.tile % 2].at[pl.ds(w.base, w.elems)],
            leaf_refs[w.leaf].at[pl.ds(w.lo, w.elems)],
            sems.at[w.tile % 2])

    for t, st in enumerate(staged.tiles):
        @pl.when(i == t)
        def _(t=t, st=st):
            bufs[t % 2][...] = new_master
            stage = stages[t % 2]
            for m in st.moves:
                stage[pl.ds(m.stage_lo, m.elems)] = \
                    bufs[m.src_tile % 2][pl.ds(m.tile_lo, m.elems)]
            for w in st.windows:
                dma(w).start()
            # Tile t-1's windows drained while this tile computed; the
            # last tile waits for its own in-step.
            for u in range(max(t - 1, 0), t + 1 if t == last else t):
                for w in staged.tiles[u].windows:
                    dma(w).wait()


def plan(offsets: Tuple[int, ...], sizes: Tuple[int, ...], pool_size: int,
         master_dtype, *, has_scale: bool = False, has_ratios: bool = False,
         tile_elems: int = 0):
    """Tile plan + analytic VMEM footprint (benchmarks / CI gate)."""
    msize = tiling.itemsize(master_dtype)
    tile = tile_elems or tiling.pick_tile(pool_size, 0, msize)
    assert tiling.legal_tile(tile, pool_size), (tile, pool_size)
    tile = min(tile, pool_size)
    sched = tiling.tile_schedule(tuple(offsets), tuple(sizes), pool_size,
                                 tile)
    staged = tiling.unpack_stages(tuple(offsets), tuple(sizes), sched)
    # Pipelined input blocks (x2 each): master, grads, momentum, mask,
    # optional pool-sized scale; pipelined new-momentum out block; the
    # double-buffered tile buffers and stage slots; the ratio-expansion
    # scratch.
    per_elem = msize * 3 + 1 + (4 if has_scale else 0)
    vmem = 2 * tile * per_elem
    vmem += 2 * tile * 4                        # new_mom out block
    vmem += 2 * tile * msize                    # tile buffers
    vmem += 2 * staged.stage_elems * msize      # stage slots
    if has_ratios:
        vmem += tile * 4          # scale_scratch
    return {"plan": sched, "staged": staged, "tile_elems": tile,
            "num_tiles": sched.num_tiles, "num_copies": sched.num_copies,
            "num_windows": staged.num_windows, "vmem_bytes": vmem}


@functools.partial(jax.jit, static_argnames=(
    "offsets", "sizes", "momentum", "weight_decay", "tile_elems",
    "interpret"))
def pool_unpack_update(
    master: jax.Array,
    grads: jax.Array,
    momentum_buf: jax.Array,
    mask: jax.Array,
    offsets: Tuple[int, ...],
    sizes: Tuple[int, ...],
    *,
    lr,
    momentum: float,
    weight_decay: float,
    scale: Optional[jax.Array] = None,
    ratios: Optional[jax.Array] = None,
    tile_elems: int = 0,
    interpret: bool = True,
) -> Tuple[List[jax.Array], jax.Array]:
    """Returns (updated 1-D leaves in segment-table order, new momentum).

    ``scale`` is a pool-sized per-element LR scale; ``ratios`` the
    per-tensor LARS vector expanded on the fly inside the kernel (pass at
    most one). ``tile_elems`` overrides the ~512KiB auto tile."""
    n = master.shape[0]
    has_scale, has_ratios = scale is not None, ratios is not None
    assert not (has_scale and has_ratios), "pass scale OR ratios, not both"
    p = plan(offsets, sizes, n, master.dtype, has_scale=has_scale,
             has_ratios=has_ratios, tile_elems=tile_elems)
    sched, staged, tile = p["plan"], p["staged"], p["tile_elems"]
    if scale is None:
        scale = jnp.ones((1,), jnp.float32)   # dummy operand, never read
    if ratios is None:
        ratios = jnp.ones((1,), jnp.float32)  # dummy operand, never read
    lr_arr = jnp.asarray(lr, jnp.float32).reshape(1)
    vec = pl.BlockSpec((tile,), lambda i: (i,))
    one = pl.BlockSpec((1,), lambda i: (0,))  # broadcast to every tile
    kern = functools.partial(
        _kernel, plan=sched, staged=staged, n_leaves=len(sizes),
        momentum=momentum, weight_decay=weight_decay, has_scale=has_scale,
        has_ratios=has_ratios)
    # Leaves come out padded to a granule multiple (windows write whole
    # granules); the padding is sliced off below.
    out_shape = tuple(
        [tiling.out_struct((n,), momentum_buf.dtype, momentum_buf)]
        + [tiling.out_struct((tiling.padded_leaf(sz),), master.dtype, master)
           for sz in sizes])
    out_specs = tuple(
        [vec] + [pl.BlockSpec(memory_space=pl.ANY)] * len(sizes))
    out = pl.pallas_call(
        kern,
        grid=(sched.num_tiles,),
        in_specs=[one, vec, vec, vec, vec,
                  vec if has_scale else one,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((tile,), master.dtype),
                        pltpu.VMEM((tile,), master.dtype),
                        pltpu.VMEM((staged.stage_elems,), master.dtype),
                        pltpu.VMEM((staged.stage_elems,), master.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        # Ratio-expansion scratch only when used, so the
                        # plan()'s VMEM accounting stays exact.
                        pltpu.VMEM((tile,) if has_ratios else (1,),
                                   jnp.float32)],
        interpret=interpret,
    )(lr_arr, master, grads, momentum_buf, mask, scale, ratios)
    leaves = [x if x.shape[0] == sz else x[:sz]
              for x, sz in zip(out[1:], sizes)]
    return leaves, out[0]
