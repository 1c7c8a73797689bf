"""Pallas TPU kernel: streaming tiled gradient-pool pack (paper §3.1, Fig 15).

One pass over the pool, never pool-resident: the grid walks ~512KiB tiles
of the output pool, and each grid step DMAs exactly the leaf slices that
land in its tile from HBM into a double-buffered VMEM scratch slot, casts
them to the wire dtype on the way out, and reduces the tile's chunk-L1
census from the same resident data. Peak VMEM is O(tile), independent of
pool size. ``ops.pool_pack`` runs it for packs with a census (quantized
wires), at every pool size; a pack with no census is one fused XLA pass
there (``ops._xla_pack``), since the kernel's only gain over XLA is the
census it folds into the pass. Its no-census mode stays as a test oracle.
The jnp twin in ``ref.py`` remains as the correctness oracle and the
shard_map/interpret fallback only.

Mechanics (see ``tiling.py`` for the schedule):

* The segment table (``GradientPool.offsets``/``sizes``) is compile-time
  static, so the leaf↔tile intersection schedule is too. A segment that
  straddles a tile boundary contributes one static copy per tile it
  crosses; the kernel unrolls the schedule into ``pl.when(i == tile)``
  blocks — a fixed DMA program, no scatter/gather indexing (the paper's
  "zero-copy" property).
* Leaves stay in HBM (``memory_space=ANY``). A DMA moves only whole
  1-D granules (``tiling.GRANULE``), so each copy reads the granule-aligned
  *window* of its (granule-padded) leaf into the tile's VMEM stage slot,
  and one static vector move shifts the segment to its tile offset
  (``tiling.pack_stages``). Tile t's windows are *started* at grid step
  t-1 into stage slot ``t % 2`` and *waited on* at step t, so the DMA for
  the next tile overlaps the cast+census compute of the current one
  (classic double buffering; the output tile is additionally pipelined by
  Pallas' own block machinery).
* The chunk census leaves lane-dense: (T, 1, R) blocks that the wrapper
  flattens (see ``chunk_l1norm.lane_dense_norms``).
* The trailing CSC padding is zero-filled per tile from the same static
  schedule, and the final tile may be ragged (the pool need not be a
  multiple of the tile) — Pallas masks the edge block.

The kernel unrolls into one ``pl.when`` block per tile holding O(copies
in the tile) DMAs and moves; at the default ~512KiB tile a 400M-element
shard unrolls ~3000 tiles, which is trace-heavy but compiles to a fixed
predicated copy list.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import chunk_l1norm as _cl
from repro.kernels import tiling


def _kernel(*refs, plan: tiling.TilePlan, staged: tiling.StagedPlan,
            n_leaves, chunk_elems, rows, with_norms, donated=False):
    # A donated wire-dtype staging buffer rides as the first operand; it
    # is aliased to the pool output and never read — every output tile is
    # fully written from the leaf DMAs (+ zero fills), so aliasing is
    # safe at any tile order.
    refs = refs[1:] if donated else refs
    leaf_refs = refs[:n_leaves]
    pool_ref = refs[n_leaves]
    norms_ref = refs[n_leaves + 1] if with_norms else None
    tile_buf, stage0, stage1, sems = refs[-4:]
    stages = (stage0, stage1)
    i = pl.program_id(0)

    def dma(w):
        return pltpu.make_async_copy(
            leaf_refs[w.leaf].at[pl.ds(w.lo, w.elems)],
            stages[w.tile % 2].at[pl.ds(w.base, w.elems)],
            sems.at[w.tile % 2])

    for t, st in enumerate(staged.tiles):
        @pl.when(i == t)
        def _(t=t, st=st):
            if t == 0:
                for w in st.windows:
                    dma(w).start()
            # Prefetch: tile t+1's windows fly while tile t computes.
            if t + 1 < plan.num_tiles:
                for w in staged.tiles[t + 1].windows:
                    dma(w).start()
            for w in st.windows:
                dma(w).wait()
            stage = stages[t % 2]
            for m in st.moves:
                tile_buf[pl.ds(m.tile_lo, m.elems)] = \
                    stage[pl.ds(m.stage_lo, m.elems)]
            for f in st.fills:  # trailing CSC padding → zeros
                tile_buf[pl.ds(f.dst_lo, f.elems)] = jnp.zeros(
                    (f.elems,), tile_buf.dtype)

    wire = tile_buf[...].astype(pool_ref.dtype)
    pool_ref[...] = wire
    if with_norms:
        x = wire.astype(jnp.float32).reshape(rows, chunk_elems)
        norms_ref[0, 0, pl.ds(0, rows)] = jnp.sum(jnp.abs(x), axis=1)


def plan(offsets: Tuple[int, ...], sizes: Tuple[int, ...], pool_size: int,
         chunk_elems: int, src_dtype, wire_dtype,
         tile_elems: int = 0) -> Dict:
    """Tile plan + analytic VMEM footprint (benchmarks / the CI kernel
    gate read this; the kernel itself builds from the same schedule)."""
    src_size = tiling.itemsize(_stage_dtype(src_dtype))
    if chunk_elems > 0:
        # Census pools hold whole chunks, and census tiles must too so
        # every tile emits complete per-chunk norms (the second assert
        # lives here, not only in pick_tile, because a forced tile_elems
        # bypasses pick_tile).
        assert pool_size % chunk_elems == 0, (pool_size, chunk_elems)
        if tile_elems:
            assert tile_elems % chunk_elems == 0, (tile_elems, chunk_elems)
    tile = tile_elems or tiling.pick_tile(pool_size, chunk_elems, src_size)
    assert tiling.legal_tile(tile, pool_size), (tile, pool_size)
    tile = min(tile, pool_size)
    sched = tiling.tile_schedule(tuple(offsets), tuple(sizes), pool_size,
                                 tile)
    staged = tiling.pack_stages(sched)
    rows = tile // chunk_elems if chunk_elems > 0 else 0
    vmem = tile * src_size                          # tile buffer
    vmem += 2 * staged.stage_elems * src_size       # double-buffered stage
    vmem += 2 * tile * tiling.itemsize(wire_dtype)  # pipelined out block
    if chunk_elems > 0:
        vmem += 2 * tiling.round_up(rows, tiling.LANES) * 4  # norms block
    return {"plan": sched, "staged": staged, "tile_elems": tile,
            "num_tiles": sched.num_tiles, "num_copies": sched.num_copies,
            "num_windows": staged.num_windows, "rows": rows,
            "vmem_bytes": vmem}


def _stage_dtype(src_dtype):
    """The tile buffer's dtype: the leaves' dtype, widened to 32 bits.
    Unaligned vector stores of packed (sub-32-bit) values are not
    lowered, and the widening is exact (the wire cast happens after)."""
    src = jnp.dtype(src_dtype)
    return src if src.itemsize == 4 else jnp.dtype(jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "offsets", "sizes", "pool_size", "chunk_elems", "wire_dtype",
    "tile_elems", "interpret"))
def pool_pack(
    leaves: Sequence[jax.Array],
    offsets: Tuple[int, ...],
    sizes: Tuple[int, ...],
    pool_size: int,
    chunk_elems: int,
    wire_dtype,
    tile_elems: int = 0,
    staging: Optional[jax.Array] = None,
    interpret: bool = True,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """1-D leaves -> (pool[pool_size] in wire dtype, f32 chunk norms).

    ``chunk_elems == 0`` skips the norm output (plain ravel+cast);
    ``tile_elems`` overrides the ~512KiB auto tile (tests force tiny tiles
    to exercise boundary straddling). ``staging`` optionally donates a
    wire-dtype pool buffer: it is aliased to the pool output
    (``input_output_aliases``), so a caller that threads the returned pool
    back in through a donated jit argument re-packs fully in place —
    the streaming-kernel form of the ref twin's staging contract (and the
    close of ROADMAP's "pack staging donation" item)."""
    wire = jnp.dtype(wire_dtype)
    with_norms = chunk_elems > 0
    assert leaves, "empty leaf list takes the ref path (ops.pool_pack)"
    src = _stage_dtype(jnp.result_type(*leaves))
    p = plan(offsets, sizes, pool_size, chunk_elems, src, wire, tile_elems)
    sched, staged, tile, rows = p["plan"], p["staged"], p["tile_elems"], \
        p["rows"]
    # DMA cannot cast: each leaf is promoted to the staging dtype (a no-op
    # for the uniform f32 common case) and padded to a granule multiple so
    # every window lies inside it (only leaves of an unaligned size copy).
    leaves = [x.astype(src) for x in leaves]
    leaves = [jnp.pad(x, (0, tiling.padded_leaf(x.shape[0]) - x.shape[0]))
              for x in leaves]
    like = leaves[0]
    out_shape = [tiling.out_struct((pool_size,), wire, like)]
    out_specs = [pl.BlockSpec((tile,), lambda i: (i,))]
    if with_norms:
        n_shape, n_spec = _cl.lane_dense_norms(rows, sched.num_tiles, like)
        out_shape.append(n_shape)
        out_specs.append(n_spec)
    donated = staging is not None
    if donated:
        assert staging.shape == (pool_size,) and staging.dtype == wire, (
            staging.shape, staging.dtype, pool_size, wire)
    kern = functools.partial(_kernel, plan=sched, staged=staged,
                             n_leaves=len(leaves), chunk_elems=chunk_elems,
                             rows=rows, with_norms=with_norms,
                             donated=donated)
    operands = ([staging] if donated else []) + list(leaves)
    out = pl.pallas_call(
        kern,
        grid=(sched.num_tiles,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(operands),
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        scratch_shapes=[pltpu.VMEM((tile,), src),
                        pltpu.VMEM((staged.stage_elems,), src),
                        pltpu.VMEM((staged.stage_elems,), src),
                        pltpu.SemaphoreType.DMA((2,))],
        input_output_aliases={0: 0} if donated else {},
        interpret=interpret,
    )(*operands)
    if not with_norms:
        return out[0], None
    return out[0], _cl.unpack_norms(out[1], rows, pool_size // chunk_elems)
