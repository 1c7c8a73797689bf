"""Static tile planning for the streaming pool kernels.

The pool pack/unpack kernels stream the gradient pool through VMEM in
~512KiB tiles instead of holding it resident (the whole-pool variants
stopped scaling exactly at AlexNet size — ROADMAP's retired 4M-element
fallback). Because the pool layout is compile-time static (the segment
table in ``GradientPool``), the entire DMA schedule is too: this module
intersects every leaf segment with every tile it touches and emits a flat
list of static copies — a segment that straddles a tile boundary simply
contributes one copy per tile it crosses. The kernels unroll the schedule
into ``pl.when(program_id == tile)`` blocks, so the compiler sees a fixed
per-tile copy list with no scatter/gather indexing at all.

Schedule size is O(num_leaves + num_tiles): each tile boundary splits at
most one segment, so a pool with L leaves and T tiles produces at most
L + T - 1 copies (plus the trailing-padding zero fills).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

# Per-operand tile target: comfortably inside VMEM (~16MiB/core) with
# double-buffering headroom, same sizing rule as chunk_l1norm.
TILE_TARGET_BYTES = 512 * 1024

# Mosaic tiles a 1-D 32-bit array in HBM and VMEM by 1024 elements: a DMA
# or ref slice of a 1-D operand must start at, and span, a multiple of
# this granule (or reach the array's end). The streaming kernels keep
# every DMA on the granule grid and move unaligned segments with vector
# loads/stores inside VMEM instead.
GRANULE = 1024

# Lane width: 1-D blocks and the last dim of 2-D blocks are multiples.
LANES = 128


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def out_struct(shape, dtype, like) -> jax.ShapeDtypeStruct:
    """ShapeDtypeStruct whose vma matches ``like`` (required when a kernel
    runs inside a manual shard_map region with check_vma)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


@dataclasses.dataclass(frozen=True)
class TileCopy:
    """One static copy between a leaf segment and a tile-local range.

    ``leaf`` indexes the segment table; ``src_lo`` is the offset inside
    that leaf, ``dst_lo`` the offset inside tile ``tile``'s VMEM slot.
    For zero fills (pool tail padding) ``leaf`` is -1 and ``src_lo`` 0.
    """

    leaf: int
    tile: int
    src_lo: int
    dst_lo: int
    elems: int


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """A pool's static streaming schedule: tiling plus the copy list."""

    pool_size: int
    tile_elems: int
    num_tiles: int
    copies: Tuple[TileCopy, ...]   # leaf <-> tile segment traffic
    fills: Tuple[TileCopy, ...]    # zero fills for the padding tail

    @property
    def num_copies(self) -> int:
        return len(self.copies)


def legal_tile(tile_elems: int, pool_size: int) -> bool:
    """A tile the compiled kernels accept: one tile spanning the whole
    pool, or a whole number of granules (so every tile boundary, and every
    pool-sized block of any dtype, sits on Mosaic's 1-D tile grid)."""
    return tile_elems >= pool_size or tile_elems % GRANULE == 0


def pick_tile(pool_size: int, chunk_elems: int, itemsize: int,
              target_bytes: int = TILE_TARGET_BYTES) -> int:
    """Tile size in elements. With a chunk census the tile is a whole
    number of chunks (rows x chunk_elems) so every tile emits complete
    per-chunk norms; without one it is a plain ~target_bytes range. The
    tile need NOT divide the pool — the final tile may be ragged (Pallas
    masks the edge block) and the copy schedule is clipped to the pool.
    Either way the tile is ``legal_tile``.
    """
    assert pool_size > 0 and itemsize > 0
    if chunk_elems > 0:
        assert pool_size % chunk_elems == 0, (pool_size, chunk_elems)
        num_chunks = pool_size // chunk_elems
        step = GRANULE // math.gcd(chunk_elems, GRANULE)
        rows = target_bytes // (chunk_elems * itemsize) // step * step
        return min(max(rows, step), num_chunks) * chunk_elems
    return min(pool_size, max(GRANULE,
                              target_bytes // itemsize // GRANULE * GRANULE))


@functools.lru_cache(maxsize=None)
def tile_schedule(offsets: Tuple[int, ...], sizes: Tuple[int, ...],
                  pool_size: int, tile_elems: int) -> TilePlan:
    """Intersect every segment with the tiles it spans (all static)."""
    assert len(offsets) == len(sizes)
    assert 0 < tile_elems
    num_tiles = -(-pool_size // tile_elems)  # cdiv
    copies = []
    for leaf, (off, sz) in enumerate(zip(offsets, sizes)):
        if sz == 0:
            continue
        assert off + sz <= pool_size, (off, sz, pool_size)
        for tile in range(off // tile_elems, (off + sz - 1) // tile_elems + 1):
            lo = max(off, tile * tile_elems)
            hi = min(off + sz, (tile + 1) * tile_elems)
            copies.append(TileCopy(leaf=leaf, tile=tile, src_lo=lo - off,
                                   dst_lo=lo - tile * tile_elems,
                                   elems=hi - lo))
    covered = (offsets[-1] + sizes[-1]) if sizes else 0
    fills = []
    if covered < pool_size:  # CSC chunk-alignment padding at the tail
        for tile in range(covered // tile_elems, num_tiles):
            lo = max(covered, tile * tile_elems)
            hi = min(pool_size, (tile + 1) * tile_elems)
            fills.append(TileCopy(leaf=-1, tile=tile, src_lo=0,
                                  dst_lo=lo - tile * tile_elems,
                                  elems=hi - lo))
    return TilePlan(pool_size=pool_size, tile_elems=tile_elems,
                    num_tiles=num_tiles, copies=tuple(copies),
                    fills=tuple(fills))


def itemsize(dtype) -> int:
    return jnp.dtype(dtype).itemsize


# -- granule-aligned staging -------------------------------------------------
#
# Leaf segments start and end anywhere in the pool, but a DMA may only move
# whole granules. So the kernels DMA granule-aligned *windows* of each leaf
# (leaves are padded to a granule multiple) between HBM and a VMEM stage
# buffer, and shift the unaligned segment between the stage buffer and the
# tile with static vector loads/stores (``Move``), which Mosaic lowers to
# masked, rotated accesses.


@dataclasses.dataclass(frozen=True)
class Window:
    """One DMA: ``elems`` leaf elements from leaf offset ``lo`` to offset
    ``base`` of tile ``tile``'s stage buffer (all granule multiples)."""

    leaf: int
    tile: int
    lo: int
    elems: int
    base: int


@dataclasses.dataclass(frozen=True)
class Move:
    """One vector move between the tile buffer of tile ``src_tile`` (at
    ``tile_lo``) and a stage buffer (at ``stage_lo``)."""

    src_tile: int
    tile_lo: int
    stage_lo: int
    elems: int


@dataclasses.dataclass(frozen=True)
class StagedTile:
    windows: Tuple[Window, ...]
    moves: Tuple[Move, ...]
    copies: Tuple[TileCopy, ...]   # this tile's entries of TilePlan.copies
    fills: Tuple[TileCopy, ...]    # ... and of TilePlan.fills


@dataclasses.dataclass(frozen=True)
class StagedPlan:
    """Per-tile windows and moves; ``stage_elems`` sizes each stage slot."""

    tiles: Tuple[StagedTile, ...]
    stage_elems: int

    @property
    def num_windows(self) -> int:
        return sum(len(t.windows) for t in self.tiles)


def padded_leaf(size: int) -> int:
    """Length a leaf is padded to so every window stays inside it."""
    return round_up(max(size, 1), GRANULE)


def _by_tile(plan: TilePlan, items) -> list:
    out = [[] for _ in range(plan.num_tiles)]
    for it in items:
        out[it.tile].append(it)
    return out


def _staged(plan: TilePlan, per_tile_windows, per_tile_moves) -> StagedPlan:
    copies, fills = _by_tile(plan, plan.copies), _by_tile(plan, plan.fills)
    tiles = tuple(StagedTile(tuple(per_tile_windows[t]),
                             tuple(per_tile_moves[t]), tuple(copies[t]),
                             tuple(fills[t]))
                  for t in range(plan.num_tiles))
    stage = max([sum(w.elems for w in t.windows) for t in tiles] + [GRANULE])
    return StagedPlan(tiles=tiles, stage_elems=stage)


@functools.lru_cache(maxsize=None)
def pack_stages(plan: TilePlan) -> StagedPlan:
    """Gather side (pack): each copy of tile t reads the granule window of
    its leaf that covers it into tile t's stage slot; one move then
    places the segment at its tile offset."""
    windows = [[] for _ in range(plan.num_tiles)]
    moves = [[] for _ in range(plan.num_tiles)]
    base = [0] * plan.num_tiles
    for c in plan.copies:
        lo = c.src_lo // GRANULE * GRANULE
        elems = round_up(c.src_lo + c.elems, GRANULE) - lo
        windows[c.tile].append(Window(c.leaf, c.tile, lo, elems,
                                      base[c.tile]))
        moves[c.tile].append(Move(c.tile, c.dst_lo,
                                  base[c.tile] + c.src_lo - lo, c.elems))
        base[c.tile] += elems
    return _staged(plan, windows, moves)


@functools.lru_cache(maxsize=None)
def unpack_stages(offsets: Tuple[int, ...], sizes: Tuple[int, ...],
                  plan: TilePlan) -> StagedPlan:
    """Scatter side (unpack): every granule of a leaf is written by ONE
    window, owned by the tile that holds the granule's last element, so
    windows never overlap. A granule that starts in the previous tile
    takes that part from the previous tile's buffer (still resident: the
    tile buffers are double-buffered); runs of granules with the same
    owner merge into one window."""
    tile = plan.tile_elems
    windows = [[] for _ in range(plan.num_tiles)]
    moves = [[] for _ in range(plan.num_tiles)]
    base = [0] * plan.num_tiles
    for leaf, (off, sz) in enumerate(zip(offsets, sizes)):
        g = 0
        n_gran = -(-sz // GRANULE)
        while g < n_gran:
            owner = (off + min((g + 1) * GRANULE, sz) - 1) // tile
            h = g + 1
            while h < n_gran and \
                    (off + min((h + 1) * GRANULE, sz) - 1) // tile == owner:
                h += 1
            lo, hi = g * GRANULE, min(h * GRANULE, sz)
            w = Window(leaf, owner, lo, (h - g) * GRANULE, base[owner])
            windows[owner].append(w)
            for src in (owner - 1, owner):
                a = max(off + lo, src * tile)
                b = min(off + hi, (src + 1) * tile)
                if a < b:
                    moves[owner].append(Move(src, a - src * tile,
                                             w.base + a - off - lo, b - a))
            base[owner] += w.elems
            g = h
    return _staged(plan, windows, moves)
