"""Pallas TPU kernel: ring allreduce (reduce-scatter + all-gather) over one
mesh axis — the paper's §2.4/§3.1 collective, owned instead of delegated
to an opaque ``psum``.

Schedule (rank d of N, segments of ``seg`` elements, see ``plan``):

    step t = 0 .. N-2   (reduce-scatter)
        send segment (d - t) % N        -> rank (d + 1) % N
        recv segment (d - t - 1) % N    <- rank (d - 1) % N, add into acc
    after N-1 steps rank d owns the fully reduced segment (d + 1) % N
    step t = 0 .. N-2   (all-gather)
        send segment (d + 1 - t) % N    -> rank (d + 1) % N
        recv segment (d - t) % N        <- rank (d - 1) % N, overwrite

2(N-1) neighbor exchanges total, each carrying one ``seg``-sized segment:
the bandwidth-optimal ring of the paper's Fig 7a. Mechanics:

* Segments travel in the **wire dtype** while the local accumulator
  stays **f32 in HBM** — the same mixed-precision wire contract as the
  pool pipeline (§2.5). Before the gather phase the owned segment is
  rounded through the wire dtype once, so every rank ends bit-identical
  (the optimizer's replicated update requires it).
* **Low-bit wires** (int8 / fp8-e4m3, ``repro.core.wire``): the caller
  passes pre-quantized scaled-domain words as ``x`` and the kernel runs
  the dequant-accumulate-requant cycle per hop — recv words up-cast to
  the f32 accumulator (dequant onto the in-flight grid), partial sums
  accumulate in f32, and each send requants through the wire grid
  (round-to-nearest for integer wires, where partial sums of per-rank
  qmax/N-clipped words stay exact integers within the grid, making the
  int8 ring lossless; fp8's non-uniform grid rounds per hop). The
  per-chunk scales ride alongside the wire buffer at the jnp level —
  dequantization to gradient units happens once after the ring, so the
  kernel stays alignment-agnostic w.r.t. chunk boundaries.
* Each exchange streams its segment through two VMEM send/recv slots of
  ~``tiling.TILE_TARGET_BYTES`` (the PR-3 slot pattern): the segment is
  padded up to a whole number of tiles (``plan``), so every sub-tile is
  full-sized and peak VMEM is O(tile) at any segment size — segments
  (pool/N) can far exceed VMEM for AlexNet-sized buckets. Sub-tiles
  drain serially (start→wait per copy); overlapping the next HBM load
  behind the in-flight RDMA is part of the on-TPU validation item in
  ROADMAP.
* Neighbor exchanges use ``pltpu.make_async_remote_copy`` addressed by
  mesh coordinates (the ring axis's neighbor position; every other mesh
  axis at this device's own coordinate). Flow control is **credit-based**, not
  barrier-based: after draining sub-tile k from its recv slot, a rank
  signals a credit to its LEFT neighbor (the sender); before writing
  sub-tile k (k >= 2) into the RIGHT neighbor's slot ``k % 2``, a rank
  consumes one credit from its RIGHT neighbor, proving that neighbor
  drained sub-tile k-2 from the same slot. Credits come only from the
  slot's actual consumer, so — unlike a signal-both-wait-2 barrier,
  where both signals can come from the same fast neighbor — no rank can
  ever overwrite an undrained slot, and ranks may skew freely by up to
  the 2-slot window. The sub-tile index k runs continuously across all
  2(N-1) steps, which also covers the step boundaries.
* Ragged pools pad to ``N * seg`` with zeros; ``ring_segment_bounds``
  describes the real (clipped) per-rank coverage — the final segment may
  be short or empty (pools smaller than N), which costs only padded wire
  bytes, never correctness.

The pure-jax ``lax.ppermute`` twin (``ref.ring_allreduce``) is the
correctness oracle and the CPU/interpret execution path: remote DMA has
no multi-device interpret mode, so ``ops.ring_allreduce`` dispatches to
the twin everywhere except compiled TPU (see ops docstring for the
vma-safe variant used under ``check_vma`` regions). ``chip_smoke.py
--chips 4`` checks the compiled kernel against a host sum and against
the twin on a four-chip host.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling

# Tile granule of the compiled ring, in elements: one (32, 128) tile, the
# Mosaic tile of the narrowest (int8) dtype the ring carries.
RING_ALIGN = 32 * tiling.LANES


def ring_segment_bounds(n_elems: int, n_ranks: int,
                        seg: Optional[int] = None,
                        ) -> Tuple[Tuple[int, int], ...]:
    """Static per-rank segmentation of a ring-reduced buffer.

    Rank r owns ``[r*seg, min((r+1)*seg, n_elems))`` with
    ``seg = ceil(n_elems / n_ranks)`` by default (``plan`` passes its
    tile-padded segment instead): equal segments, a ragged final one, and
    empty segments for ranks past the data (pools smaller than N). The
    bounds cover ``[0, n_elems)`` exactly once for any ``seg`` >= the
    default — the property test in tests/test_properties.py pins this
    for random sizes/ranks.
    """
    assert n_ranks >= 1, n_ranks
    if seg is None:
        seg = -(-n_elems // n_ranks) if n_elems else 0
    return tuple((min(r * seg, n_elems), min((r + 1) * seg, n_elems))
                 for r in range(n_ranks))


def plan(n_elems: int, n_ranks: int, wire_dtype,
         accum_dtype=jnp.float32, tile_elems: int = 0,
         src_dtype=None) -> Dict:
    """Static ring schedule + analytic VMEM/wire footprint.

    Pure python arithmetic (no devices): the benchmark ring gate and the
    step-count tests read ``exchange_steps`` / ``wire_bytes_per_step``
    from here, and the kernel builds from the same numbers.

    The kernel's sub-tile loop streams fixed-size tiles, so the segment
    is padded UP to a whole number of tiles (at most tile-1 elements of
    zeros per rank, ≤ ~512KiB of extra wire per step) — never the other
    way around: collapsing the tile to the segment would make VMEM
    O(segment) and break the streaming bound for the ragged segment
    sizes tensor-aligned buckets routinely produce.

    One-byte wire dtypes (int8 / fp8-e4m3) flow through unchanged:
    ``wire_bytes_per_step`` scales with the 1-byte itemsize (the 2x-over-
    bf16 reduction the kernel gate pins) and the default tile doubles in
    elements at the same ~512KiB byte budget.
    """
    wsize = tiling.itemsize(wire_dtype)
    asize = tiling.itemsize(accum_dtype)
    ssize = tiling.itemsize(src_dtype) if src_dtype is not None else wsize
    raw_seg = -(-n_elems // n_ranks) if (n_elems and n_ranks > 1) else \
        n_elems
    tile = tile_elems or min(max(raw_seg, 1),
                             max(1, tiling.TILE_TARGET_BYTES // wsize))
    seg = -(-raw_seg // tile) * tile if raw_seg else 0
    tiles_per_seg = seg // tile if seg else 0
    steps = 2 * (n_ranks - 1) if n_ranks > 1 else 0
    # Two wire send slots + two wire recv slots + the (2, tile) f32
    # staging the drain reads/writes through + the source-dtype seed
    # buffer: O(tile), segment-size independent.
    vmem = 2 * tile * wsize * 2 + 2 * tile * asize + tile * ssize
    return {
        "segment_bounds": ring_segment_bounds(n_elems, n_ranks,
                                              seg if n_ranks > 1 else None),
        "seg_elems": seg,
        "padded_elems": seg * n_ranks if n_ranks > 1 else n_elems,
        "exchange_steps": steps,
        "tiles_per_segment": tiles_per_seg,
        "tile_elems": tile,
        "wire_bytes_per_step": seg * wsize if n_ranks > 1 else 0,
        "total_wire_bytes": steps * seg * wsize,
        "vmem_bytes": vmem,
    }


def _kernel(ids_ref, x_ref, out_ref, send_buf, recv_buf, stage, seed_buf,
            send_sems, recv_sems, copy_sems, credit_sem, *, axis: str,
            n: int, seg: int, tile: int, wire, accum):
    """One rank's full 2(N-1)-step ring. ``ids_ref`` holds
    (me, right, left) — positions along the ring ``axis`` — in SMEM;
    ``x_ref``/``out_ref`` are the padded (n*seg,) source-dtype input and
    f32 accumulator in HBM, both viewed as (rows, 128); ``seg`` and
    ``tile`` count rows. Peers are addressed by mesh coordinates: the
    ring axis set to the neighbor's position, every other mesh axis left
    at this device's own coordinate, so a ring along ``data`` stays
    inside its row of a multi-axis mesh."""
    me = ids_ref[0]
    right = {axis: ids_ref[1]}
    left = {axis: ids_ref[2]}
    n_tiles = seg // tile
    integer_wire = jnp.issubdtype(jnp.dtype(wire), jnp.integer)

    def tile_ds(base, j):
        return pl.ds(pl.multiple_of(base + j * tile, tile), tile)

    def requant(vals):
        """f32 accumulator values -> the wire grid (the requant half of
        the low-bit dequant-accumulate-requant cycle; dequant is the
        ``.astype(accum)`` on the recv side). Integer wires (int8)
        round-to-nearest explicitly — astype truncates toward zero — and
        need no clip: quantized ring inputs are per-rank-clipped to
        qmax/N (repro.core.wire), so every partial sum is an exact
        integer within the grid and this requant is lossless. Float
        wires (bf16, fp8-e4m3) round via the cast itself."""
        if integer_wire:
            vals = jnp.round(vals)
        return vals.astype(wire)

    def rdma(slot):
        return pltpu.make_async_remote_copy(
            src_ref=send_buf.at[slot],
            dst_ref=recv_buf.at[slot],
            send_sem=send_sems.at[slot],
            recv_sem=recv_sems.at[slot],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.MESH,
        )

    # Start-up handshake with both neighbors: no peer may write into
    # this device's recv slots or signal its credit semaphore before
    # this kernel owns them.
    barrier = pltpu.get_barrier_semaphore()
    for peer in (left, right):
        pltpu.semaphore_signal(barrier, 1, device_id=peer,
                               device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(barrier, 2)

    # Seed the f32 accumulator from the input in its ORIGINAL dtype —
    # local contributions are never wire-rounded, exactly like the ref
    # twin (only segments in transit pass through the wire dtype).
    # Staging goes through seed_buf — OUR buffer, which no neighbor ever
    # writes — so a fast left neighbor racing ahead into the ring may
    # land its first sub-tiles in recv_buf while we are still seeding
    # without corrupting anything.
    def seed_tile(k, _):
        cp = pltpu.make_async_copy(x_ref.at[tile_ds(0, k)],
                                   seed_buf.at[...], copy_sems.at[0])
        cp.start()
        cp.wait()
        stage[0] = seed_buf[...].astype(accum)
        out = pltpu.make_async_copy(stage.at[0],
                                    out_ref.at[tile_ds(0, k)],
                                    copy_sems.at[0])
        out.start()
        out.wait()
        return _

    jax.lax.fori_loop(0, n * n_tiles, seed_tile, None)

    def exchange(step_no, send_idx, recv_idx, accumulate):
        """One ring step, sub-tile at a time (serial start→wait drain).

        ``k = step_no * n_tiles + j`` numbers sub-tiles continuously
        across the whole ring; slot ``k % 2`` may be rewritten only
        after the RIGHT neighbor's credit for its drain of sub-tile k-2
        arrives (window = the 2 slots)."""
        def body(j, _):
            k = step_no * n_tiles + j
            slot = k % 2

            @pl.when(k >= 2)
            def _():
                # Credit from the slot's consumer (our RIGHT neighbor):
                # it drained sub-tile k-2 from recv_buf[k % 2].
                pltpu.semaphore_wait(credit_sem, 1)

            # acc segment sub-tile -> f32 stage -> wire send slot.
            cp = pltpu.make_async_copy(
                out_ref.at[tile_ds(send_idx * seg, j)],
                stage.at[slot], copy_sems.at[slot])
            cp.start()
            cp.wait()
            send_buf[slot] = requant(stage[slot])
            rd = rdma(slot)
            rd.start()
            rd.wait()
            if accumulate:
                cp = pltpu.make_async_copy(
                    out_ref.at[tile_ds(recv_idx * seg, j)],
                    stage.at[slot], copy_sems.at[slot])
                cp.start()
                cp.wait()
                stage[slot] = stage[slot] + recv_buf[slot].astype(accum)
            else:
                stage[slot] = recv_buf[slot].astype(accum)
            out = pltpu.make_async_copy(
                stage.at[slot], out_ref.at[tile_ds(recv_idx * seg, j)],
                copy_sems.at[slot])
            out.start()
            out.wait()
            # Drained: our LEFT neighbor (the sender into this slot) may
            # reuse the slot for its sub-tile k+2.
            pltpu.semaphore_signal(
                credit_sem, inc=1, device_id=left,
                device_id_type=pltpu.DeviceIdType.MESH)
            return _

        jax.lax.fori_loop(0, n_tiles, body, None)

    # Reduce-scatter: N-1 accumulate exchanges.
    def rs_step(t, _):
        exchange(t, (me - t) % n, (me - t - 1) % n, accumulate=True)
        return _

    jax.lax.fori_loop(0, n - 1, rs_step, None)

    # Round the owned segment through the wire dtype once so every rank
    # gathers bit-identical values (matches the ref twin). Local only:
    # stage/copy_sems, no credits involved.
    own = (me + 1) % n
    if jnp.dtype(wire) != jnp.dtype(accum):
        def wire_round(j, _):
            cp = pltpu.make_async_copy(out_ref.at[tile_ds(own * seg, j)],
                                       stage.at[0], copy_sems.at[0])
            cp.start()
            cp.wait()
            stage[0] = requant(stage[0]).astype(accum)
            out = pltpu.make_async_copy(
                stage.at[0], out_ref.at[tile_ds(own * seg, j)],
                copy_sems.at[0])
            out.start()
            out.wait()
            return _

        jax.lax.fori_loop(0, n_tiles, wire_round, None)

    # All-gather: N-1 overwrite exchanges; the continuous sub-tile index
    # keeps the credit accounting seamless across the phase switch.
    def ag_step(t, _):
        exchange(n - 1 + t, (me + 1 - t) % n, (me - t) % n,
                 accumulate=False)
        return _

    jax.lax.fori_loop(0, n - 1, ag_step, None)
    # The right neighbor's credits for the last two sub-tiles are never
    # consumed by a send: take them, so the semaphore leaves at zero and
    # no signal lands after this kernel has exited.
    pltpu.semaphore_wait(credit_sem, min(2, 2 * (n - 1) * n_tiles))


@functools.partial(jax.jit, static_argnames=(
    "axis_name", "axis_size", "wire_dtype", "tile_elems", "collective_id"))
def ring_allreduce(x: jax.Array, axis_name: str, axis_size: int, *,
                   wire_dtype=None, tile_elems: int = 0,
                   collective_id: int = 0) -> jax.Array:
    """Compiled-TPU ring allreduce of the 1-D ``x`` over ``axis_name``.

    Must be called inside the manual shard_map region that owns
    ``axis_name``; peers are addressed by mesh coordinates (see
    ``_kernel``), so the ring may run along one axis of a multi-axis
    mesh. ``collective_id`` must be distinct for every ring that can be
    live in the same compiled program AND identical across hosts for the
    same logical ring — GradientFlow stamps the bucket index through
    ``ops.ring_allreduce`` (host-invariant by construction); two
    concurrent kernels sharing an id would share Mosaic's collective
    bookkeeping. CPU/interpret callers never reach this —
    ``ops.ring_allreduce`` routes them to the ``ref`` ppermute twin, the
    semantic ground truth this kernel is validated against.
    """
    n = int(axis_size)
    if n == 1:
        return x
    out_dtype = x.dtype
    wire = jnp.dtype(wire_dtype) if wire_dtype is not None else x.dtype
    accum = jnp.float32
    p = plan(x.shape[0], n, wire, accum, tile_elems, src_dtype=x.dtype)
    # Every buffer is (rows, 128): whole (32, 128) tiles of the narrowest
    # dtype the ring carries (int8 words), so every row slice of an HBM
    # operand and every slot of a VMEM buffer sits on Mosaic's tile grid.
    # Only segments shorter than one such tile grow here.
    tile = tiling.round_up(p["tile_elems"], RING_ALIGN)
    seg = tiling.round_up(p["seg_elems"], tile)
    # The input rides in its ORIGINAL dtype: local contributions reach
    # the f32 accumulator unrounded (matching the ref twin); only the
    # in-flight segments are cast to the wire dtype inside the kernel.
    pad = seg * n - x.shape[0]
    xp = x if not pad else jnp.concatenate(
        [x, jnp.zeros((pad,), x.dtype)])
    lanes = tiling.LANES
    tr = tile // lanes
    me = jax.lax.axis_index(axis_name)
    ids = jnp.stack([me, (me + 1) % n, (me - 1) % n]).astype(jnp.int32)
    kern = functools.partial(_kernel, axis=axis_name, n=n,
                             seg=seg // lanes, tile=tr, wire=wire,
                             accum=accum)
    out = pl.pallas_call(
        kern,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((seg * n // lanes, lanes), accum),
        scratch_shapes=[pltpu.VMEM((2, tr, lanes), wire),    # send slots
                        pltpu.VMEM((2, tr, lanes), wire),    # recv slots
                        pltpu.VMEM((2, tr, lanes), accum),   # f32 staging
                        pltpu.VMEM((tr, lanes), x.dtype),    # seed buffer
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.REGULAR],        # credits
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id),
        interpret=False,
    )(ids, xp.reshape(-1, lanes))
    out = out.reshape(-1)
    return out[:x.shape[0]].astype(out_dtype)
