"""Gradient memory pool (paper §3.1, Figure 15).

The paper places all gradient tensors in one contiguous memory pool ordered
by *generation order* — the backward pass produces layer-n's gradients first,
so tensor-m (top layer) sits at offset 0 and tensor-1 (bottom layer) at the
end. Fused (lazy) allreduce then operates on contiguous pool prefixes with no
gather/copy cost, and chunk-granular CSC indexes the same buffer.

In JAX the analogue is a deterministic ravel of the gradient pytree into a
1-D vector using **reversed flatten order** (params flatten bottom-up:
embedding → layers → head; backward generates head-first), plus metadata
(offsets / sizes / names) so that:

  * ``pack`` builds the pool in a single pass (cast, place at the static
    offsets, optional fused chunk-L1 census; ``pack_into`` threads a
    donated staging buffer so steady-state steps allocate nothing
    pool-sized);

  * lazy allreduce can split the pool into θ-element buckets whose psum
    depends only on the grads inside the bucket (XLA can then overlap each
    bucket's collective with the remaining backward compute);
  * CSC can view the pool as (n_chunks, chunk_elems);
  * LARS can compute per-tensor norms via segment offsets.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """Metadata for one gradient tensor inside the pool."""

    name: str
    shape: Tuple[int, ...]
    dtype: Any
    size: int
    offset: int  # start offset in the pool, in elements


@dataclasses.dataclass(frozen=True)
class PoolView:
    """Bucket-aligned view of a pool span: the segment-table rows whose
    tensors live entirely inside ``[start, end)``, with offsets rebased to
    the span start.

    This is the update-side contract of the overlap engine
    (``repro.core.engine``): buckets close at tensor boundaries, so every
    bucket maps to a *whole* run of segment-table rows plus (for the final
    bucket) the pool's padding tail — which means the per-bucket optimizer
    update can reuse the exact same kernels as the whole-pool path, just
    with the view's sub-table (the streaming ``TilePlan`` restricted to
    the bucket span falls out of ``tiling.tile_schedule`` on the
    sub-table).
    """

    start: int                      # span bounds in pool elements
    end: int
    leaf_lo: int                    # segment-table row range [lo, hi)
    leaf_hi: int
    specs: Tuple["LeafSpec", ...]   # the rows themselves (absolute offsets)
    offsets: Tuple[int, ...]        # rebased to ``start``
    sizes: Tuple[int, ...]
    padding: int                    # trailing pool-padding elems in span

    @property
    def size(self) -> int:
        return self.end - self.start

    @property
    def num_tensors(self) -> int:
        return self.leaf_hi - self.leaf_lo


def _leaf_name(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


class GradientPool:
    """Bidirectional map between a gradient pytree and the 1-D pool.

    Built once from the *parameter* pytree structure (shapes only — accepts
    ShapeDtypeStructs), reused every step. Padding to a multiple of
    ``pad_to`` elements (CSC chunk size) is appended at the end so the pool
    reshapes exactly to (n_chunks, chunk_elems).
    """

    def __init__(self, params: Any, pad_to: int = 1):
        leaves_with_path = jax.tree_util.tree_flatten_with_path(params)[0]
        self.treedef = jax.tree_util.tree_structure(params)
        # Reverse generation order: backward produces the *last* flatten-order
        # leaves first (head / top layers), so the pool starts with them.
        ordered = list(reversed(leaves_with_path))
        specs: List[LeafSpec] = []
        offset = 0
        for path, leaf in ordered:
            size = int(np.prod(leaf.shape)) if leaf.shape else 1
            specs.append(
                LeafSpec(
                    name=_leaf_name(path),
                    shape=tuple(leaf.shape),
                    dtype=jnp.dtype(leaf.dtype),
                    size=size,
                    offset=offset,
                ))
            offset += size
        self.specs: Tuple[LeafSpec, ...] = tuple(specs)
        self.unpadded_size = offset
        self.pad_to = max(int(pad_to), 1)
        rem = offset % self.pad_to
        self.padding = (self.pad_to - rem) % self.pad_to
        self.size = offset + self.padding
        # Static segment table, precomputed once: python tuples that
        # specialize the pack/unpack kernels — every slice, and the whole
        # leaf<->tile DMA schedule of the streaming kernels, is a
        # compile-time constant derived from these.
        self.offsets: Tuple[int, ...] = tuple(s.offset for s in self.specs)
        self.sizes: Tuple[int, ...] = tuple(s.size for s in self.specs)

    # -- single-pass pack / unpack (the pipeline entry points) -------------

    def flat_leaves(self, grads: Any) -> List[jax.Array]:
        """Pytree → 1-D leaves in pool (reverse-generation) order, with
        shape checks against the layout this pool was built for."""
        leaves = list(reversed(jax.tree_util.tree_leaves(grads)))
        assert len(leaves) == len(self.specs), (
            f"pool built for {len(self.specs)} leaves, got {len(leaves)}")
        out = []
        for leaf, spec in zip(leaves, self.specs):
            assert tuple(leaf.shape) == spec.shape, (
                f"{spec.name}: expected {spec.shape}, got {leaf.shape}")
            out.append(leaf.reshape((-1,)))
        return out

    def unflatten(self, leaves_1d: Sequence[jax.Array]) -> Any:
        """1-D leaves in pool order → pytree (inverse of flat_leaves)."""
        assert len(leaves_1d) == len(self.specs)
        shaped = [x.reshape(spec.shape)
                  for x, spec in zip(leaves_1d, self.specs)]
        return jax.tree_util.tree_unflatten(self.treedef,
                                            list(reversed(shaped)))

    def pack(self, grads: Any, dtype: Any = None, *,
             norms_chunk: int = 0, use_kernels: bool = False,
             out: Optional[jax.Array] = None, tile_elems: int = 0,
             ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """Pytree → (1-D pool, optional f32 per-chunk L1 norms), one pass.

        Fuses what used to be three passes — concatenate-ravel, wire-dtype
        cast, chunk-norm census — into a single sweep. With kernels off
        each leaf is written into its static segment of one preallocated
        buffer, with a single trailing cast to ``dtype``. ``norms_chunk >
        0`` additionally emits the per-chunk L1 norms of the packed (wire)
        values. ``out`` optionally supplies the staging buffer (see
        ``pack_into`` for the donation-threading variant that returns it).

        ``use_kernels=True`` routes through ``kernels.ops.pool_pack``: a
        pack with no census is one fused XLA pass (convert and
        concatenate, no pool-sized buffer in the leaves' dtype); a census
        pack runs the streaming tiled Pallas kernel at every pool size,
        leaf slices DMA'd through ~512KiB VMEM tiles (``tile_elems``
        overrides the auto tile)."""
        pool, norms, _ = self._pack(grads, dtype, norms_chunk, use_kernels,
                                    out, tile_elems)
        return pool, norms

    def pack_into(self, out: jax.Array, grads: Any, dtype: Any = None, *,
                  norms_chunk: int = 0, use_kernels: bool = False,
                  tile_elems: int = 0,
                  ) -> Tuple[jax.Array, Optional[jax.Array], jax.Array]:
        """Donation-aware pack: writes into the staging buffer ``out`` and
        returns (pool, norms, staging) so the caller can thread the
        staging buffer through a donated jit argument — steady-state packs
        then allocate no pool-sized buffer and skip the zero-fill
        entirely.

        Two staging contracts, selected by ``out``'s dtype:

        * leaves' (source) dtype — the ref path stages in place and casts
          to ``dtype`` in one trailing pass (the original contract);
        * wire dtype with ``use_kernels=True`` — ``out`` is donated to the
          pack (the census kernel aliases it to its pool output through
          ``input_output_aliases``; the no-census XLA pass takes it as a
          donated argument), so the returned pool IS the staging for the
          next step: one wire-dtype buffer, re-written every pack.
        """
        return self._pack(grads, dtype, norms_chunk, use_kernels, out,
                          tile_elems)

    def _pack(self, grads, dtype, norms_chunk, use_kernels, out,
              tile_elems=0):
        leaves = self.flat_leaves(grads)
        if dtype is None:
            dtype = jnp.result_type(*leaves) if leaves else jnp.float32
        if norms_chunk:
            assert self.size % norms_chunk == 0, (self.size, norms_chunk)
        if use_kernels:
            from repro.kernels import ops as kops
            return kops.pool_pack(leaves, self.offsets, self.sizes,
                                  self.size, norms_chunk, dtype, out=out,
                                  tile_elems=tile_elems)
        from repro.kernels import ref
        return ref.pool_pack(leaves, self.offsets, self.size, norms_chunk,
                             dtype, out=out)

    # -- ravel / unravel (thin compatibility wrappers) ---------------------

    def ravel(self, grads: Any, dtype: Any = None) -> jax.Array:
        """Pytree → 1-D pool (reverse-generation order, padded)."""
        pool, _ = self.pack(grads, dtype=dtype)
        return pool

    def unravel(self, pool: jax.Array, dtype: Any = None) -> Any:
        """1-D pool → pytree (inverse of ravel; drops padding). Static
        ``lax.slice`` per segment — the offsets are compile-time constants
        from the segment table, so XLA fuses the slices into the consumers
        instead of emitting dynamic-slice ops."""
        leaves = []
        for spec in self.specs:
            x = jax.lax.slice(pool, (spec.offset,),
                              (spec.offset + spec.size,))
            if dtype is not None:
                x = x.astype(dtype)
            elif x.dtype != spec.dtype:
                x = x.astype(spec.dtype)
            leaves.append(x)
        return self.unflatten(leaves)

    # -- bucketing for lazy allreduce -------------------------------------

    def bucket_boundaries(self, bucket_elems: int) -> List[Tuple[int, int]]:
        """θ-bucketing (paper's lazy-allreduce threshold).

        Buckets close at the first *tensor boundary* at or after every θ
        elements, mirroring the paper: allreduce fires once the waited
        tensors exceed θ. Returns [(start, end), ...] covering [0, size).
        ``bucket_elems == 0`` means one bucket for the entire pool
        (the paper's 'disable-overlap' single fused allreduce).
        """
        if bucket_elems <= 0 or bucket_elems >= self.size:
            return [(0, self.size)]
        bounds: List[Tuple[int, int]] = []
        start = 0
        acc = 0
        for spec in self.specs:
            acc += spec.size
            if acc - start >= bucket_elems:
                bounds.append((start, acc))
                start = acc
        if start < self.size:
            bounds.append((start, self.size))
        return bounds

    # -- bucket-aligned views (overlap engine) ------------------------------

    def leaf_range(self, start: int, end: int) -> Tuple[int, int]:
        """Segment-table row range [lo, hi) of the tensors fully inside
        ``[start, end)``. Requires tensor-aligned bounds: ``start`` must be
        a tensor offset (or the padding tail) and ``end`` a tensor end (or
        the pool end) — exactly what ``bucket_boundaries`` produces.
        Bisects the precomputed ``offsets`` table: O(log tensors) per
        bucket, so compiling a StepPlan stays linear in bucket count."""
        assert 0 <= start <= end <= self.size, (start, end, self.size)
        lo = bisect.bisect_left(self.offsets, start)
        if lo == len(self.offsets) or self.offsets[lo] != start:
            assert start >= self.unpadded_size, (
                f"bucket start {start} is not a tensor boundary")
            lo = len(self.specs)
        # leaves [lo, hi) are those starting before ``end``; the last one
        # must also END by ``end`` for the bucket to be tensor-aligned.
        hi = bisect.bisect_left(self.offsets, end, lo)
        if hi > lo:
            last = self.specs[hi - 1]
            assert last.offset + last.size <= end, (
                f"bucket end {end} is not a tensor boundary")
        return lo, hi

    def bucket_view(self, start: int, end: int) -> PoolView:
        """Bucket-aligned segment-table view of ``[start, end)`` — the
        per-bucket update range of the overlap engine. Offsets come back
        rebased to ``start`` so the view's sub-table drives the same
        unpack/update kernels as the whole-pool table."""
        lo, hi = self.leaf_range(start, end)
        specs = self.specs[lo:hi]
        covered = (specs[-1].offset + specs[-1].size) if specs else start
        return PoolView(
            start=start, end=end, leaf_lo=lo, leaf_hi=hi, specs=specs,
            offsets=tuple(s.offset - start for s in specs),
            sizes=tuple(s.size for s in specs),
            padding=end - covered)

    # -- per-tensor segments (LARS etc.) -----------------------------------

    def segment_ids(self) -> np.ndarray:
        """int32[size] mapping each pool element to its tensor index
        (padding maps to the last tensor id + 1)."""
        ids = np.zeros((self.size,), dtype=np.int32)
        for i, spec in enumerate(self.specs):
            ids[spec.offset:spec.offset + spec.size] = i
        if self.padding:
            ids[self.unpadded_size:] = len(self.specs)
        return ids

    @property
    def num_tensors(self) -> int:
        return len(self.specs)

    def num_chunks(self, chunk_elems: int) -> int:
        # The *padded* size must divide exactly: a pool merely padded to a
        # pad_to that chunk_elems divides is not enough (e.g. pad_to=1).
        assert self.size % chunk_elems == 0, (
            f"pool size {self.size} must be a multiple of chunk_elems "
            f"{chunk_elems}; construct with pad_to=chunk_elems")
        return self.size // chunk_elems

    def abstract_pool(self, dtype: Any = jnp.float32) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct((self.size,), jnp.dtype(dtype))
