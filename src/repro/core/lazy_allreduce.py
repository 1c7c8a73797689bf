"""Lazy allreduce (paper §3.1).

Instead of one allreduce per gradient tensor (the §2.3 baseline), the
contiguous gradient pool is reduced in θ-element buckets that close at
tensor boundaries — one fused collective per bucket. Each bucket's psum
depends only on the gradients inside it, so XLA's latency-hiding scheduler
can overlap bucket i's collective with the backward compute that produces
bucket j > i (the pool is in reverse-generation order: bucket 0 holds the
top layers' gradients, available earliest).

``bucket_elems == 0`` reproduces the paper's *disable-overlap* setting:
a single fused allreduce over the whole pool after backward.

The reduction itself is delegated to a ``ReduceAlgorithm`` from
``repro.parallel.topology`` (flat ring / two-level / k-level tree /
pallas_ring) — either one algorithm for every bucket or one per bucket,
the layout the topology auto-selector produces. Buckets close at tensor
boundaries, so their sizes are ragged; the ring algorithm re-segments
every bucket independently into N ceil(bucket/N) segments (short or
empty final segment included — ``ring_segment_bounds``), which is why no
bucket layout needs to know the device count.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.parallel.collectives import reduce_pool

# One algorithm for all buckets, or one per bucket (len == len(boundaries)).
AlgoSpec = Union[None, object, Sequence[object]]


def _algo_for(algo: AlgoSpec, i: int):
    if algo is None or hasattr(algo, "reduce"):
        return algo
    return algo[i]


def reduce_bucket(
    pool: jax.Array,
    start: int,
    end: int,
    axes: Sequence[str],
    wire_dtype,
    *,
    algo=None,
    accum_dtype=jnp.float32,
) -> jax.Array:
    """Issue ONE bucket's collective: slice [start, end) off the pool,
    cast to the wire dtype (``None`` = the pool is already wire-packed),
    reduce across the data axes with ``algo``, return the summed segment
    in ``accum_dtype``. This is the per-bucket primitive both the
    monolithic ``bucketed_reduce`` and the overlap engine's ``StepPlan``
    execution bottom out in — one definition, so the pipelined and
    monolithic paths cannot drift. Scope ``gf.reduce``, wire casts
    included."""
    with jax.named_scope("gf.reduce"):
        seg = jax.lax.slice_in_dim(pool, start, end)
        if wire_dtype is not None:
            seg = seg.astype(jnp.dtype(wire_dtype))
        if (jnp.issubdtype(seg.dtype, jnp.floating)
                and seg.dtype.itemsize == 1
                and getattr(algo, "name", "flat") != "pallas_ring"):
            # fp8-e4m3 wire on a psum-based algorithm: XLA would
            # accumulate in fp8, rounding at every add. Upcast to the
            # accumulator first — the exact sum of the per-rank fp8
            # words, i.e. the dequantize-then-sum reference the ring's
            # per-hop requant is tolerance-gated against. int8 words sum
            # exactly in any dtype and ride every algorithm as-is (see
            # repro.core.wire).
            seg = seg.astype(accum_dtype)
        seg = reduce_pool(seg, axes, algo=algo)
        return seg.astype(accum_dtype)


def bucketed_reduce_parts(
    pool: jax.Array,
    boundaries: Sequence[Tuple[int, int]],
    axes: Sequence[str],
    wire_dtype,
    *,
    algo: AlgoSpec = None,
    accum_dtype=jnp.float32,
) -> List[jax.Array]:
    """Per-bucket variant of ``bucketed_reduce``: one summed segment per
    boundary instead of one concatenated pool — what the overlap engine
    consumes (bucket i's segment feeds bucket i's update without waiting
    on the rest of the pool)."""
    return [reduce_bucket(pool, start, end, axes, wire_dtype,
                          algo=_algo_for(algo, i), accum_dtype=accum_dtype)
            for i, (start, end) in enumerate(boundaries)]


def bucketed_reduce(
    pool: jax.Array,
    boundaries: Sequence[Tuple[int, int]],
    axes: Sequence[str],
    wire_dtype,
    *,
    algo: AlgoSpec = None,
    accum_dtype=jnp.float32,
) -> jax.Array:
    """Reduce the 1-D pool across data axes in fused buckets.

    The wire dtype (paper: FP16; here default bf16) is applied per bucket —
    gradients are cast down for transport and back up to ``accum_dtype``
    after the reduce, mirroring mixed-precision communication (§2.5).
    ``wire_dtype=None`` means the pool is *already* in wire form (the
    single-pass pack pipeline casts at pack time) and buckets go on the
    wire as-is, with no per-bucket cast.
    ``algo`` selects the collective algorithm (None = flat ring psum).
    Returns the *summed* pool in ``accum_dtype`` (caller normalizes).
    """
    parts = bucketed_reduce_parts(pool, boundaries, axes, wire_dtype,
                                  algo=algo, accum_dtype=accum_dtype)
    if len(parts) == 1:
        return parts[0]
    return jnp.concatenate(parts)


def per_tensor_reduce(
    pool: jax.Array,
    tensor_boundaries: Sequence[Tuple[int, int]],
    axes: Sequence[str],
    wire_dtype,
    *,
    accum_dtype=jnp.float32,
) -> jax.Array:
    """§2.3 baseline: one allreduce per gradient tensor (no fusion).

    Kept as the paper-faithful *dense* baseline so benchmarks can count the
    collective-op blowup (26 ops for AlexNet, 153 for ResNet-50) that lazy
    allreduce removes.
    """
    return bucketed_reduce(pool, tensor_boundaries, axes, wire_dtype,
                           accum_dtype=accum_dtype)
