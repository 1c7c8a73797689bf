"""Jit'd public wrappers for the Pallas kernels.

Each call decides, when it is made (never at import), how the kernel
runs: compiled (``interpret=False``) when the default backend is a TPU,
otherwise in Pallas interpret mode, which executes the kernel body on
XLA:CPU with the same semantics, validated against ref.py.

One interpret-only caveat: interpret mode lowers the kernel grid to a
``while_loop`` whose internal carry cannot carry shard_map's device-varying
(vma) tags, so *inside a manual shard_map region* the interpret path
dispatches to the pure-jnp ref instead (same math — the kernels' semantics
are exactly ref.py, enforced by tests/test_kernels.py). On TPU the
compiled kernels run everywhere; ``dispatch_counts`` shows which path
each call took.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import chunk_l1norm as _cl
from repro.kernels import csc_compact as _cc
from repro.kernels import fused_update as _fu
from repro.kernels import pool_pack as _pp
from repro.kernels import ref

# Python-level dispatch tally: the kernel/ref decision happens here, in
# python, at call/trace time — so counting it here is faithful. The
# kernel-bench CI gate reads this to prove the streaming kernels are the
# path actually taken (a reintroduced size fallback would silently pass
# an output-equivalence check, since ref output == kernel output).
dispatch_counts: Dict[str, int] = {}


def _count(name: str, path: str) -> None:
    key = f"{name}.{path}"
    dispatch_counts[key] = dispatch_counts.get(key, 0) + 1


def _interpret() -> bool:
    """Compiled kernels on a TPU backend; interpret mode anywhere else."""
    return jax.default_backend() != "tpu"


def _needs_ref_fallback(*arrays) -> bool:
    """Interpret mode inside a vma-tagged shard_map region (see module
    docstring). ``None`` operands (optional arguments) are skipped."""
    if not _interpret():
        return False
    return any(jax.typeof(a).vma for a in arrays if a is not None)


def ring_allreduce(x: jax.Array, axes: Sequence[str],
                   wire_dtype=None, collective_id: int = 0) -> jax.Array:
    """Ring allreduce of the 1-D ``x`` across the manual ``axes`` —
    the execution entry point of the ``pallas_ring`` ReduceAlgorithm.

    Multi-axis reductions run one full-payload ring per axis, innermost
    (fastest) level first; each ring is 2(N-1) neighbor exchanges with
    wire-dtype segments and f32 accumulation. Per-axis dispatch:

    * compiled TPU — the Pallas RDMA kernel (``kernels.ring_reduce``);
    * CPU/interpret — the ``lax.ppermute`` twin (``ref.ring_allreduce``),
      the kernel's correctness oracle;
    * interpret mode in shard_map regions with vma tags
      (``check_vma=True``) — the
      vma-safe twin (ring reduce-scatter + place-and-psum gather): the
      checker keeps the varying tag on every ppermute result, so the full
      ring cannot leave such a region as a replicated value.

    ``wire_dtype=None`` transports segments in ``x.dtype`` — the pool
    pipeline hands this function an already wire-cast (bf16) bucket, so
    no extra plumbing is needed for mixed-precision wire traffic.

    ``collective_id`` is this call's Mosaic collective id *base*:
    per-bucket rings inside one compiled step are data-independent and
    may run concurrently, so two live kernels must never share an id (or
    Mosaic's collective bookkeeping). The id must be a value every host
    derives identically for the same logical ring — GradientFlow passes
    the bucket index, a pure function of the (host-invariant) bucket
    layout; NEVER derive it from process-local state like a call counter,
    whose value depends on what else each host happened to trace. The
    per-axis rings of a multi-axis reduce fan out below the base.
    """
    for i, axis in enumerate(reversed(tuple(axes))):
        x = _ring_one(x, axis, wire_dtype,
                      collective_id * _RING_ID_AXES + i)
    return x


# Id headroom for the per-axis rings under one collective_id base (mesh
# depth is ≤ 3 levels everywhere in this repo; 8 leaves slack).
_RING_ID_AXES = 8


def _ring_one(x: jax.Array, axis: str, wire_dtype,
              collective_id: int = 0) -> jax.Array:
    if not _interpret():
        from repro.kernels import ring_reduce
        from repro.parallel.collectives import axis_size
        _count("ring_allreduce", "kernel")
        return ring_reduce.ring_allreduce(
            x, axis, axis_size((axis,)), wire_dtype=wire_dtype,
            collective_id=collective_id)
    if _needs_ref_fallback(x):
        _count("ring_allreduce", "ref_invariant")
        return ref.ring_allreduce_invariant(x, axis, wire_dtype=wire_dtype)
    _count("ring_allreduce", "ref")
    return ref.ring_allreduce(x, axis, wire_dtype=wire_dtype)


def chunk_l1norm(pool: jax.Array, chunk_elems: int) -> jax.Array:
    if _needs_ref_fallback(pool):
        _count("chunk_l1norm", "ref")
        return ref.chunk_l1norm(pool, chunk_elems)
    _count("chunk_l1norm", "kernel")
    return _cl.chunk_l1norm(pool, chunk_elems, interpret=_interpret())


def csc_compact(pool: jax.Array, idx: jax.Array,
                chunk_elems: int) -> jax.Array:
    if _needs_ref_fallback(pool, idx):
        _count("csc_compact", "ref")
        return ref.csc_compact(pool, idx, chunk_elems)
    _count("csc_compact", "kernel")
    return _cc.csc_compact(pool, idx, chunk_elems, interpret=_interpret())


def pool_pack(leaves: Sequence[jax.Array], offsets: Tuple[int, ...],
              sizes: Tuple[int, ...], pool_size: int, chunk_elems: int,
              wire_dtype, out: Optional[jax.Array] = None,
              tile_elems: int = 0
              ) -> Tuple[jax.Array, Optional[jax.Array],
                         Optional[jax.Array]]:
    """Fused ravel + wire cast + chunk-L1 census over the gradient pool.
    Returns (wire pool, norms or None, staging buffer or None) — see
    ref.pool_pack for the staging/donation contract.

    ``chunk_elems`` decides the path:

    * no census (``chunk_elems == 0``) — one XLA pass on every backend
      (``_xla_pack``, counted ``pool_pack.xla``): each leaf converted to
      the wire dtype and concatenated at its offset with the zero tail.
      XLA fuses that into one bandwidth-bound write of the pool; the
      Mosaic kernel would add nothing but its O(tiles) unrolled schedule;
    * census (quantized wires) — the streaming tiled kernel, which folds
      the chunk-L1 norms into the same pass (peak VMEM O(tile) at every
      pool size).

    Donated staging: a **wire-dtype** ``out`` buffer is consumed — the
    kernel aliases it to its pool output (``input_output_aliases``), the
    XLA pass takes it as a donated argument — and the packed pool is
    returned as the staging for the next step, so steady-state packs
    allocate nothing pool-sized. A *source*-dtype ``out`` that differs
    from the wire dtype asks for the ref twin's stage-then-cast contract
    and takes the ref twin (the trainer never passes one with kernels
    on: its staging is always the wire dtype), as do empty pools and, for
    the census kernel, the shard_map/interpret vma limitation described
    in the module docstring."""
    wire = jnp.dtype(wire_dtype)
    src = jnp.result_type(*leaves) if leaves else wire
    wire_staging = out is not None and out.dtype == wire
    assert out is None or wire_staging or out.dtype == src, (
        "staging buffer must be wire- or source-dtype",
        out.dtype, wire, src)
    if leaves and (out is None or wire_staging) and chunk_elems == 0:
        _count("pool_pack", "xla")
        pool = _xla_pack(tuple(leaves), tuple(offsets), pool_size,
                         wire.name, staging=out)
        return pool, None, (pool if wire_staging else None)
    if not leaves or _needs_ref_fallback(*leaves) or \
            (out is not None and not wire_staging):
        _count("pool_pack", "ref")
        # The ref twin stages in the source dtype; a wire-dtype staging
        # buffer (the kernel aliasing contract) seeds it only when the
        # two dtypes agree — otherwise drop the donation for this call
        # and hand the pool back as the next step's wire staging so the
        # threading stays typed.
        seed = out if (out is None or out.dtype == src) else None
        pool, norms, staging = ref.pool_pack(
            leaves, offsets, pool_size, chunk_elems, wire_dtype, out=seed)
        return pool, norms, (pool if wire_staging else staging)
    _count("pool_pack", "kernel")
    pool, norms = _pp.pool_pack(
        tuple(leaves), tuple(offsets), tuple(sizes), pool_size,
        chunk_elems, wire.name, tile_elems=tile_elems,
        staging=out, interpret=_interpret())
    return pool, norms, (pool if wire_staging else None)


@functools.partial(jax.jit, static_argnames=("offsets", "pool_size",
                                              "wire_dtype"),
                   donate_argnames=("staging",), keep_unused=True)
def _xla_pack(leaves: Tuple[jax.Array, ...], offsets: Tuple[int, ...],
              pool_size: int, wire_dtype: str,
              staging: Optional[jax.Array] = None) -> jax.Array:
    """The no-census pack as one XLA pass: per-leaf convert, concatenate,
    zero tail up to ``pool_size``. No pool-sized buffer exists in the
    source dtype, only the wire-dtype pool. ``staging`` is read by
    nothing: donating it lets XLA write the pool into its buffer (kept,
    not pruned, for that; the TPU compiler turns the concatenate into
    in-place writes of each converted leaf there). Each leaf goes
    through the staging dtype first, as in ``ref.pool_pack``, so
    mixed-dtype trees round the same way; for float leaves that widening
    is exact."""
    del staging
    wire = jnp.dtype(wire_dtype)
    src = jnp.result_type(*leaves)
    parts, end = [], 0
    for x, off in zip(leaves, offsets):
        assert off == end, ("leaves must tile the pool in order", off, end)
        parts.append(x.astype(src).astype(wire))
        end += x.shape[0]
    assert end <= pool_size, (end, pool_size)
    if end < pool_size:
        parts.append(jnp.zeros((pool_size - end,), wire))
    return jnp.concatenate(parts)


def update_unpack(master, grads, momentum_buf, mask,
                  offsets: Tuple[int, ...], sizes: Tuple[int, ...], *,
                  lr, momentum, weight_decay,
                  scale: Optional[jax.Array] = None,
                  ratios: Optional[jax.Array] = None,
                  tile_elems: int = 0
                  ) -> Tuple[List[jax.Array], jax.Array]:
    """Fused momentum-SGD update + pool unravel (leaves out, pool never
    re-materialized on the update side), streaming at every pool size.
    ``ratios`` passes the per-tensor LARS vector for in-kernel expansion
    (no pool-sized scale buffer); ``scale`` remains the expanded
    per-element form for the oracle/fallback paths."""
    if _needs_ref_fallback(master, grads, momentum_buf, mask, scale,
                           ratios):
        _count("update_unpack", "ref")
        return ref.pool_unpack_update(
            master, grads, momentum_buf, mask, offsets, sizes, lr=lr,
            momentum=momentum, weight_decay=weight_decay, scale=scale,
            ratios=ratios)
    _count("update_unpack", "kernel")
    return _fu.update_unpack(
        master, grads, momentum_buf, mask, tuple(offsets), tuple(sizes),
        lr=lr, momentum=momentum, weight_decay=weight_decay, scale=scale,
        ratios=ratios, tile_elems=tile_elems, interpret=_interpret())


# Back-compat name for the update-side entry point.
pool_unpack_update = update_unpack


def fused_update(master, grads, momentum_buf, mask, *, lr, momentum,
                 weight_decay, scale: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    if _needs_ref_fallback(master, grads, momentum_buf, mask):
        _count("fused_update", "ref")
        return ref.fused_update(master, grads, momentum_buf, mask, lr=lr,
                                momentum=momentum,
                                weight_decay=weight_decay, scale=scale)
    _count("fused_update", "kernel")
    return _fu.fused_update(master, grads, momentum_buf, mask, lr=lr,
                            momentum=momentum, weight_decay=weight_decay,
                            scale=scale, interpret=_interpret())
