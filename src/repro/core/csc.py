"""Coarse-grained sparse communication (paper §3.2, Figs 17–18, Algorithm 1).

The gradient pool is partitioned into fixed-size chunks (paper: 32K
gradients). Each iteration only the top-(1−ρ) fraction of chunks by
*globally agreed* L1 norm is exchanged — packed into a dense buffer so the
allreduce runs at full ring bandwidth (the paper's argument against
fine-grained k-v sparse aggregation, which is even stronger on TPU).

Key mechanics, all paper-faithful:

* **Cross-iteration selection** (Fig 18): per-chunk L1 norms of the
  *post-reduce* pool are allreduced at the end of iteration t; the top-k
  chunk set derived from them is used in iteration t+1. Selection state
  therefore lives in ``CSCState.chunk_norms`` and every GPU provably selects
  the same chunks (inputs to top_k are identical after the psum).
* **Momentum SGD correction** (Algorithm 1): unselected gradients are
  accumulated into a historical buffer ``hg`` scaled by the SGD momentum and
  re-injected before the next reduction — no gradient information is lost.
  The matching update-side masking lives in ``repro.optim.sgd``.
* **Warm-up dense training**: handled by ``repro.core.schedule`` — k is
  static per compiled stage.

Under mean-reduction the paper's "divide important-chunk L1 by N" step is
the identity: the reduced chunk already holds sum/N, so its L1 equals the
paper's normalized value. Unimportant chunks contribute their local L1,
summed by the norm-psum, exactly as in Fig 18.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import GradientFlowConfig
from repro.core import wire as wire_mod
from repro.core.lazy_allreduce import bucketed_reduce
from repro.parallel.collectives import reduce_pool


class CSCState(NamedTuple):
    """Carried across iterations inside the train state.

    hg          : f32[pool]   — historical (unsent) gradients, Algorithm 1.
    chunk_norms : f32[chunks] — allreduced L1 norms from the previous
                  iteration; the top-k of these defines this iteration's
                  important chunks (identical on every device).
    """

    hg: jax.Array
    chunk_norms: jax.Array


def init_state(pool_size: int, chunk_elems: int,
               dtype=jnp.float32) -> CSCState:
    num_chunks = pool_size // chunk_elems
    assert num_chunks * chunk_elems == pool_size, (
        "pool must be padded to a chunk multiple")
    return CSCState(
        hg=jnp.zeros((pool_size,), dtype=dtype),
        # descending init => warm-up (dense) selects every chunk; the first
        # sparse iteration uses norms produced by real gradients.
        chunk_norms=jnp.arange(num_chunks, 0, -1, dtype=dtype),
    )


def select_chunks(chunk_norms: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Top-k chunk ids (sorted ascending for deterministic layout) + mask.
    Selection, compaction and the census run in scope ``gf.csc_select``."""
    num_chunks = chunk_norms.shape[0]
    with jax.named_scope("gf.csc_select"):
        _, idx = jax.lax.top_k(chunk_norms, k)
        idx = jnp.sort(idx)
        mask = jnp.zeros((num_chunks,), dtype=jnp.bool_).at[idx].set(True)
    return idx, mask


def compact_chunks(pool: jax.Array, idx: jax.Array, chunk_elems: int, *,
                   use_kernels: bool = False) -> jax.Array:
    """Gather selected chunks into the dense wire buffer (k*chunk,);
    through the ``csc_compact`` kernel with ``use_kernels``."""
    with jax.named_scope("gf.csc_select"):
        if use_kernels:
            from repro.kernels import ops as kops
            return kops.csc_compact(pool, idx, chunk_elems)
        chunks = pool.reshape((-1, chunk_elems))
        return jnp.take(chunks, idx, axis=0).reshape((-1,))


def scatter_chunks(pool: jax.Array, idx: jax.Array, values: jax.Array,
                   chunk_elems: int) -> jax.Array:
    """Write reduced chunks back into the pool at their chunk positions."""
    chunks = pool.reshape((-1, chunk_elems))
    chunks = chunks.at[idx].set(values.reshape((-1, chunk_elems)))
    return chunks.reshape((-1,))


def chunk_l1_norms(pool: jax.Array, chunk_elems: int, *,
                   use_kernels: bool = False) -> jax.Array:
    """Per-chunk L1 norm; f32 accumulate regardless of pool dtype.
    Delegates to the kernel oracle so the census has one definition —
    the same math the fused pack emits in its single pass — or, with
    ``use_kernels``, to the ``chunk_l1norm`` kernel."""
    from repro.kernels import ops as kops, ref
    with jax.named_scope("gf.csc_select"):
        if use_kernels:
            return kops.chunk_l1norm(pool, chunk_elems)
        return ref.chunk_l1norm(pool, chunk_elems)


@dataclasses.dataclass(frozen=True)
class CSCReduceResult:
    grads: jax.Array        # update-ready pool: mean for important, ZERO else
                            # (device-invariant: safe input for the optimizer)
    elem_mask: jax.Array    # bool[pool]; True where the update may apply
    state: CSCState         # hg is per-data-shard (device-varying) by design
    residual: Any = None    # error-feedback residual (quantized wire formats
                            # only; per-shard, updated at selected chunks)


def csc_reduce(
    pool_grads: jax.Array,
    state: CSCState,
    cfg: GradientFlowConfig,
    *,
    num_selected: int,
    bucket_boundaries: Sequence[Tuple[int, int]],
    num_data_shards: int,
    algo=None,
    residual=None,
) -> CSCReduceResult:
    """One CSC reduction (Fig 17 + Algorithm 1 preprocess step).

    Args:
      pool_grads: local per-data-shard raveled gradients (any float dtype).
      state: CSC state from the previous iteration.
      cfg: GradientFlow config (chunk size, momentum, wire dtype, axes).
      num_selected: static k for this compiled stage.
      bucket_boundaries: θ buckets *over the packed wire buffer* — CSC
        "relies on lazy allreduce" (paper §3.2): the compacted selection is
        itself transmitted in fused θ buckets.
      num_data_shards: product of data-axis sizes (for the mean).
      algo: ReduceAlgorithm (or one per bucket) for the wire-buffer
        collectives; None = flat ring psum. ``pallas_ring`` reduces the
        *compacted* buffer — k*chunk_elems elements, re-segmented per
        wire bucket — so sparsity shrinks the ring's segments, never its
        step count. The norm census stays flat — it is one tiny
        f32[chunks] message, below any crossover point.
      residual: error-feedback residual pool (f32[pool], per-shard) for
        quantized wire formats. Re-injected into the send values of the
        SELECTED chunks and replaced there with this step's quantization
        error; unselected chunks keep their residual (their payload
        flows through hg, Algorithm 1). None => no feedback (ablation or
        native transport).

    Quantized wire formats (``cfg.wire_format`` in {'int8','fp8_e4m3'}):
    only the surviving chunks of the compacted buffer are quantized —
    per-chunk scales come from the PREVIOUS iteration's allreduced census
    (``state.chunk_norms``, already rank-invariant: zero extra
    collectives), gathered at the selected chunk ids. Scale drift between
    iterations is absorbed by the saturating clip + error feedback and
    watched by the guard's per-chunk overflow limit (repro.core.guard).
    """
    chunk = cfg.chunk_elems
    momentum = cfg.momentum
    spec = wire_mod.resolve(cfg.wire_format)
    g = pool_grads.astype(jnp.float32)

    # Algorithm 1 line 7: re-inject historical gradients.
    g = g + state.hg

    # Selection from the PREVIOUS iteration's allreduced norms (Fig 18).
    idx, chunk_mask = select_chunks(state.chunk_norms, num_selected)
    elem_mask = jnp.repeat(chunk_mask, chunk)

    # Error feedback: selected chunks also carry the residual of their
    # previous quantized sends.
    g_send = g if (spec is None or residual is None) else g + residual

    # Pack important chunks; fused bucketed allreduce over the wire buffer.
    wire = compact_chunks(g_send, idx, chunk, use_kernels=cfg.use_kernels)
    residual_new = residual
    if spec is None:
        reduced = bucketed_reduce(
            wire, bucket_boundaries, cfg.reduce_axes, cfg.wire_dtype,
            algo=algo)
    else:
        scales = wire_mod.scales_from_census(
            jnp.take(state.chunk_norms, idx), chunk_elems=chunk,
            num_shards=num_data_shards, spec=spec)
        # Pre-quantization send census (see the norms_new block below):
        # captured before the saturating clip/cast can eat NaN or cap
        # magnitudes.
        send_l1 = chunk_l1_norms(wire.astype(jnp.float32), chunk)
        qwire, err = wire_mod.quantize_pool(
            wire, scales, chunk_elems=chunk, spec=spec,
            num_shards=num_data_shards)
        # Scaled-domain transport: the ring dequant-accumulate-requants
        # in flight; wire_dtype=None means "already wire-packed".
        summed = bucketed_reduce(qwire, bucket_boundaries, cfg.reduce_axes,
                                 None, algo=algo)
        reduced = wire_mod.dequantize_pool(summed, scales, chunk)
        if residual is not None:
            residual_new = scatter_chunks(residual, idx, err, chunk)
    reduced = reduced / num_data_shards  # mean over data shards

    # Post-reduce view: important chunks hold the mean, others local g
    # (device-varying — it feeds the per-shard hg and the norm census).
    g_out = scatter_chunks(g, idx, reduced, chunk)

    # Update-ready view: important chunks hold the mean, others ZERO —
    # device-invariant by construction, so the optimizer's outputs (params,
    # momentum) are provably replicated across data shards. (A fresh zeros
    # constant, NOT zeros_like(g): that would inherit g's varying tag.)
    g_update = scatter_chunks(jnp.zeros(g.shape, g.dtype), idx, reduced,
                              chunk)

    # Algorithm 1 lines 8–11: historical-gradient bookkeeping (per-shard).
    hg_new = jnp.where(elem_mask, 0.0, momentum * g_out).astype(state.hg.dtype)

    # Fig 18: next-iteration importance. Post-reduce pool: important chunks
    # hold the mean (≡ paper's sum/N), others hold local g — L1 per chunk,
    # then a (cheap) psum so every device agrees.
    l1 = chunk_l1_norms(g_out, chunk, use_kernels=cfg.use_kernels)
    if spec is not None:
        # Quantized wires: selected chunks contribute their PRE-QUANT
        # send-buffer L1 instead of the post-dequant mean's. Three birds,
        # one (unchanged) psum: (a) the census is the health channel —
        # int8's round/clip eats NaN and caps saturation at ~WIRE_MARGIN x
        # basis, so only the pre-quant values still carry poison and the
        # 512x per-chunk overflow jump (guard.per_chunk_limit); (b) the
        # resulting norms are next iteration's SCALE basis, and a sum of
        # per-rank L1s bounds per-rank magnitudes — exactly what
        # wire.rank_clip budgets against; (c) selection importance is
        # preserved (both are the same census up to cross-rank
        # cancellation).
        l1 = l1.at[idx].set(send_l1)
    norms_new = reduce_pool(l1, cfg.reduce_axes)

    return CSCReduceResult(
        grads=g_update,
        elem_mask=elem_mask,
        state=CSCState(hg=hg_new, chunk_norms=norms_new),
        residual=residual_new,
    )


def wire_bucket_boundaries(num_selected: int, chunk_elems: int,
                           bucket_elems: int) -> Tuple[Tuple[int, int], ...]:
    """θ buckets over the packed (k * chunk_elems) wire buffer,
    aligned to chunk boundaries."""
    total = num_selected * chunk_elems
    if bucket_elems <= 0 or bucket_elems >= total:
        return ((0, total),)
    chunks_per_bucket = max(bucket_elems // chunk_elems, 1)
    step = chunks_per_bucket * chunk_elems
    bounds = []
    start = 0
    while start < total:
        end = min(start + step, total)
        bounds.append((start, end))
        start = end
    return tuple(bounds)
