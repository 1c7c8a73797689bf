"""Collective helpers used inside the manual (data-parallel) shard_map region.

All functions assume they are called inside a shard_map whose *manual* axes
include every name in ``axes``. The `model` axis is GSPMD-auto and never
appears here.

Axis-name convention (matches ``GradientFlowConfig.reduce_axes`` and
``Topology``): axes are ordered outermost/slowest first — e.g.
``('pod', 'data')`` — so ``axes[-1]`` is always the fastest (intra-node)
level. The multi-level reductions scatter over the fast axes first, push
the shrunken shard across the slow links, then gather back out.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp


# -- shard_map / mesh helpers (jax >= 0.9) ----------------------------------


def compat_shard_map(f, *, mesh=None, in_specs, out_specs,
                     axis_names: Optional[Set[str]] = None,
                     check_vma: bool = True):
    """``jax.shard_map`` with ``axis_names`` the set of manual axes (None =
    all mesh axes manual) and ``mesh=None`` meaning "resolve from
    context" (e.g. a nested shard_map inside a manual region)."""
    kwargs: dict = {}
    if mesh is not None:
        kwargs["mesh"] = mesh
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma, **kwargs)


def compat_set_mesh(mesh) -> contextlib.AbstractContextManager:
    """``with compat_set_mesh(mesh):`` — ``jax.sharding.set_mesh``."""
    return jax.sharding.set_mesh(mesh)


def compat_pvary(x: jax.Array, axes: Sequence[str]) -> jax.Array:
    """Tag ``x`` as varying over the manual ``axes``. Only the axes not
    already in ``x``'s vma set are cast: ``pcast`` rejects a
    varying -> varying cast of an axis the value already varies over."""
    missing = tuple(a for a in axes if a not in jax.typeof(x).vma)
    if missing:
        x = jax.lax.pcast(x, missing, to="varying")
    return x


def compat_make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def compat_abstract_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.sharding.AbstractMesh(axis_sizes, axis_names)``."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))


def _one_axis_size(axis: str) -> int:
    """Static size of one manual axis."""
    return int(jax.lax.axis_size(axis))


def axis_size(axes: Sequence[str]) -> int:
    n = 1
    for a in axes:
        n *= _one_axis_size(a)
    return n


def psum(x: jax.Array, axes: Sequence[str]) -> jax.Array:
    return jax.lax.psum(x, tuple(axes))


def pmean(x: jax.Array, axes: Sequence[str]) -> jax.Array:
    return jax.lax.pmean(x, tuple(axes))


def _pad_to_multiple(x: jax.Array, m: int) -> Tuple[jax.Array, int]:
    pad = (-x.shape[0]) % m
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), dtype=x.dtype)])
    return x, pad


def ring_perm(n: int) -> list:
    """The unidirectional ring permutation for ``lax.ppermute``: rank d
    sends to (d + 1) % n. One such exchange is one ring *step*; a full
    ring allreduce is 2(n-1) of them (see ``repro.kernels.ref`` /
    ``repro.kernels.ring_reduce``)."""
    return [(d, (d + 1) % n) for d in range(n)]


def _all_gather_invariant(shard: jax.Array, axis: str, n: int,
                          idx: Optional[jax.Array] = None) -> jax.Array:
    """All-gather via place-and-psum: semantically an all-gather with the
    same wire bytes, but the vma system knows a psum result is device-
    invariant (a raw all_gather keeps the varying tag and fails check_vma
    at the shard_map boundary).

    ``idx`` is the destination row of this device's shard (default: its
    own axis index). The ring reduce-scatter leaves rank d owning segment
    (d+1) % n, so its vma-safe all-gather phase passes that rotation here.
    """
    n_sh = shard.shape[0]
    if idx is None:
        idx = jax.lax.axis_index(axis)
    buf = jnp.zeros((n, n_sh), shard.dtype)
    buf = jax.lax.dynamic_update_index_in_dim(buf, shard, idx, 0)
    return jax.lax.psum(buf, axis).reshape(-1)


def hierarchical_psum(x: jax.Array, intra_axis: str,
                      inter_axes: Sequence[str]) -> jax.Array:
    """Two-level allreduce for multi-pod meshes.

    reduce-scatter over the (fast, intra-pod) ``intra_axis``, psum the
    scattered shard over the (slow, inter-pod) ``inter_axes``, then
    all-gather back over ``intra_axis``. Inter-pod traffic per device drops
    from |x| to |x| / intra_size — the TPU analogue of the paper's
    hierarchical allreduce (NCCL-H, Fig. 7b), which is *more* attractive
    here because cross-pod links are the scarce resource.
    """
    if not inter_axes:
        return jax.lax.psum(x, intra_axis)
    n = _one_axis_size(intra_axis)
    xp, pad = _pad_to_multiple(x, n)
    shard = jax.lax.psum_scatter(xp, intra_axis, scatter_dimension=0,
                                 tiled=True)
    shard = jax.lax.psum(shard, tuple(inter_axes))
    full = _all_gather_invariant(shard, intra_axis, n)
    if pad:
        full = full[:x.shape[0]]
    return full


def tree_psum(x: jax.Array, axes: Sequence[str]) -> jax.Array:
    """k-level tree allreduce.

    Recursively reduce-scatters from the innermost (fastest) axis outward,
    runs the top-level psum over the outermost (slowest) axis on a shard
    shrunk by the product of all inner level sizes, then all-gathers back
    down. With two axes this coincides with ``hierarchical_psum``; with
    three (e.g. ``('pod', 'host', 'data')``) the slowest link carries
    |x| / (host*data) bytes per device instead of |x|.
    """
    axes = tuple(axes)
    if len(axes) <= 1:
        return jax.lax.psum(x, axes)
    inner = axes[-1]
    n = _one_axis_size(inner)
    xp, pad = _pad_to_multiple(x, n)
    shard = jax.lax.psum_scatter(xp, inner, scatter_dimension=0,
                                 tiled=True)
    shard = tree_psum(shard, axes[:-1])
    full = _all_gather_invariant(shard, inner, n)
    if pad:
        full = full[:x.shape[0]]
    return full


def reduce_pool(x: jax.Array, axes: Sequence[str],
                algo: "object | None" = None) -> jax.Array:
    """Sum ``x`` across the data-parallel axes.

    ``algo`` is a ``repro.parallel.topology.ReduceAlgorithm`` (or anything
    with a ``reduce(x, axes)`` method); ``None`` means the flat single-ring
    psum. The old ``hierarchical: bool`` flag grew into this object — see
    docs/collectives.md. Note that algorithms need not bottom out in a
    psum at all: ``pallas_ring`` executes its own 2(N-1)-step neighbor
    exchange (``repro.kernels.ring_reduce`` on TPU, the ``lax.ppermute``
    twin in ``repro.kernels.ref`` elsewhere). Scope ``gf.reduce``, that
    of GradientFlow's data-parallel exchange.
    """
    axes = tuple(axes)
    with jax.named_scope("gf.reduce"):
        if algo is None:
            return jax.lax.psum(x, axes)
        return algo.reduce(x, axes)
