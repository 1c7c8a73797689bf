"""GradientFlow — the paper's communication backend, as a composable JAX module.

Top-level API used by the train step (inside the manual-DP shard_map):

    pool = GradientPool(params, pad_to=cfg.chunk_elems)
    gf = GradientFlow(cfg, pool, num_data_shards)
    state = gf.init_state()
    ...
    reduced, mask, state = gf.reduce(pool_grads, state, stage=stage)

Modes (GradientFlowConfig.mode):
  'dense' — per-tensor psum (§2.3 baseline; what MPI/NCCL-per-tensor did)
  'lazy'  — θ-bucketed fused psum over the contiguous pool (§3.1)
  'csc'   — lazy + coarse-grained sparse communication (§3.2)
All modes cast gradients to the wire dtype for transport (§2.5
mixed-precision communication) and return an f32 mean-reduced pool.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import GradientFlowConfig
from repro.core import csc as csc_mod
from repro.core import schedule as schedule_mod
from repro.core import wire as wire_mod
from repro.core.lazy_allreduce import bucketed_reduce
from repro.core.pool import GradientPool
from repro.parallel import topology as topo_mod


class GFState(NamedTuple):
    """GradientFlow's cross-iteration state (empty tensors when unused).

    ``residual`` is the error-feedback residual of the quantized wire
    formats (repro.core.wire): per-data-shard, pool-shaped f32, stored
    UNSCALED — the loss-scale interplay divides the quantization error by
    the (power-of-two) scale on write and multiplies on read, so scaler
    backoffs never corrupt carried feedback. It joins params/momentum/hg
    in the guard's atomic skip set: a rejected step restores it
    bit-identically."""

    hg: jax.Array           # f32[pool] historical gradients (CSC)
    chunk_norms: jax.Array  # f32[chunks] previous-iteration norms (CSC)
    residual: jax.Array = ()  # f32[pool] error-feedback residual (quantized)


class GradientFlow:
    def __init__(self, cfg: GradientFlowConfig, pool: GradientPool,
                 num_data_shards: int):
        self.cfg = cfg
        self.pool = pool
        self.num_data_shards = int(num_data_shards)
        # Validates wire_format at build time (unknown/unavailable raises).
        self.wire_spec = wire_mod.resolve(cfg.wire_format)
        if cfg.csc_enabled or self.wire_spec is not None:
            assert pool.size % cfg.chunk_elems == 0, (
                "GradientPool must be constructed with pad_to=chunk_elems "
                "(CSC chunking and per-chunk quantization scales both key "
                "off whole chunks)")
            self.num_chunks = pool.size // cfg.chunk_elems
        else:
            self.num_chunks = 0
        self.stages = schedule_mod.build_stages(cfg, max(self.num_chunks, 1))
        self._stage_firsts = schedule_mod.stage_first_steps(self.stages)
        self._resolve_layout()

    def _resolve_layout(self) -> None:
        """Resolve the topology-dependent layout: bucket boundaries (θ
        re-tuned when auto_bucket), per-bucket algorithms, and the plan
        cache. Called at build time and again by ``replan`` — everything
        that depends on the mesh shape must be derived here, nowhere
        else."""
        cfg, pool = self.cfg, self.pool
        # Static bucket layouts. θ comes from the config, or — when
        # auto_bucket is on and a topology is known — from the cost-model
        # tuner (docs/collectives.md).
        self._dense_bounds = tuple(
            (s.offset, s.offset + s.size) for s in pool.specs)
        if self._dense_bounds and pool.size > self._dense_bounds[-1][1]:
            # Chunk-padded pools (CSC / quantized wires) have a zero tail
            # past the last tensor; give it its own bucket — the same
            # dedicated padding task plan() appends — so bucketed_reduce
            # keeps producing pool-shaped output on the monolithic path.
            self._dense_bounds += ((self._dense_bounds[-1][1], pool.size),)
        self.bucket_elems = cfg.bucket_elems
        if cfg.auto_bucket and cfg.topology is not None:
            # Staged execution prices θ against the overlap engine's full
            # pipeline (updates overlap in-flight collectives); the
            # monolithic twin keeps the comm-only objective.
            from repro.parallel.cost_model import HBM_BW
            update_bw = HBM_BW if cfg.overlap == "staged" else None
            self.bucket_elems, bounds = topo_mod.auto_bucket_boundaries(
                pool, cfg.wire_dtype, cfg.topology,
                collective_algo=cfg.collective_algo, update_bw=update_bw)
            self._lazy_bounds = tuple(bounds)
        else:
            self._lazy_bounds = tuple(
                pool.bucket_boundaries(self.bucket_elems))
        # Per-bucket collective algorithms, resolved once per layout.
        self._dense_algos = self._algos_for(self._dense_bounds)
        self._lazy_algos = self._algos_for(self._lazy_bounds)
        # Compiled StepPlans are layout-derived: drop them with the layout.
        self._plan_cache: dict = {}

    def plan_cache_key(self) -> Tuple:
        """The mesh-shape key the plan cache (and every compiled StepPlan)
        is stamped with. Any elastic event that changes the topology, the
        data degree, or the tuned θ changes this key — the soak harness
        asserts exactly that after each remesh."""
        topo = self.cfg.topology
        topo_key = tuple((lv.axis, lv.size) for lv in topo.levels) \
            if topo is not None else None
        return (self.cfg.mode, self.cfg.collective_algo,
                str(self.cfg.wire_dtype), self.cfg.wire_format,
                self.num_data_shards, self.bucket_elems, topo_key)

    def replan(self, topology: Optional[topo_mod.Topology] = None, *,
               num_data_shards: Optional[int] = None,
               reduce_axes: Optional[Tuple[str, ...]] = None
               ) -> "GradientFlow":
        """Recompile the collective layout for a new mesh (elastic event).

        Swaps the (frozen) config's topology / reduce_axes, updates the
        data degree, and re-resolves everything layout-derived: θ is
        re-tuned, per-bucket algorithms re-selected, and the StepPlan
        cache invalidated — the next ``plan()`` compiles for the new
        topology. ``reduce_axes`` defaults to the new topology's axes
        (pure-simulation callers); execution callers (Trainer) pass the
        live mesh axis names explicitly. Returns self for chaining."""
        cfg = self.cfg
        if topology is not None:
            if reduce_axes is None:
                reduce_axes = topology.axes
            cfg = dataclasses.replace(cfg, topology=topology,
                                      reduce_axes=tuple(reduce_axes))
        elif reduce_axes is not None:
            cfg = dataclasses.replace(cfg, reduce_axes=tuple(reduce_axes))
        self.cfg = cfg
        if num_data_shards is not None:
            self.num_data_shards = int(num_data_shards)
        self._resolve_layout()
        return self

    def _algos_for(self, bounds) -> tuple:
        """One ReduceAlgorithm per bucket (auto-selected by byte size).

        ``pallas_ring`` entries are stamped with the bucket index as
        their Mosaic collective-id base: per-bucket rings in one compiled
        step may run concurrently and must not share collective
        bookkeeping, and the bucket layout — unlike any process-local
        counter — is derived identically on every host."""
        elt = wire_mod.wire_itemsize(self.cfg.wire_format,
                                     self.cfg.wire_dtype)
        algos = []
        for i, (s, e) in enumerate(bounds):
            algo = topo_mod.resolve_algorithm(self.cfg.collective_algo,
                                              self.cfg.topology,
                                              (e - s) * elt)
            if isinstance(algo, topo_mod.PallasRing):
                algo = algo.with_id(i)
            algos.append(algo)
        return tuple(algos)

    # -- state -------------------------------------------------------------

    @property
    def _residual_size(self) -> int:
        """Pool-shaped when error feedback is live, zero-size otherwise
        (placeholders keep the train-state pytree uniform)."""
        return self.pool.size if self.cfg.feedback_enabled else 0

    def init_state(self) -> GFState:
        residual = jnp.zeros((self._residual_size,), jnp.float32)
        if self.cfg.csc_enabled:
            st = csc_mod.init_state(self.pool.size, self.cfg.chunk_elems)
            return GFState(hg=st.hg, chunk_norms=st.chunk_norms,
                           residual=residual)
        # Zero-size placeholders keep the train-state pytree uniform.
        return GFState(hg=jnp.zeros((0,), jnp.float32),
                       chunk_norms=jnp.zeros((0,), jnp.float32),
                       residual=residual)

    def abstract_state(self) -> GFState:
        residual = jax.ShapeDtypeStruct((self._residual_size,), jnp.float32)
        if self.cfg.csc_enabled:
            return GFState(
                hg=jax.ShapeDtypeStruct((self.pool.size,), jnp.float32),
                chunk_norms=jax.ShapeDtypeStruct((self.num_chunks,),
                                                 jnp.float32),
                residual=residual)
        return GFState(hg=jax.ShapeDtypeStruct((0,), jnp.float32),
                       chunk_norms=jax.ShapeDtypeStruct((0,), jnp.float32),
                       residual=residual)

    def stage_for_step(self, step: int) -> schedule_mod.SparsityStage:
        return schedule_mod.stage_at(self.stages, step,
                                     first_steps=self._stage_firsts)

    def plan(self, stage: Optional[schedule_mod.SparsityStage] = None):
        """Compile this backend's bucket layout into the overlap engine's
        ``StepPlan`` IR (``repro.core.engine``): one ``BucketTask`` per
        collective plus the tensor-aligned update spans. The plan reuses
        the exact bounds/algorithms ``reduce`` executes monolithically —
        same layout, explicit structure.

        Plans are cached per (mesh-shape key, stage); ``replan`` clears
        the cache, so a plan compiled for a retired topology can never be
        served after an elastic event."""
        # Keyed on the full (frozen) stage, not stage.index: synthetic
        # stages (e.g. the dense warm-up twin) share an index with real
        # schedule entries but compile to a different plan.
        key = (self.plan_cache_key(), stage)
        plan = self._plan_cache.get(key)
        if plan is None:
            from repro.core import engine
            plan = engine.compile_step_plan(self, stage)
            self._plan_cache[key] = plan
        return plan

    # -- the reduction -----------------------------------------------------

    def reduce(
        self,
        pool_grads: jax.Array,
        state: GFState,
        *,
        stage: Optional[schedule_mod.SparsityStage] = None,
        prepacked: bool = False,
        census: Optional[jax.Array] = None,
        census_sum: Optional[jax.Array] = None,
        loss_scale=None,
    ) -> Tuple[jax.Array, jax.Array, GFState]:
        """Reduce the local gradient pool across the data axes.

        Returns (mean_grads f32[pool], elem_mask bool[pool], new_state).
        ``elem_mask`` is all-True except for CSC's unselected chunks, whose
        update the optimizer must skip (Algorithm 1 lines 13–17).

        ``prepacked=True`` declares that ``pool_grads`` is already in the
        wire dtype (the single-pass pack pipeline casts at pack time), so
        the dense/lazy buckets skip their per-bucket down-cast. CSC keeps
        f32 input regardless — its hg accumulation must not round through
        the wire dtype before the selection decides what is transmitted.

        Quantized wire formats: ``census`` is the per-rank chunk-L1
        census the pack pipeline already emitted for ``pool_grads``
        (recomputed here when None — one extra pool pass); the dense/lazy
        quantized path psums it (one tiny f32[chunks] collective) to
        derive rank-invariant per-chunk scales. ``census_sum`` hands in an
        ALREADY-allreduced census instead (the guarded monolithic path,
        which needs the sum for its health verdict too — passing it back
        keeps the guarded step at exactly the unguarded step's collective
        count). ``loss_scale`` is the
        guard's power-of-two scale on ``pool_grads`` (None = 1): the
        error-feedback residual is stored UNSCALED, so the scaled
        quantization error is divided by it on write and re-multiplied on
        read — scaler backoffs never corrupt carried feedback.
        """
        with jax.named_scope("gf.reduce"):
            cfg = self.cfg
            if cfg.mode == "csc":
                assert not prepacked, (
                    "CSC consumes the f32 pool: pack with dtype=float32")
                stage = stage or self.stages[-1]
                k = stage.num_selected
                if k >= self.num_chunks:
                    # Warm-up dense stage: full pool via the lazy path,
                    # but the CSC state must keep tracking norms for the
                    # handoff. Quantized runs keep NATIVE transport here:
                    # the very first iterations have no trustworthy
                    # census basis yet, and warm-up is by definition the
                    # dense phase.
                    return self._dense_or_lazy_with_norms(pool_grads, state)
                wire_bounds = csc_mod.wire_bucket_boundaries(
                    k, cfg.chunk_elems, self.bucket_elems)
                feedback = cfg.feedback_enabled
                res = csc_mod.csc_reduce(
                    pool_grads,
                    csc_mod.CSCState(hg=state.hg,
                                     chunk_norms=state.chunk_norms),
                    cfg,
                    num_selected=k,
                    bucket_boundaries=wire_bounds,
                    num_data_shards=self.num_data_shards,
                    algo=self._algos_for(wire_bounds),
                    residual=state.residual if feedback else None,
                )
                return res.grads, res.elem_mask, GFState(
                    hg=res.state.hg, chunk_norms=res.state.chunk_norms,
                    residual=res.residual if feedback else state.residual)

            dense = cfg.mode == "dense"
            bounds = self._dense_bounds if dense else self._lazy_bounds
            algos = self._dense_algos if dense else self._lazy_algos
            if self.wire_spec is not None:
                return self._quantized_dense_or_lazy(
                    pool_grads, state, bounds, algos, census=census,
                    census_sum=census_sum, loss_scale=loss_scale)
            wire = None if prepacked else cfg.wire_dtype
            summed = bucketed_reduce(pool_grads, bounds, cfg.reduce_axes,
                                     wire, algo=algos)
            mean = summed / self.num_data_shards
            mask = jnp.ones(mean.shape, dtype=jnp.bool_)
            return mean, mask, state

    def quantized_scales(self, census_sum: jax.Array) -> jax.Array:
        """Per-chunk wire scales from a rank-invariant census sum."""
        return wire_mod.scales_from_census(
            census_sum, chunk_elems=self.cfg.chunk_elems,
            num_shards=self.num_data_shards, spec=self.wire_spec)

    def _quantized_dense_or_lazy(
        self, pool_grads: jax.Array, state: GFState, bounds, algos, *,
        census: Optional[jax.Array] = None,
        census_sum: Optional[jax.Array] = None, loss_scale=None,
    ) -> Tuple[jax.Array, jax.Array, GFState]:
        """Dense/lazy transport on a low-bit wire: one census psum for
        rank-invariant scales, one pool-pass quantize with error
        feedback, scaled-domain buckets on the wire, dequant after."""
        cfg = self.cfg
        from repro.parallel.collectives import reduce_pool
        g = pool_grads.astype(jnp.float32)
        if cfg.feedback_enabled:
            r = state.residual if loss_scale is None else \
                state.residual * loss_scale
            g = g + r
        if census_sum is None:
            if census is None:
                census = wire_mod.chunk_l1(pool_grads.astype(jnp.float32),
                                           cfg.chunk_elems)
            census_sum = reduce_pool(census, cfg.reduce_axes)
        scales = self.quantized_scales(census_sum)
        q, err = wire_mod.quantize_pool(
            g, scales, chunk_elems=cfg.chunk_elems, spec=self.wire_spec,
            num_shards=self.num_data_shards)
        summed = bucketed_reduce(q, bounds, cfg.reduce_axes, None,
                                 algo=algos)
        mean = wire_mod.dequantize_pool(summed, scales, cfg.chunk_elems) \
            / self.num_data_shards
        mask = jnp.ones(mean.shape, dtype=jnp.bool_)
        if cfg.feedback_enabled:
            residual = err if loss_scale is None else err / loss_scale
            state = state._replace(residual=residual)
        return mean, mask, state

    def _dense_or_lazy_with_norms(
        self, pool_grads: jax.Array, state: GFState,
    ) -> Tuple[jax.Array, jax.Array, GFState]:
        """Dense warm-up iteration of CSC: reduce everything, refresh norms,
        absorb any pending hg (none in steady warm-up)."""
        cfg = self.cfg
        g = pool_grads.astype(jnp.float32) + state.hg
        summed = bucketed_reduce(g, self._lazy_bounds, cfg.reduce_axes,
                                 cfg.wire_dtype, algo=self._lazy_algos)
        mean = summed / self.num_data_shards
        l1 = csc_mod.chunk_l1_norms(mean, cfg.chunk_elems)
        from repro.parallel.collectives import reduce_pool
        from repro.parallel.sharding import match_vma
        norms = reduce_pool(l1, cfg.reduce_axes)
        mask = jnp.ones(mean.shape, dtype=jnp.bool_)
        # hg is per-data-shard state: keep its device-varying tag even for
        # the (invariant) zeros written during dense warm-up.
        hg_new = match_vma(jnp.zeros_like(state.hg), pool_grads)
        return mean, mask, GFState(hg=hg_new, chunk_norms=norms,
                                   residual=state.residual)

    # -- analytics ---------------------------------------------------------

    def wire_bytes_per_step(self, stage: Optional[schedule_mod.SparsityStage]
                            = None) -> int:
        """Bytes entering the allreduce on each device (model, not measured).
        Used by the paper-table benchmarks and the kernel-bench wire gate.

        Low-bit formats count 1 byte per payload element plus the f32
        census sidecar: CSC's norm allreduce already carries the census
        (scales derive from it for free), while the dense/lazy quantized
        path adds its own f32[chunks] census psum."""
        elt = wire_mod.wire_itemsize(self.cfg.wire_format,
                                     self.cfg.wire_dtype)
        quantized = self.wire_spec is not None
        census_bytes = self.num_chunks * 4  # f32 per-chunk census
        if self.cfg.mode == "csc":
            stage = stage or self.stages[-1]
            if stage.num_selected < self.num_chunks:
                payload = stage.num_selected * self.cfg.chunk_elems * elt
                if quantized:
                    return payload + census_bytes
                # native: the norm allreduce rides at ≈ wire width
                return payload + self.num_chunks * elt
            # warm-up stays on native transport (see reduce()).
            return self.pool.size * jnp.dtype(self.cfg.wire_dtype).itemsize \
                + census_bytes
        payload = self.pool.size * elt
        return payload + census_bytes if quantized else payload

    def num_collectives(self, stage=None) -> int:
        cfg = self.cfg
        # Quantized dense/lazy adds the census psum the scales derive from.
        extra = 1 if (self.wire_spec is not None
                      and cfg.mode in ("dense", "lazy")) else 0
        if cfg.mode == "dense":
            return len(self._dense_bounds) + extra
        if cfg.mode == "lazy":
            return len(self._lazy_bounds) + extra
        stage = stage or self.stages[-1]
        if stage.num_selected >= self.num_chunks:
            return len(self._lazy_bounds) + 1
        return len(csc_mod.wire_bucket_boundaries(
            stage.num_selected, cfg.chunk_elems, self.bucket_elems)) + 1
