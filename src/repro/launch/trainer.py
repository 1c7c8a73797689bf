"""Step builders: assemble model + GradientFlow + optimizer into jitted
train / serve steps over the production mesh.

Distribution architecture (see DESIGN.md §3.1):

  jit
  ├─ fwd/bwd shard_map — MANUAL over data axes ('pod','data'); AUTO over
  │  'model':
  │    params are pcast-to-varying so jax.grad yields *per-data-shard,
  │    unsummed* gradients — the DP reduction belongs to GradientFlow,
  │    not to implicit autodiff collectives (the paper's whole point);
  │    model code uses with_sharding_constraint TP/EP/SP over 'model'
  │    (GSPMD inserts those collectives). Gradients exit STACKED along a
  │    leading data axis (each shard holds its own row — a relabeling,
  │    not a transfer).
  └─ update shard_map — fully MANUAL over data AND model axes (a SIBLING
     region, not a nested one):
       reduce+update in *local pool space*: each model shard ravels its
       own parameter slices into a contiguous pool (zero gather), the
       overlap engine (repro.core.engine) runs the per-bucket staged
       pipeline — bucket i's collective across the data axes issued while
       bucket i-1's fused optimizer update runs — and the pool-space
       optimizer updates the f32 master; optimizer + GradientFlow state
       is thereby sharded over the model axis (ZeRO-style) for free.

The update runs in a SIBLING region, not nested inside the fwd/bwd
region, so the data-axis psums/ppermutes are issued from one flat manual
region over (data..., model).

The reduce step dispatches on ``GradientFlowConfig.collective_algo``
through the topology registry: ``flat``/``two_level``/``tree`` bottom out
in psum flavors, while ``pallas_ring`` runs this repo's own 2(N-1)-step
ring (kernels/ring_reduce.py on TPU, the ppermute twin on CPU) inside the
same manual region — no trainer-side plumbing beyond the config string
(tests/test_ring_reduce.py trains end-to-end with it).
``GradientFlowConfig.overlap`` selects staged (per-bucket pipeline,
default) vs monolithic (the old barrier chain) execution of the update
region; both are numerically equivalent (tests/test_engine.py).

Device scopes (``jax.named_scope``; they reach each compiled
instruction's ``op_name`` and so name device time in a profile):
``model`` holds forward, loss and head, its backward showing as
``transpose(jvp(model))``; GradientFlow's phases are ``gf.pack``,
``gf.master_pack``, ``gf.reduce``, ``gf.update``, ``gf.apply_inflight``
and ``gf.csc_select`` (docs/architecture.md, "Reading a trace").
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ShapeConfig, TrainConfig
from repro.core import GFState, GradientFlow, GradientPool
from repro.core.schedule import SparsityStage
from repro.models import build_model
from repro.models.registry import input_specs as model_input_specs
from repro.optim import abstract_state as opt_abstract_state
from repro.optim import init_state as opt_init_state
from repro.optim import update_unpack as opt_update_unpack
from repro.optim import scaler as scaler_mod
from repro.optim.lars import LARSScaler
from repro.optim.schedules import lr_at
from repro.parallel import sharding as sh
from repro.parallel.collectives import (compat_pvary, compat_set_mesh,
                                        compat_shard_map)


class TrainState(NamedTuple):
    params: Any   # f32 master tree; sharded over 'model' per rules
    opt: Any      # pool-space optimizer state; P('model')
    gf: GFState   # GradientFlow state; P('model')
    step: jax.Array
    # Loss-scaler state (repro.optim.scaler.ScalerState) when the numeric
    # guard is enabled (GradientFlowConfig.guard); the empty tuple — zero
    # pytree leaves — otherwise, so unguarded states, their checkpoints,
    # and positional construction all predate-compatibly ignore it.
    guard: Any = ()
    # The pack staging buffer (repro.core.pool.pack_into): the previous
    # step's packed gradient pool, threaded back through the donated
    # state so the fwd-region pack writes fully in place — steady-state
    # steps allocate nothing pool-sized. A step built with donate=False
    # passes it through untouched (donation is the whole point). The
    # empty-tuple default keeps positionally-constructed legacy states
    # valid; ``Trainer.init_state`` always materializes the buffer.
    staging: Any = ()
    # Cross-step pipeline lane (repro.core.engine.InflightLane): the
    # deferred tail buckets' reduced-but-unapplied mean segments. LIVE
    # ONLY inside a pipelined scanned window — ``build_train_window``
    # seeds it from zeros and flushes it before returning, so every
    # TrainState that crosses the jit boundary (checkpoints, replan,
    # eval) carries the empty tuple: fully-applied params, by
    # construction. ``assert_flushed`` is the seam that pins this.
    inflight: Any = ()


_pvary = compat_pvary


def is_flushed(state: TrainState) -> bool:
    """True when the state carries no live cross-step pipeline lane —
    i.e. every emitted bucket update has been applied to params. Only a
    flushed state may be checkpointed, replanned, or handed to a
    non-pipelined step: a live lane's deferred segments exist nowhere
    but in the carry."""
    return not jax.tree_util.tree_leaves(state.inflight)


def assert_flushed(state: TrainState, what: str = "checkpoint") -> None:
    """The window-edge seam: refuse to let a mid-pipeline TrainState
    escape. ``build_train_window`` flushes its lane before returning, so
    hitting this means a caller reached into the scan carry (or built a
    state by hand) — saving it would silently drop the in-flight tail
    updates."""
    if not is_flushed(state):
        raise ValueError(
            f"TrainState carries an in-flight pipeline lane; {what} "
            "requires a flushed state (window edges flush — pass the "
            "state a build_train_window call returned, not a mid-window "
            "carry)")


class Trainer:
    def __init__(self, cfg: TrainConfig, mesh: Mesh,
                 rules: Dict[str, Optional[str]]):
        self.cfg = cfg
        self.mesh = mesh
        self.rules = dict(rules)
        self.model = build_model(cfg.model)
        self.specs = self.model.param_specs()

        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.model_size = sizes.get("model", 1)
        self.data_axes = tuple(a for a in mesh.axis_names
                               if a in ("pod", "data"))
        self.num_data = int(np.prod([sizes[a] for a in self.data_axes])) \
            if self.data_axes else 1

        # Local (per-model-shard) pool.
        self.local_specs = sh.localize_specs(self.specs, self.rules,
                                             self.model_size)
        gf_cfg = dataclasses.replace(cfg.gradientflow,
                                     reduce_axes=self.data_axes)
        if gf_cfg.topology is None and self.data_axes:
            # Derive bandwidth/latency levels from the mesh so 'auto'
            # algorithm selection and θ tuning have a model to price
            # against (see repro.parallel.topology).
            from repro.launch.mesh import mesh_topology
            gf_cfg = dataclasses.replace(
                gf_cfg, topology=mesh_topology(mesh, self.data_axes))
        # CSC chunking and per-chunk quantization scales both key off
        # whole chunks: pad the pool to a chunk multiple for either.
        pad = gf_cfg.chunk_elems \
            if (gf_cfg.csc_enabled or gf_cfg.quantized) else 1
        self.pool = GradientPool(sh.abstract_params(self.local_specs),
                                 pad_to=pad)
        self.gf = GradientFlow(gf_cfg, self.pool, self.num_data)
        self.gf_cfg = gf_cfg
        self.opt_name = cfg.optimizer.name
        self.lars = LARSScaler(self.pool) if self.opt_name == "lars" else None
        from repro.core.engine import OverlapEngine
        self.engine = OverlapEngine(self.gf, self.opt_name, cfg.optimizer,
                                    lars=self.lars)

        self.global_pool = self.pool.size * self.model_size
        self.num_chunks_global = self.gf.num_chunks * self.model_size

        self.param_pspecs = sh.param_pspecs(self.specs, self.rules)
        self.param_shardings = sh.param_shardings(self.specs, mesh,
                                                  self.rules)

    # -- elastic replan -------------------------------------------------------

    def replan(self, mesh: Optional[Mesh] = None, topology=None) -> None:
        """Recompile the collective layer after an elastic event.

        An elastic remesh keeps the model axis fixed (per-layer sharding
        and the local pool are unchanged) but changes the data degree and
        the fabric levels — everything θ tuning, per-bucket algorithm
        selection, and the staged timeline were priced against. This
        re-derives the data axes / degree / topology from the new mesh
        (or takes an explicit ``topology``) and routes through
        ``OverlapEngine.replan`` → ``GradientFlow.replan``, invalidating
        the StepPlan cache. Callers must rebuild their jitted step
        (``build_train_step``) afterwards — the old trace embeds the old
        plan."""
        if mesh is not None:
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            assert sizes.get("model", 1) == self.model_size, (
                "elastic events keep the model-parallel degree fixed",
                sizes, self.model_size)
            self.mesh = mesh
            self.data_axes = tuple(a for a in mesh.axis_names
                                   if a in ("pod", "data"))
            self.num_data = int(np.prod([sizes[a]
                                         for a in self.data_axes])) \
                if self.data_axes else 1
            if topology is None and self.data_axes:
                from repro.launch.mesh import mesh_topology
                topology = mesh_topology(mesh, self.data_axes)
            self.param_shardings = sh.param_shardings(self.specs, mesh,
                                                      self.rules)
        # reduce_axes stay the LIVE mesh axis names (execution), even when
        # the topology models different level names (simulation).
        self.engine.replan(topology, num_data_shards=self.num_data,
                           reduce_axes=self.data_axes)
        self.gf_cfg = self.gf.cfg

    # -- state construction ---------------------------------------------------

    def _pool_sharding(self) -> NamedSharding:
        spec = P("model") if self.model_size > 1 else P(None)
        return NamedSharding(self.mesh, spec)

    def _hg_sharding(self) -> NamedSharding:
        # hg is per-data-shard state (the paper's per-GPU historical
        # gradients): leading dim indexes the data shard.
        row = self.data_axes if self.data_axes else None
        col = "model" if self.model_size > 1 else None
        return NamedSharding(self.mesh, P(row, col))

    def _gf_abstract(self) -> GFState:
        # Error-feedback residual: per-data-shard pool state, exactly
        # hg's layout (a stacked row per shard). Zero-size placeholder
        # keeps the pytree uniform when feedback is off.
        rep = NamedSharding(self.mesh, P(None, None))
        residual = jax.ShapeDtypeStruct(
            (self.num_data, self.global_pool), jnp.float32,
            sharding=self._hg_sharding()) \
            if self.gf_cfg.feedback_enabled else \
            jax.ShapeDtypeStruct((1, 0), jnp.float32, sharding=rep)
        if self.gf_cfg.csc_enabled:
            return GFState(
                hg=jax.ShapeDtypeStruct((self.num_data, self.global_pool),
                                        jnp.float32,
                                        sharding=self._hg_sharding()),
                chunk_norms=jax.ShapeDtypeStruct(
                    (self.num_chunks_global,), jnp.float32,
                    sharding=self._pool_sharding()),
                residual=residual)
        return GFState(
            hg=jax.ShapeDtypeStruct((1, 0), jnp.float32, sharding=rep),
            chunk_norms=jax.ShapeDtypeStruct((0,), jnp.float32,
                                             sharding=NamedSharding(
                                                 self.mesh, P(None))),
            residual=residual)

    def abstract_state(self) -> TrainState:
        params = jax.tree_util.tree_map(
            lambda s, shd: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                sharding=shd),
            sh.abstract_params(self.specs, jnp.float32),
            self.param_shardings)
        opt = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=self._pool_sharding()),
            opt_abstract_state(self.opt_name, self.global_pool))
        rep = NamedSharding(self.mesh, P())
        guard = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
            scaler_mod.abstract(self.gf_cfg.guard)) \
            if self.gf_cfg.guarded else ()
        staging = jax.ShapeDtypeStruct(
            (self.num_data, self.global_pool), self._staging_dtype,
            sharding=self._hg_sharding())
        return TrainState(
            params=params, opt=opt, gf=self._gf_abstract(),
            step=jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
            guard=guard, staging=staging)

    def init_state(self, key: jax.Array) -> TrainState:
        with compat_set_mesh(self.mesh):
            params = sh.init_params(self.specs, key, dtype=jnp.float32)
            params = jax.tree_util.tree_map(jax.device_put, params,
                                            self.param_shardings)
            opt = jax.tree_util.tree_map(
                lambda a: jax.device_put(
                    jnp.zeros((self.global_pool,), a.dtype),
                    self._pool_sharding()),
                opt_init_state(self.opt_name, 1))
            residual = jax.device_put(
                jnp.zeros((self.num_data, self.global_pool), jnp.float32),
                self._hg_sharding()) \
                if self.gf_cfg.feedback_enabled else \
                jnp.zeros((1, 0), jnp.float32)
            if self.gf_cfg.csc_enabled:
                from repro.core import csc as csc_mod
                # per-shard init tiled across model shards
                one = csc_mod.init_state(self.pool.size,
                                         self.gf_cfg.chunk_elems)
                gf = GFState(
                    hg=jax.device_put(
                        jnp.zeros((self.num_data, self.global_pool),
                                  jnp.float32),
                        self._hg_sharding()),
                    chunk_norms=jax.device_put(
                        jnp.tile(one.chunk_norms, self.model_size),
                        self._pool_sharding()),
                    residual=residual)
            else:
                gf = GFState(hg=jnp.zeros((1, 0), jnp.float32),
                             chunk_norms=jnp.zeros((0,), jnp.float32),
                             residual=residual)
            guard = scaler_mod.init(self.gf_cfg.guard) \
                if self.gf_cfg.guarded else ()
            staging = jax.device_put(
                jnp.zeros((self.num_data, self.global_pool),
                          self._staging_dtype), self._hg_sharding())
            return TrainState(params=params, opt=opt, gf=gf,
                              step=jnp.zeros((), jnp.int32), guard=guard,
                              staging=staging)

    # -- batch specs ----------------------------------------------------------

    def batch_pspec(self, batch_tree: Any) -> Any:
        """Shard the leading (batch) dim over the data axes — unless the
        per-cell batch is smaller than the data degree (long_500k B=1),
        in which case it replicates."""
        def one(x):
            b = x.shape[0] if hasattr(x, "shape") and x.shape else 0
            if self.data_axes and b >= self.num_data and \
                    b % self.num_data == 0:
                return P(self.data_axes)
            return P()
        return jax.tree_util.tree_map(one, batch_tree)

    def per_shard_batch(self, global_batch: int) -> int:
        if global_batch >= self.num_data:
            assert global_batch % self.num_data == 0
            return global_batch // self.num_data
        return global_batch  # replicated

    # -- the train step ---------------------------------------------------

    @property
    def _pack_dtype(self):
        """Pool dtype of the grad handoff: dense/lazy pack straight to the
        wire dtype (the reduce then skips its per-bucket cast); CSC packs
        to f32 because hg accumulation precedes the wire cast, and the
        quantized wire formats pack to f32 because the update region
        quantizes AFTER error-feedback injection (repro.core.wire)."""
        prepacked = self.gf_cfg.mode in ("dense", "lazy") \
            and self.gf.wire_spec is None
        return jnp.dtype(self.gf_cfg.wire_dtype) if prepacked \
            else jnp.float32

    @property
    def _staging_dtype(self):
        """Dtype of the pack staging buffer (``pool.pack_into``): with
        kernels on, the pack dtype — ``ops.pool_pack`` consumes a staging
        buffer of that dtype and hands the pool back as the next one,
        whether the no-census XLA pass or the census kernel packs it;
        with kernels off, the leaves' (f32) source dtype — the ref twin's
        stage-then-cast contract."""
        pd = self._pack_dtype
        if pd == jnp.dtype(jnp.float32) or self.gf_cfg.use_kernels:
            return pd
        return jnp.dtype(jnp.float32)

    @property
    def _census_on(self) -> bool:
        """Quantized dense/lazy: the fwd-region pack emits the fused
        chunk-L1 census the wire scales derive from (one pass, no new
        sweep); it rides the region boundary next to the pool."""
        return self.gf.wire_spec is not None \
            and self.gf_cfg.mode in ("dense", "lazy")

    def _inner_update(self, gpool, params, opt, gfstate, lr, stage,
                      scaler=None, census=None):
        """Runs fully manual (data+model), as the SIBLING region of the
        fwd/bwd shard_map. Everything here is local; ``gpool`` arrives
        already packed (the fwd region ravels grads into the local pool
        before the handoff) and gfstate.hg as this data shard's
        (1, local_pool) row.

        ``overlap='staged'`` (default) routes through the overlap engine:
        the StepPlan compiled from GradientFlow's bucket layout executes
        software-pipelined, bucket i's collective issued while bucket
        i-1's fused update runs. ``'monolithic'`` keeps the barrier chain
        below — reduce every bucket, then one fused update+unpack of the
        whole pool. Both paths bottom out in the same per-bucket
        primitives and are numerically equivalent (tests/test_engine.py).
        """
        cfg = self.gf_cfg
        gf_local = GFState(hg=gfstate.hg[0], chunk_norms=gfstate.chunk_norms,
                           residual=gfstate.residual[0])
        if scaler is not None:
            return self._inner_update_guarded(gpool, params, opt, gf_local,
                                              scaler, lr, stage,
                                              census=census)
        if cfg.overlap == "staged":
            plan = self.engine.plan_for(stage)
            new_params, opt2, gf2 = self.engine.run(
                plan, gpool, params, opt, gf_local, lr, census=census)
            return new_params, opt2, GFState(hg=gf2.hg[None],
                                             chunk_norms=gf2.chunk_norms,
                                             residual=gf2.residual[None])
        assert cfg.overlap == "monolithic", cfg.overlap
        prepacked = cfg.mode in ("dense", "lazy") \
            and self.gf.wire_spec is None
        reduced, mask, gf2 = self.gf.reduce(gpool, gf_local, stage=stage,
                                            prepacked=prepacked,
                                            census=census)
        master = self.engine.master_pool(params)
        scale = ratios = None
        if self.lars is not None:
            r = self.lars.ratios(master, reduced, self.cfg.optimizer, mask)
            if cfg.use_kernels:
                # Streaming update: hand the per-tensor vector straight to
                # the kernel (expanded per tile in VMEM) — the pool-sized
                # scale buffer and its extra HBM pass disappear.
                ratios = r
            else:
                scale = self.lars.expand(r)
        new_params, opt2 = opt_update_unpack(
            self.opt_name, self.pool, master, reduced, opt, mask,
            self.cfg.optimizer, lr, scale=scale, ratios=ratios,
            use_kernels=cfg.use_kernels)
        gf2 = GFState(hg=gf2.hg[None], chunk_norms=gf2.chunk_norms,
                      residual=gf2.residual[None])
        return new_params, opt2, gf2

    def _inner_update_guarded(self, gpool, params, opt, gf_local, scaler,
                              lr, stage, census=None):
        """Guard-railed reduce+update: the SAME collectives as the
        unguarded paths (the `--guard-check` jaxpr gate pins this), plus
        the census-derived health verdict and one atomic ``lax.cond``
        commit. ``gpool`` arrives scaled by ``scaler.scale`` (the fwd
        region scaled the loss); dense/lazy unscale the reduced mean
        while CSC unscales at entry so the hg residual stays
        scale-invariant across backoffs. A tripped verdict rejects the
        step — params, momentum, and hg bit-identical — and only the
        scaler state advances. Returns (params, opt, gfstate, scaler,
        HealthFlags); the flags ride out to the step metrics so a
        scanned window keeps per-step guard visibility."""
        from repro.core import guard as guard_mod

        cfg = self.gf_cfg
        gcfg = cfg.guard
        quantized = self.gf.wire_spec is not None
        if cfg.overlap == "staged":
            plan = self.engine.plan_for(stage)
            new_params, opt2, gf2, sc2, flags = self.engine.run_guarded(
                plan, gpool, params, opt, gf_local, scaler, lr,
                census=census)
            return new_params, opt2, GFState(
                hg=gf2.hg[None], chunk_norms=gf2.chunk_norms,
                residual=gf2.residual[None]), sc2, flags
        assert cfg.overlap == "monolithic", cfg.overlap
        limit = guard_mod.overflow_limit(gcfg, cfg.wire_dtype)
        prepacked = cfg.mode in ("dense", "lazy") and not quantized
        census_sum = None
        if quantized and not cfg.csc_enabled:
            # Low-bit wires saturate instead of overflowing to Inf, so
            # the census psum (which the wire scales need anyway) is the
            # health channel; passing the sum back into reduce() keeps
            # the guarded step at the unguarded collective count.
            from repro.core import wire as wire_mod
            from repro.parallel.collectives import reduce_pool
            if census is None:
                census = wire_mod.chunk_l1(gpool.astype(jnp.float32),
                                           cfg.chunk_elems)
            census_sum = reduce_pool(census, self.data_axes)
        gin = gpool if (prepacked or quantized and not cfg.csc_enabled) \
            else gpool.astype(jnp.float32) / scaler.scale
        reduced, mask, gf2 = self.gf.reduce(
            gin, gf_local, stage=stage, prepacked=prepacked,
            census_sum=census_sum,
            loss_scale=scaler.scale if quantized else None)
        if cfg.csc_enabled:
            # The allreduced chunk census (already issued for selection /
            # warm-up tracking) IS the health channel; `reduced` is
            # already unscaled since `gin` was. Quantized sparse stages
            # tighten the limit per chunk against the scale basis (the
            # previous census) — int8's saturating clip never produces
            # the Inf a scalar limit waits for.
            limit_c = limit
            if quantized and stage.num_selected < self.gf.num_chunks:
                limit_c = guard_mod.per_chunk_limit(gf_local.chunk_norms,
                                                    gcfg, limit)
            flags = guard_mod.flags_from_census(gf2.chunk_norms, limit_c)
            red = reduced
        elif quantized:
            flags = guard_mod.flags_from_census(census_sum, limit)
            red = reduced / scaler.scale
        else:
            flags = guard_mod.flags_from_words(
                [guard_mod.health_word(reduced)], limit)
            red = reduced / scaler.scale
        master = self.engine.master_pool(params)

        def commit():
            scale = ratios = None
            if self.lars is not None:
                r = self.lars.ratios(master, red, self.cfg.optimizer, mask)
                if cfg.use_kernels:
                    ratios = r
                else:
                    scale = self.lars.expand(r)
            new_params, opt2 = opt_update_unpack(
                self.opt_name, self.pool, master, red, opt, mask,
                self.cfg.optimizer, lr, scale=scale, ratios=ratios,
                use_kernels=cfg.use_kernels)
            return new_params, opt2, gf2

        ok = ~guard_mod.tripped(flags)
        new_params, opt2, gf3 = guard_mod.guarded_commit(
            ok, commit, (params, opt, gf_local))
        sc2 = scaler_mod.update(scaler, ok, gcfg)
        return new_params, opt2, GFState(
            hg=gf3.hg[None], chunk_norms=gf3.chunk_norms,
            residual=gf3.residual[None]), sc2, flags

    def _inner_update_pipelined(self, gpool, params, opt, gfstate, lr,
                                stage, scaler=None):
        """Pipelined twin of ``_inner_update`` (staged native dense/lazy
        only): commits head buckets in-step and returns the deferred
        tail's reduced segments as an ``InflightLane`` instead of
        applying them — the NEXT step's prologue region applies the lane
        before its forward pass. Returns (params, opt, gf, lane) or,
        guarded, (params, opt, gf, scaler, flags, lane)."""
        gf_local = GFState(hg=gfstate.hg[0],
                           chunk_norms=gfstate.chunk_norms,
                           residual=gfstate.residual[0])
        plan = self.engine.plan_for(stage)
        if scaler is not None:
            new_params, opt2, gf2, sc2, lane, flags = \
                self.engine.run_pipelined_guarded(
                    plan, gpool, params, opt, gf_local, scaler, lr)
            return new_params, opt2, GFState(
                hg=gf2.hg[None], chunk_norms=gf2.chunk_norms,
                residual=gf2.residual[None]), sc2, flags, lane
        new_params, opt2, gf2, lane = self.engine.run_pipelined(
            plan, gpool, params, opt, gf_local, lr)
        return new_params, opt2, GFState(
            hg=gf2.hg[None], chunk_norms=gf2.chunk_norms,
            residual=gf2.residual[None]), lane

    def _pipeline_plan(self, stage: Optional[SparsityStage] = None):
        """The StepPlan a pipelined window would run, or None when the
        config can't pipeline (no deferred tail, monolithic overlap, csc
        / quantized wire, warmup)."""
        if self.gf_cfg.overlap != "staged":
            return None
        plan = self.engine.plan_for(stage or self.gf.stages[-1])
        return plan if plan.pipeline_tail else None

    def _lane_specs(self, plan):
        from repro.core.engine import InflightLane
        pool_spec = P("model") if self.model_size > 1 else P(None)
        return InflightLane(
            segs=tuple(pool_spec for _ in plan.tail_tasks),
            lr=P(), ok=P())

    def _build_lane_apply(self, stage: Optional[SparsityStage] = None):
        """The prologue/flush region: a fully-manual (data+model)
        shard_map applying the carried lane to (params, opt) — the same
        axes as the update region, since the lane lives in local pool
        space. Runs before the fwd region each pipelined step and once
        more at the window edge (the flush). Every data shard computes
        the identical update (the lane is data-replicated), mirroring
        the update region's determinism contract."""
        stage = stage or self.gf.stages[-1]
        plan = self.engine.plan_for(stage)
        pool_spec = P("model") if self.model_size > 1 else P(None)
        opt_specs = jax.tree_util.tree_map(
            lambda _: pool_spec, opt_abstract_state(self.opt_name, 1))

        def apply_body(params, opt, lane):
            return self.engine.apply_inflight(plan, params, opt, lane)

        return compat_shard_map(
            apply_body, mesh=self.mesh,
            in_specs=(self.param_pspecs, opt_specs,
                      self._lane_specs(plan)),
            out_specs=(self.param_pspecs, opt_specs),
            axis_names=self._update_axes(), check_vma=False)

    def _empty_inflight_global(self, plan, *, guarded: bool):
        """Zero lane in GLOBAL (cross-model-shard) layout: each model
        shard's local tail segment concatenates along the pool axis,
        exactly as the pipelined update region's out_specs lay it out."""
        from repro.core.engine import InflightLane
        dt = self.engine.lane_dtype(guarded=guarded)
        return InflightLane(
            segs=tuple(jnp.zeros((t.size * self.model_size,), dt)
                       for t in plan.tail_tasks),
            lr=jnp.zeros((), jnp.float32),
            ok=jnp.zeros((), jnp.bool_))

    def _update_axes(self) -> set:
        axes = set(self.data_axes)
        if "model" in self.mesh.axis_names:
            axes.add("model")
        return axes

    def _build_step_fn(self, stage: Optional[SparsityStage] = None,
                       donate: bool = True, fault_hook=None,
                       pipelined: bool = False):
        """The un-jitted ``step(state, batch) -> (state, metrics)``
        closure shared by ``build_train_step`` (jit per step) and
        ``build_train_window`` (``lax.scan`` over a window of steps —
        the closure is already in scan-body form).

        ``pipelined=True`` (windows only; requires
        ``_pipeline_plan(stage)``) makes the step a one-step software
        pipeline: a prologue region applies the PREVIOUS step's carried
        tail-bucket updates to (params, opt) before the forward pass
        reads them — so fwd sees exactly the fully-updated params the
        unpipelined loop would have — and the update region commits head
        buckets in-step while deferring the tail's reduced segments into
        ``TrainState.inflight`` for the next iteration.

        ``fault_hook(gpool, step) -> gpool`` (optional) is traced into
        the update region on the LOCAL packed pool before the reduce —
        the data-plane fault-injection point (repro.runtime.faults): one
        compiled program, corruption gated on the step counter, hitting
        the real wire path rather than the analytic timeline. The step
        the hook sees is ``state.step`` — in-carry, so under a scanned
        window the corruption still fires mid-window on exactly its
        scheduled step."""
        cfg = self.cfg
        rules = self.rules
        stage = stage or self.gf.stages[-1]
        compute_dtype = jnp.dtype(cfg.model.compute_dtype)
        manual_axes = set(self.data_axes)
        guarded = self.gf_cfg.guarded

        pool_spec = P("model") if self.model_size > 1 else P(None)
        opt_specs = jax.tree_util.tree_map(lambda _: pool_spec,
                                           opt_abstract_state(self.opt_name,
                                                              1))
        # Update-region specs: hg keeps its leading per-data-shard dim
        # (size 1 per shard once the data axes split it).
        data_lead = (self.data_axes if len(self.data_axes) > 1 else
                     self.data_axes[0]) if self.data_axes else None
        lead_spec = P(data_lead, "model") if self.model_size > 1 \
            else P(data_lead, None)
        res_spec = lead_spec if self.gf_cfg.feedback_enabled \
            else P(None, None)
        if self.gf_cfg.csc_enabled:
            gf_specs = GFState(hg=lead_spec, chunk_norms=pool_spec,
                               residual=res_spec)
        else:
            gf_specs = GFState(hg=P(None, None), chunk_norms=P(None),
                               residual=res_spec)

        staging_on = donate
        census_on = self._census_on
        norms_chunk = self.gf_cfg.chunk_elems if census_on else 0
        if pipelined:
            plan = self._pipeline_plan(stage)
            assert plan is not None, "config cannot pipeline (no tail)"
            assert not census_on, "quantized wires never pipeline"

        def pack_local(grads, *st):
            """Grad pytree → local 1-D pool (runs where leaf shapes are
            local: directly in the fwd region when model is unsharded,
            else inside the nested pack shard_map below — pure local
            compute, no collectives).

            ``st`` threads the previous step's staging buffer
            (``pack_into`` donation: the pack writes fully in place);
            ``norms_chunk`` fuses the chunk-L1 census into the same pass
            when the wire scales need it (quantized dense/lazy). Returns
            (gpool[, staging][, census]) per the static flags."""
            with jax.named_scope("gf.pack"):
                if st:
                    gpool, census, staging = self.pool.pack_into(
                        st[0], grads, dtype=self._pack_dtype,
                        norms_chunk=norms_chunk,
                        use_kernels=self.gf_cfg.use_kernels)
                else:
                    gpool, census = self.pool.pack(
                        grads, dtype=self._pack_dtype,
                        norms_chunk=norms_chunk,
                        use_kernels=self.gf_cfg.use_kernels)
                    staging = gpool
            outs = (gpool,)
            if staging_on:
                outs += (staging,)
            if census_on:
                outs += (census,)
            return outs

        def fwd_bwd(params, batch, *rest):
            # When guarded, the loss is multiplied by the live scaler
            # scale BEFORE autodiff, so every gradient (and the bf16 pool
            # pack below) carries it — small gradients survive the wire
            # cast; the update region divides it back out.
            i = 0
            loss_scale = None
            if guarded:
                loss_scale = rest[i]
                i += 1
            staging_in = rest[i] if staging_on else None
            params_v = jax.tree_util.tree_map(
                lambda x: _pvary(x, self.data_axes), params)

            def loss_fn(p):
                with jax.named_scope("model"):
                    cp = jax.tree_util.tree_map(
                        lambda x: x.astype(compute_dtype), p)
                    loss, metrics = self.model.loss_fn(
                        cp, batch, rules=rules, remat=cfg.remat,
                        scan_layers=cfg.scan_layers,
                        attn_chunk=cfg.attn_chunk,
                        causal_skip=cfg.causal_skip,
                        compute_dtype=compute_dtype)
                    if loss_scale is not None:
                        loss = loss * loss_scale
                return loss, metrics

            if cfg.microbatches > 1:
                grads, metrics = self._accumulate(loss_fn, params_v, batch,
                                                  loss_scale=loss_scale)
            else:
                (_, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params_v)
            if self.data_axes:
                # The loss's mean over the data shards: a collective of
                # the step, so under the scope every collective is in.
                with jax.named_scope("gf.reduce"):
                    metrics = jax.tree_util.tree_map(
                        lambda m: jax.lax.pmean(m, self.data_axes), metrics)
            # Hand off grads to the sibling update region in POOL form:
            # the pack runs here (model-local space), so no scanned-layer
            # gradient ever crosses the region boundary — only a flat 1-D
            # pool, stacked along a leading data dim (each shard keeps
            # holding exactly its own row; a relabeling, not a transfer).
            # The staging buffer and the fused census (when on) ride the
            # same boundary next to the pool.
            pk_args = (grads,)
            if staging_on:
                pk_args += (staging_in[0],)
            # A model-sharded pool packs in a nested model-manual region;
            # so does any kernel pack, because a Pallas kernel cannot sit
            # in a region with an auto axis (even one of size 1). The
            # nested region re-lists the data axes: under a concrete mesh
            # context (``set_mesh``) jax lowers it against that mesh, and
            # only axes listed here then count as manual.
            if self.model_size > 1 or (self.gf_cfg.use_kernels and
                                       "model" in self.mesh.axis_names):
                n_out = len(pk_args) + (1 if census_on else 0)
                pk_in = (self.param_pspecs,) + \
                    ((pool_spec,) if staging_on else ())
                outs = compat_shard_map(
                    pack_local,
                    in_specs=pk_in, out_specs=(pool_spec,) * n_out,
                    axis_names={"model"} | manual_axes,
                    check_vma=False)(*pk_args)
            else:
                outs = pack_local(*pk_args)
            if self.data_axes:
                outs = tuple(x[None] for x in outs)
            return outs, metrics

        def update_body(gpool_st, params, opt, gfstate, lr, *extra):
            # extra = (census?, scaler?, step?) depending on the quantized
            # wire format / guarded / fault_hook flags.
            gpool = gpool_st[0] if self.data_axes else gpool_st
            i = 0
            census = None
            if census_on:
                census = extra[i][0] if self.data_axes else extra[i]
                i += 1
            scaler = None
            if guarded:
                scaler = extra[i]
                i += 1
            if fault_hook is not None:
                gpool = fault_hook(gpool, extra[i])
            if pipelined:
                return self._inner_update_pipelined(
                    gpool, params, opt, gfstate, lr, stage, scaler=scaler)
            return self._inner_update(gpool, params, opt, gfstate, lr,
                                      stage, scaler=scaler, census=census)

        # The jit-level batch is GLOBAL; in_specs split dim 0 over the data
        # axes so each shard sees its per-shard slice.
        global_batch_tree = model_input_specs(
            cfg.model, ShapeConfig(seq_len=cfg.seq_len,
                                   global_batch=cfg.global_batch,
                                   kind="train"), cfg.global_batch)
        batch_in = self.batch_pspec(global_batch_tree)
        metrics_out = {"loss": P(), "aux_loss": P()}
        params_in = jax.tree_util.tree_map(
            lambda _: P(), self.param_pspecs,
            is_leaf=lambda x: isinstance(x, P))
        # fwd/bwd region specs may only mention ITS manual axes (data):
        # the pool exits split over the leading data dim, its model-dim
        # layout left to GSPMD (the region is auto over model). The
        # update region re-declares it with the model split explicit
        # (model is manual there).
        if self.data_axes:
            pool_out_spec = P(data_lead)
            pool_in_spec = P(data_lead, "model") if self.model_size > 1 \
                else P(data_lead, None)
        else:
            pool_out_spec = P()
            pool_in_spec = pool_spec

        n_handoff = 1 + int(staging_on) + int(census_on)
        fwd_in_specs = (params_in, batch_in) + ((P(),) if guarded else ()) \
            + ((pool_out_spec,) if staging_on else ())
        sm_fwd = compat_shard_map(
            fwd_bwd, mesh=self.mesh, in_specs=fwd_in_specs,
            out_specs=((pool_out_spec,) * n_handoff, metrics_out),
            axis_names=manual_axes)
        # check_vma=False: model-replicated params flow through the
        # (model-sharded) pool, so the static checker tags their updates
        # as possibly model-varying. They are not: their grads arrive
        # model-invariant (GSPMD all-reduces them in the auto region) and
        # the update is deterministic, so all model shards compute
        # identical values (tested).
        scaler_specs = jax.tree_util.tree_map(
            lambda _: P(), scaler_mod.abstract(self.gf_cfg.guard)) \
            if guarded else None
        upd_in_specs = (pool_in_spec, self.param_pspecs, opt_specs,
                        gf_specs, P())
        upd_out_specs = (self.param_pspecs, opt_specs, gf_specs)
        if census_on:
            # The census rides the boundary in the pool's stacked layout.
            upd_in_specs = upd_in_specs + (pool_in_spec,)
        if guarded:
            from repro.core import guard as guard_mod
            upd_in_specs = upd_in_specs + (scaler_specs,)
            upd_out_specs = upd_out_specs + \
                (scaler_specs, guard_mod.HealthFlags(P(), P()))
        if fault_hook is not None:
            upd_in_specs = upd_in_specs + (P(),)
        if pipelined:
            # The outgoing lane exits in the pool's model-sharded layout
            # (each model shard emits its local tail segments).
            upd_out_specs = upd_out_specs + (self._lane_specs(plan),)
        sm_update = compat_shard_map(
            update_body, mesh=self.mesh,
            in_specs=upd_in_specs, out_specs=upd_out_specs,
            axis_names=self._update_axes(), check_vma=False)

        sm_apply = self._build_lane_apply(stage) if pipelined else None

        def step(state: TrainState, batch):
            if pipelined:
                # Apply step t-1's carried tail updates BEFORE fwd reads
                # the params: fwd then sees bit-for-bit the params the
                # unpipelined loop's step t would have started from.
                params0, opt0 = sm_apply(state.params, state.opt,
                                         state.inflight)
            else:
                params0, opt0 = state.params, state.opt
            fwd_args = (params0, batch)
            if guarded:
                fwd_args = fwd_args + (state.guard.scale,)
            if staging_on:
                fwd_args = fwd_args + (state.staging,)
            handoff, metrics = sm_fwd(*fwd_args)
            gpool_st = handoff[0]
            staging_st = handoff[1] if staging_on else state.staging
            census_st = handoff[-1] if census_on else None
            lr = lr_at(cfg.optimizer, state.step)
            upd_args = (gpool_st, params0, opt0, state.gf, lr)
            if census_on:
                upd_args = upd_args + (census_st,)
            if guarded:
                upd_args = upd_args + (state.guard,)
            if fault_hook is not None:
                upd_args = upd_args + (state.step,)
            out = sm_update(*upd_args)
            lane = state.inflight
            if pipelined:
                out, lane = out[:-1], out[-1]
            if guarded:
                from repro.core import guard as guard_mod
                new_params, opt2, gf2, sc2, flags = out
                metrics = {**metrics, **guard_mod.as_metrics(flags)}
            else:
                (new_params, opt2, gf2), sc2 = out, state.guard
            return TrainState(params=new_params, opt=opt2, gf=gf2,
                              step=state.step + 1, guard=sc2,
                              staging=staging_st, inflight=lane), metrics

        return step

    def build_train_step(self, stage: Optional[SparsityStage] = None,
                         donate: bool = True, fault_hook=None):
        """One jitted training step (see ``_build_step_fn`` for the
        closure semantics). ``donate=True`` donates the whole TrainState
        — params, optimizer, GFState (incl. the error-feedback
        residual), scaler, and the pack staging buffer update in
        place."""
        step = self._build_step_fn(stage=stage, donate=donate,
                                   fault_hook=fault_hook)
        return jax.jit(step, donate_argnums=(0,) if donate else ())

    def build_train_window(self, window_steps: int,
                           stage: Optional[SparsityStage] = None,
                           donate: bool = True, fault_hook=None):
        """``window_steps`` training steps as ONE compiled XLA program:
        ``lax.scan`` over the shared step closure with the full
        TrainState as the (donated) carry, so a whole window runs with a
        single dispatch and a single host sync.

        The scan wraps the shard_map'd step from the OUTSIDE. Batches arrive
        stacked on a leading scan axis (length ``window_steps``, or
        shorter for a tail window — jit re-specializes per length, so
        keep full windows on the hot path) and per-step metrics return
        stacked ``[K]`` so the host reads the whole window at once.

        A window is compiled per CSC ``stage`` exactly like
        ``build_train_step``: snap stage boundaries to the window grid
        (repro.core.schedule.snap_stages_to_window) so no window
        straddles a stage and each stage costs one executable.

        When the plan carries a deferred tail
        (``GradientFlowConfig.pipeline_tail_buckets`` != 0, staged
        overlap, native dense/lazy) and the window has more than one
        step, the scan body runs cross-step pipelined: the carry grows
        an ``InflightLane`` of reduced-but-unapplied tail segments,
        seeded from zeros at window entry and FLUSHED before the window
        returns — the TrainState crossing the jit boundary is always
        fully applied (``assert_flushed``). The lane apply runs before
        each fwd, so every step's forward pass sees bit-for-bit the
        params the unpipelined scan's would (the per-step loss stream is
        bitwise identical); the pipelined update SEQUENCE is itself
        bit-identical as a computation (tests/test_engine.py asserts
        exact zero on per-step dispatches), but embedded in a scan the
        final params can pick up ~1-ulp noise from XLA's
        context-sensitive FMA contraction of the scan body — the same
        codegen noise the scan-vs-per-step equivalence tests already
        tolerate at rtol 1e-6."""
        assert window_steps >= 1, window_steps
        plan = self._pipeline_plan(stage) if window_steps > 1 else None
        pipelined = plan is not None
        step = self._build_step_fn(stage=stage, donate=donate,
                                   fault_hook=fault_hook,
                                   pipelined=pipelined)
        sm_flush = self._build_lane_apply(stage) if pipelined else None
        guarded = self.gf_cfg.guarded

        def window(state: TrainState, batches):
            lens = {x.shape[0] for x in jax.tree_util.tree_leaves(batches)}
            assert len(lens) == 1 and next(iter(lens)) <= window_steps, (
                "stacked batch leading dims must agree and fit the "
                "window", lens, window_steps)
            if not pipelined:
                return jax.lax.scan(step, state, batches)
            state = state._replace(inflight=self._empty_inflight_global(
                plan, guarded=guarded))
            state, metrics = jax.lax.scan(step, state, batches)
            params, opt = sm_flush(state.params, state.opt,
                                   state.inflight)
            return state._replace(params=params, opt=opt,
                                  inflight=()), metrics

        return jax.jit(window, donate_argnums=(0,) if donate else ())

    def _accumulate(self, loss_fn, params_v, batch, loss_scale=None):
        """Gradient accumulation over microbatches (scan); grads in f32.
        ``loss_scale`` (guarded runs) multiplies each microbatch loss
        before autodiff; metrics stay unscaled."""
        n = self.cfg.microbatches
        split = jax.tree_util.tree_map(
            lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)
        g0 = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params_v)
        m0 = {"loss": jnp.zeros((), jnp.float32),
              "aux_loss": jnp.zeros((), jnp.float32)}

        def body(carry, mb):
            gacc, macc = carry
            (_, metrics), grads = jax.value_and_grad(
                lambda p: loss_fn_mb(p, mb), has_aux=True)(params_v)
            gacc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), gacc, grads)
            macc = jax.tree_util.tree_map(lambda a, m: a + m / n, macc,
                                          metrics)
            return (gacc, macc), None

        def loss_fn_mb(p, mb):
            with jax.named_scope("model"):
                cp = jax.tree_util.tree_map(
                    lambda x: x.astype(
                        jnp.dtype(self.cfg.model.compute_dtype)), p)
                loss, metrics = self.model.loss_fn(
                    cp, mb, rules=self.rules, remat=self.cfg.remat,
                    scan_layers=self.cfg.scan_layers,
                    attn_chunk=self.cfg.attn_chunk,
                    causal_skip=self.cfg.causal_skip,
                    compute_dtype=jnp.dtype(self.cfg.model.compute_dtype))
                if loss_scale is not None:
                    loss = loss * loss_scale
            return loss, metrics

        (grads, metrics), _ = jax.lax.scan(body, (g0, m0), split)
        grads = jax.tree_util.tree_map(lambda g: g / n, grads)
        return grads, metrics

    def abstract_train_batch(self, shape: Optional[ShapeConfig] = None):
        """Global-batch ShapeDtypeStructs (with shardings) for lowering."""
        cfg = self.cfg
        shape = shape or ShapeConfig(seq_len=cfg.seq_len,
                                     global_batch=cfg.global_batch,
                                     kind="train")
        tree = model_input_specs(cfg.model, shape, shape.global_batch)
        specs = self.batch_pspec(tree)
        return jax.tree_util.tree_map(
            lambda s, sp: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(self.mesh, sp)),
            tree, specs)

    # -- serving ------------------------------------------------------------

    def serve_rules(self, long_context: bool = False):
        r = dict(self.rules)
        r["serve_batch"] = self.data_axes if self.data_axes else None
        if r.get("kv_heads") is None and self.model_size > 1:
            # KV heads don't cover the model axis (GQA kv < TP degree):
            # shard the KV-cache *sequence* over 'model' instead — the
            # decode softmax reduces over it (GSPMD inserts the combine),
            # the flash-decoding/split-KV layout.
            r["kv_seq"] = "model"
        if long_context:
            # long_500k: B=1 — batch can't shard; shard the cache sequence
            # over 'model' unless the KV heads already cover that axis
            # (one mesh axis may shard only one cache dim).
            r["serve_batch"] = None
            if self.model_size > 1 and r.get("kv_heads") is None:
                r["kv_seq"] = "model"
        return r

    def build_serve_step(self, shape: ShapeConfig, *, mode: str,
                         kv_seq_shard: Optional[Any] = None,
                         split_combine: bool = False,
                         flash_decode: bool = False):
        """Pure-pjit serving step (no gradient machinery). Params in bf16
        (the deployment artifact)."""
        cfg = self.cfg
        long = shape.global_batch < self.num_data
        rules = self.serve_rules(long_context=long)
        if kv_seq_shard is not None:
            rules["kv_seq"] = kv_seq_shard
        if flash_decode and mode == "decode" and \
                rules.get("kv_seq") == "model":
            # flash-decoding layout: replicate attention heads so the
            # sequence-sharded KV cache is consumed shard-locally (GSPMD
            # otherwise re-shards the repeated KV by heads => all-gather
            # of the whole cache, the dominant decode collective).
            rules["heads"] = None

        def fn(params, batch, cache):
            lg, new_cache = self.model.serve_step(
                params, batch, cache, mode=mode, rules=rules,
                compute_dtype=jnp.dtype(cfg.model.compute_dtype),
                split_combine=split_combine)
            return lg, new_cache

        # Pin the OUTPUT cache to the input layout: otherwise XLA may pick
        # a different output sharding and insert a whole-cache regather.
        cache_out = jax.tree_util.tree_map(
            lambda ax: NamedSharding(self.mesh, sh.logical_spec(ax, rules)),
            self.model.cache_logical_axes(),
            is_leaf=lambda x: isinstance(x, tuple) and all(
                a is None or isinstance(a, str) for a in x))
        return jax.jit(fn, donate_argnums=(2,),
                       out_shardings=(None, cache_out)), rules

    def abstract_serve_args(self, shape: ShapeConfig, rules,
                            mode: str) -> Tuple[Any, Any, Any]:
        cfg = self.cfg
        b = shape.global_batch  # serving runs in pure pjit: global batch
        params = jax.tree_util.tree_map(
            lambda s, shd: jax.ShapeDtypeStruct(
                s.shape, jnp.bfloat16, sharding=shd),
            sh.abstract_params(self.specs, jnp.bfloat16),
            self.param_shardings)
        max_len = shape.seq_len
        if cfg.model.family == "vlm":
            # VLM prefill writes text + vision positions into the cache.
            max_len += cfg.model.num_vision_tokens
        cache = self.model.abstract_cache(b, max_len)
        cache_axes = self.model.cache_logical_axes()
        cache = jax.tree_util.tree_map(
            lambda s, ax: jax.ShapeDtypeStruct(
                s.shape, s.dtype,
                sharding=NamedSharding(self.mesh,
                                       sh.logical_spec(ax, rules))),
            cache, cache_axes)
        serve_shape = ShapeConfig(name=shape.name, seq_len=shape.seq_len,
                                  global_batch=b, kind=mode if mode !=
                                  "prefill" else "prefill")
        batch = model_input_specs(cfg.model, serve_shape, b)
        bspec = self.batch_pspec(batch)
        batch = jax.tree_util.tree_map(
            lambda s, sp: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(self.mesh, sp)),
            batch, bspec)
        return params, batch, cache
