"""Overlap engine: the per-bucket staged pipeline (paper §3.1's
computation/communication overlap, made explicit).

The paper's speedup rests on three pillars — lazy allreduce, sparse
communication, and comm/compute overlap. The first two are owned
subsystems (``lazy_allreduce``, ``csc``, the topology registry); overlap
used to be implicit: the train step was a barrier chain (pack whole pool →
reduce every bucket → update whole pool) that left XLA's latency-hiding
scheduler as the only overlap mechanism. This module makes the pipeline an
explicit IR plus an executor:

* ``StepPlan`` — the compiled step: one ``BucketTask`` per collective
  (pack slice → reduce algorithm from the topology registry) and a
  tensor-aligned partition of the pool into update spans (each span is a
  ``GradientPool.bucket_view`` — buckets close at tensor boundaries, so
  the per-bucket optimizer update reuses the whole-pool kernels on the
  view's sub-table).
* ``OverlapEngine.run`` — software-pipelined execution: bucket *i*'s
  collective is ISSUED before bucket *i-1*'s fused optimizer update is
  emitted, so the lowered module interleaves reduce_i with update_{i-1}
  instead of fencing the whole pool between them (the
  ``benchmarks/micro.py --overlap-check`` gate asserts this op order in
  the jaxpr). CSC pipelines reduce_i with *scatter*_{i-1} — chunk
  selection is dynamic, so every update span depends on every wire
  bucket, and the update side runs as its own segmented pass.
* ``simulate_plan`` / ``render_timeline`` — the analytic twin: the same
  plan priced on a ``Topology`` by the cost model's two-engine timeline
  (serial comm engine ∥ serial update engine), yielding per-bucket
  start/finish, exposed-comm seconds, and overlap efficiency — the
  numbers the θ auto-tuner and ``launch/dryrun.py --timeline`` report.

The pipelined and monolithic paths bottom out in the same per-bucket
primitives (``lazy_allreduce.reduce_bucket``, the optimizer view update),
so they are numerically equivalent by construction — the equivalence
matrix in ``tests/test_engine.py`` pins it across
{dense, lazy, csc} × {flat, pallas_ring} × device counts.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import csc as csc_mod
from repro.core import lazy_allreduce as lazy_mod
from repro.core import schedule as schedule_mod
from repro.core import wire as wire_mod
from repro.parallel import cost_model
from repro.parallel.collectives import reduce_pool


# -- the IR ------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BucketTask:
    """One collective of the step: payload span [start, end) of the wire
    buffer (the pool itself for dense/lazy; the compacted k·chunk buffer
    for CSC) plus the ReduceAlgorithm that executes it.

    ``update_span`` is the pool range whose optimizer update this task's
    result unblocks — for dense/lazy it equals the payload span (tensor
    aligned); for CSC it is None (selection is dynamic, the update side
    has its own spans in ``StepPlan.update_spans``).

    ``commit_epoch`` is the cross-step pipeline tag: 0 = the update
    commits in the same step (the default); 1 = the reduced segment is
    deferred into the scan carry (``InflightLane``) and applied at the
    START of the next step, before the forward pass touches the span's
    params. Deferred tasks are always a contiguous suffix of the plan
    (late buckets = early layers = last consumed by the next forward)."""

    index: int
    start: int
    end: int
    algo: Any                                   # topology.ReduceAlgorithm
    update_span: Optional[Tuple[int, int]] = None
    commit_epoch: int = 0

    @property
    def size(self) -> int:
        return self.end - self.start


@dataclasses.dataclass(frozen=True)
class StepPlan:
    """The compiled pipeline of one train step (static, trace-time)."""

    mode: str                                   # 'dense' | 'lazy' | 'csc'
    pool_size: int
    payload_elems: int                          # total elems on the wire
    wire_dtype: str
    reduce_axes: Tuple[str, ...]
    num_data_shards: int
    tasks: Tuple[BucketTask, ...]               # the collectives, in order
    update_spans: Tuple[Tuple[int, int], ...]   # tensor-aligned pool tiling
    warmup: bool = False                        # CSC dense warm-up stage
    num_selected: int = 0                       # CSC k (0 for dense/lazy)
    chunk_elems: int = 0
    # Cross-step pipeline depth: the last ``pipeline_tail`` tasks carry
    # commit_epoch=1 (their updates defer into the next step's prologue).
    # 0 = classic within-step plan. Only native dense/lazy plans pipeline
    # (CSC's update spans are dynamic; quantized wires would need their
    # per-chunk scales carried too).
    pipeline_tail: int = 0
    # The mesh-shape key the plan was compiled under
    # (GradientFlow.plan_cache_key()). After an elastic event the soak
    # harness asserts the active plan's key matches the NEW topology —
    # i.e. nobody kept executing a plan compiled for the retired mesh.
    plan_key: Tuple = ()

    @property
    def num_collectives(self) -> int:
        return len(self.tasks)

    @property
    def head_tasks(self) -> Tuple[BucketTask, ...]:
        return self.tasks[:len(self.tasks) - self.pipeline_tail]

    @property
    def tail_tasks(self) -> Tuple[BucketTask, ...]:
        """The deferred (commit_epoch=1) suffix, in plan order."""
        return self.tasks[len(self.tasks) - self.pipeline_tail:]

    def validate(self) -> None:
        """The partition invariants the hypothesis property pins: tasks
        tile [0, payload_elems) and update spans tile [0, pool_size),
        each exactly once, in order, with no overlap or gap. Deferred
        tasks must be exactly the ``pipeline_tail``-long suffix, and
        pipelining is only legal for native dense/lazy plans (static,
        tensor-aligned update spans; values on the wire, not codes)."""
        pos = 0
        for t in self.tasks:
            assert t.start == pos and t.end > t.start, (t, pos)
            pos = t.end
        assert pos == self.payload_elems, (pos, self.payload_elems)
        pos = 0
        for s, e in self.update_spans:
            assert s == pos and e > s, ((s, e), pos)
            pos = e
        assert pos == self.pool_size, (pos, self.pool_size)
        n = len(self.tasks)
        assert 0 <= self.pipeline_tail < max(n, 1), (self.pipeline_tail, n)
        for i, t in enumerate(self.tasks):
            want = 1 if i >= n - self.pipeline_tail else 0
            assert t.commit_epoch == want, (i, t.commit_epoch, want)
        if self.pipeline_tail:
            assert self.mode in ("dense", "lazy") and not self.warmup, self
            for t in self.tail_tasks:
                assert t.update_span == (t.start, t.end), t


def resolve_pipeline_tail(gf, tasks) -> int:
    """How many trailing buckets the cross-step pipeline defers.

    ``GradientFlowConfig.pipeline_tail_buckets``: 0 = off, N > 0 = defer
    the last min(N, buckets-1) tasks, -1 = auto — sweep every tail depth
    through ``cost_model.select_pipeline_tail`` (cross-step two-row
    timeline priced on the config's topology) and keep the steady-state
    minimum. CSC (dynamic update spans) and quantized wires (the carry
    would need per-chunk scales too) never pipeline."""
    cfg = gf.cfg
    want = cfg.pipeline_tail_buckets
    n = len(tasks)
    if want == 0 or n <= 1 or cfg.mode == "csc" or gf.wire_spec is not None:
        return 0
    if want > 0:
        return min(want, n - 1)
    assert want == -1, want
    topo = cfg.topology
    if topo is None:
        return 1
    elt = jnp.dtype(cfg.wire_dtype).itemsize
    sizes = [t.size * elt for t in tasks]
    backward_s = cost_model.ring_allreduce_time(
        sum(t.size for t in tasks) * elt, topo.num_devices,
        topo.slowest_fabric)
    comm = [t.algo.predicted_time(b, topo) for t, b in zip(tasks, sizes)]
    rel = cost_model.bucket_release_times(sizes, backward_s)
    upd = [cost_model.update_time(t.size) for t in tasks]
    return cost_model.select_pipeline_tail(comm, rel, upd, backward_s)


def _tag_tail(tasks, tail: int):
    """Stamp commit_epoch=1 on the deferred suffix."""
    if not tail:
        return tuple(tasks)
    n = len(tasks)
    return tuple(dataclasses.replace(t, commit_epoch=1)
                 if i >= n - tail else t for i, t in enumerate(tasks))


def compile_step_plan(gf, stage: Optional[schedule_mod.SparsityStage] = None,
                      ) -> StepPlan:
    """Compile GradientFlow's implicit pipeline into an explicit StepPlan.

    Reuses the bucket layouts and per-bucket algorithms GradientFlow
    resolved at build time (θ auto-tuning included), so the plan IS the
    layout the monolithic path reduces — the IR adds structure, never a
    different bucketing."""
    cfg = gf.cfg
    pool = gf.pool
    common = dict(pool_size=pool.size, wire_dtype=str(cfg.wire_dtype),
                  reduce_axes=tuple(cfg.reduce_axes),
                  num_data_shards=gf.num_data_shards,
                  plan_key=gf.plan_cache_key())

    def pool_tasks(bounds, algos):
        return tuple(BucketTask(index=i, start=s, end=e, algo=a,
                                update_span=(s, e))
                     for i, ((s, e), a) in enumerate(zip(bounds, algos)))

    if cfg.mode == "dense":
        bounds = list(gf._dense_bounds)
        if bounds and bounds[-1][1] < pool.size:
            # Per-tensor bounds stop at the last tensor; the plan must
            # tile the whole pool, so the padding tail gets its own task.
            bounds.append((bounds[-1][1], pool.size))
        elif not bounds:
            bounds = [(0, pool.size)]
        algos = gf._algos_for(tuple(bounds))
        tasks = pool_tasks(bounds, algos)
        tail = resolve_pipeline_tail(gf, tasks)
        return StepPlan(mode="dense", payload_elems=pool.size,
                        tasks=_tag_tail(tasks, tail),
                        update_spans=tuple(bounds), pipeline_tail=tail,
                        **common)

    if cfg.mode == "lazy":
        tasks = pool_tasks(gf._lazy_bounds, gf._lazy_algos)
        tail = resolve_pipeline_tail(gf, tasks)
        return StepPlan(mode="lazy", payload_elems=pool.size,
                        tasks=_tag_tail(tasks, tail),
                        update_spans=tuple(gf._lazy_bounds),
                        pipeline_tail=tail, **common)

    assert cfg.mode == "csc", cfg.mode
    stage = stage or gf.stages[-1]
    k = stage.num_selected
    if k >= gf.num_chunks:
        # Dense warm-up: the full pool goes over the wire in lazy buckets,
        # but the plan is marked so execution refreshes the norm census.
        tasks = pool_tasks(gf._lazy_bounds, gf._lazy_algos)
        return StepPlan(mode="csc", payload_elems=pool.size, tasks=tasks,
                        update_spans=tuple(gf._lazy_bounds), warmup=True,
                        num_selected=k, chunk_elems=cfg.chunk_elems,
                        **common)
    wire_bounds = csc_mod.wire_bucket_boundaries(k, cfg.chunk_elems,
                                                 gf.bucket_elems)
    algos = gf._algos_for(wire_bounds)
    tasks = tuple(BucketTask(index=i, start=s, end=e, algo=a)
                  for i, ((s, e), a) in enumerate(zip(wire_bounds, algos)))
    return StepPlan(mode="csc", payload_elems=k * cfg.chunk_elems,
                    tasks=tasks,
                    update_spans=tuple(pool.bucket_boundaries(
                        gf.bucket_elems)),
                    num_selected=k, chunk_elems=cfg.chunk_elems, **common)


# -- the executor ------------------------------------------------------------


def _seg(x: jax.Array, start: int, end: int) -> jax.Array:
    return jax.lax.slice_in_dim(x, start, end)


class InflightLane(NamedTuple):
    """The cross-step pipeline's scan-carry lane: one mean-reduced
    segment per deferred tail bucket (UNSCALED — guarded runs divide the
    loss scale out before carrying, so a scaler backoff between emit and
    apply cannot skew the carried update), plus the emitting step's
    learning rate and verdict.

    ``ok=False`` means nothing to apply: either the window prologue
    (``OverlapEngine.empty_inflight``) or a guarded step that tripped —
    its deferred buckets join the atomic skip set exactly like its head
    buckets, so a rejected step leaves params/momentum untouched at both
    commit epochs."""

    segs: Tuple[jax.Array, ...]
    lr: jax.Array                   # f32 scalar, the emitting step's lr
    ok: jax.Array                   # bool scalar


class OverlapEngine:
    """Executes a StepPlan software-pipelined inside the manual region.

    Holds the same collaborators the monolithic update path uses
    (GradientFlow, optimizer config, optional LARS scaler) and emits the
    same math — just per bucket, with bucket *i*'s collective issued
    before bucket *i-1*'s update ops.

    Compile-once loop contract: ``run`` / ``run_guarded`` are valid
    ``lax.scan`` body code — no host syncs, ``plan_for`` resolves at
    trace time (one StepPlan per stage executable), the guarded commit
    is a single traced ``lax.cond``, and the scaler state is ordinary
    carry data. ``run_guarded`` returns the HealthFlags so the scanned
    window can stack per-step verdicts into its metrics."""

    def __init__(self, gf, opt_name: str, opt_cfg, lars=None):
        self.gf = gf
        self.pool = gf.pool
        self.opt_name = opt_name
        self.opt_cfg = opt_cfg
        self.lars = lars

    def plan_for(self, stage=None) -> StepPlan:
        # Routed through GradientFlow's plan cache (keyed on the mesh
        # shape + stage), so repeated traces reuse the compiled plan and
        # an elastic replan invalidates it.
        return self.gf.plan(stage)

    def replan(self, topology=None, *, num_data_shards=None,
               reduce_axes=None) -> None:
        """Recompile the backend's layout for a new topology (delegates to
        ``GradientFlow.replan``): θ re-tuned, per-bucket algorithms
        re-selected, plan cache invalidated. The next ``plan_for`` returns
        a plan stamped with the new mesh-shape key."""
        self.gf.replan(topology, num_data_shards=num_data_shards,
                       reduce_axes=reduce_axes)

    # -- public entry point --------------------------------------------------

    def master_pool(self, params_tree):
        """The f32 master pool the update reads, packed anew from the
        params every step (scope ``gf.master_pack``)."""
        with jax.named_scope("gf.master_pack"):
            master, _ = self.pool.pack(params_tree, dtype=jnp.float32,
                                       use_kernels=self.gf.cfg.use_kernels)
        return master

    def run(self, plan: StepPlan, gpool, params_tree, opt_state,
            gfstate, lr, census=None):
        """One pipelined reduce+update phase. ``gpool`` is the local
        gradient pool, already packed (wire dtype for dense/lazy, f32 for
        CSC and the quantized wire formats); ``gfstate`` the LOCAL
        GradientFlow state (hg as a flat [pool] row, as inside the manual
        region). ``census`` is the per-rank chunk-L1 census the pack
        pipeline already emitted for ``gpool`` (quantized formats only;
        recomputed here when None — one extra pool pass). Returns
        (new_params_tree, new_opt_state, new_gfstate)."""
        cfg = self.gf.cfg
        prepacked = cfg.mode in ("dense", "lazy")
        master = self.master_pool(params_tree)
        if cfg.mode == "csc" and not plan.warmup:
            return self._run_csc(plan, gpool, master, opt_state, gfstate,
                                 lr)
        if cfg.mode == "csc":
            return self._run_csc_warmup(plan, gpool, master, opt_state,
                                        gfstate, lr)
        if self.gf.wire_spec is not None:
            return self._run_quantized_pool(plan, gpool, master, opt_state,
                                            gfstate, lr, census)
        new_params, opt2 = self._run_pool_pipeline(
            plan, gpool, master, opt_state, lr, prepacked=prepacked,
            mask=None)
        return new_params, opt2, gfstate

    def run_guarded(self, plan: StepPlan, gpool, params_tree, opt_state,
                    gfstate, scaler_state, lr, census=None):
        """Guard-railed twin of ``run``: the same collectives, in the same
        order, plus the census-derived health verdict and ONE atomic
        commit. Every bucket's reduce is issued first (they still overlap
        each other and the backward release schedule); the combined
        per-bucket health words then gate the whole update stage through a
        single atomic verdict (a ``where``-select for native dense/lazy, a
        ``lax.cond`` for csc/quantized) — so no bucket's update can commit
        when any other bucket (earlier OR later) trips, and a rejected step
        leaves
        params, momentum, and the CSC hg residual bit-identical while only
        the scaler state advances.

        ``gpool`` arrives scaled by ``scaler_state.scale`` (the fwd region
        scaled the loss): dense/lazy keep the scaled values on the wire
        (that is the point — small gradients survive the bf16 cast) and
        unscale the reduced mean before the update; CSC unscales at entry
        so the hg residual stays scale-invariant across backoffs.

        Returns (new_params_tree, new_opt_state, new_gfstate,
        new_scaler_state, HealthFlags)."""
        from repro.core import guard as guard_mod
        from repro.optim import scaler as scaler_mod

        cfg = self.gf.cfg
        gcfg = cfg.guard
        assert gcfg is not None, "run_guarded needs GradientFlowConfig.guard"
        limit = guard_mod.overflow_limit(gcfg, cfg.wire_dtype)
        master = self.master_pool(params_tree)
        if cfg.mode == "csc" and not plan.warmup:
            out = self._guarded_csc(plan, gpool, master, params_tree,
                                    opt_state, gfstate, scaler_state, lr,
                                    limit)
        elif cfg.mode == "csc":
            out = self._guarded_csc_warmup(plan, gpool, master, params_tree,
                                           opt_state, gfstate, scaler_state,
                                           lr, limit)
        elif self.gf.wire_spec is not None:
            out = self._guarded_quantized_pool(plan, gpool, master,
                                               params_tree, opt_state,
                                               gfstate, scaler_state, lr,
                                               limit, census)
        else:
            out = self._guarded_pool(plan, gpool, master, params_tree,
                                     opt_state, gfstate, scaler_state, lr,
                                     limit)
        new_params, opt2, gf2, flags = out
        new_scaler = scaler_mod.update(scaler_state,
                                       ~guard_mod.tripped(flags), gcfg)
        return new_params, opt2, gf2, new_scaler, flags

    def _guarded_pool(self, plan, gpool, master, params_tree, opt_state,
                      gfstate, scaler_state, lr, limit):
        """Dense/lazy guarded stage: reduce every bucket (the pool is
        prepacked in the wire dtype, scaled), derive each bucket's in-band
        health word from its reduced segment — the allreduce already mixed
        every shard, so the verdict is globally consistent with zero extra
        collectives — then commit or skip the whole update sweep.

        The skip gate is a ``where``-select over the computed update, not
        a ``lax.cond``: XLA codegens an elementwise chain differently
        inside a cond branch than in the main computation (different FMA
        contraction), and the cross-step pipeline's bit-identity guarantee
        needs ``run_guarded`` / ``run_pipelined_guarded`` / the lane apply
        to emit each span's update with the SAME codegen context. A
        rejected step still returns the pre-step values bit-identically
        (the select takes the old operand wholesale — NaNs in the
        discarded update never propagate)."""
        from repro.core import guard as guard_mod

        segs = []
        for task in plan.tasks:
            segs.append(lazy_mod.reduce_bucket(
                gpool, task.start, task.end, plan.reduce_axes, None,
                algo=task.algo) / plan.num_data_shards)
        flags = guard_mod.flags_from_words(
            [guard_mod.health_word(s) for s in segs], limit)
        ok = ~guard_mod.tripped(flags)
        scale = scaler_state.scale
        outs = [self._update_span(t.update_span, segs[t.index] / scale,
                                  master, opt_state, lr, None)
                for t in plan.tasks]
        new_params, opt2 = jax.lax.optimization_barrier(
            self._assemble(outs))
        pick = lambda new, old: jnp.where(ok, new, old)
        with jax.named_scope("gf.update"):
            new_params = jax.tree_util.tree_map(pick, new_params,
                                                params_tree)
            opt2 = jax.tree_util.tree_map(pick, opt2, opt_state)
        return new_params, opt2, gfstate, flags

    # -- quantized wire formats (int8 / fp8) ----------------------------------

    def _quantize_wire(self, gpool, gfstate, reduce_axes, census,
                       loss_scale):
        """Quantize the f32 pool for scaled-domain transport (the staged
        twin of ``GradientFlow._quantized_dense_or_lazy``'s front half):
        census psum → rank-invariant per-chunk scales → one pool-pass
        quantize with error feedback. ``loss_scale`` (guarded runs) is the
        scaler's power-of-two scale already riding on ``gpool``: the
        residual is stored UNSCALED (err / scale on write, r * scale on
        read), so scaler backoffs never corrupt carried feedback. Returns
        (q, scales, census_sum, residual)."""
        gf = self.gf
        cfg = gf.cfg
        chunk = cfg.chunk_elems
        g = gpool.astype(jnp.float32)
        if cfg.feedback_enabled:
            r = gfstate.residual if loss_scale is None \
                else gfstate.residual * loss_scale
            g = g + r
        if census is None:
            census = wire_mod.chunk_l1(gpool.astype(jnp.float32), chunk)
        census_sum = reduce_pool(census, reduce_axes)
        scales = gf.quantized_scales(census_sum)
        q, err = wire_mod.quantize_pool(g, scales, chunk_elems=chunk,
                                        spec=gf.wire_spec,
                                        num_shards=gf.num_data_shards)
        if cfg.feedback_enabled:
            residual = err if loss_scale is None else err / loss_scale
        else:
            residual = gfstate.residual
        return q, scales, census_sum, residual

    def _run_quantized_pool(self, plan, gpool, master, opt_state, gfstate,
                            lr, census):
        """Dense/lazy pipeline on a low-bit wire: quantize the whole pool
        once (scales from the census psum), run the staged loop in the
        scaled domain (wire_dtype=None — the int8/fp8 words ARE the wire),
        and dequantize each bucket's mean segment as it retires."""
        cfg = self.gf.cfg
        chunk = cfg.chunk_elems
        q, scales, _, residual = self._quantize_wire(
            gpool, gfstate, plan.reduce_axes, census, None)

        def dequant(red, task):
            return wire_mod.dequantize_segment(red, scales, task.start,
                                               task.end, chunk)

        new_params, opt2 = self._run_pool_pipeline(
            plan, q, master, opt_state, lr, prepacked=True, mask=None,
            xform=dequant)
        return new_params, opt2, gfstate._replace(residual=residual)

    def _guarded_quantized_pool(self, plan, gpool, master, params_tree,
                                opt_state, gfstate, scaler_state, lr,
                                limit, census):
        """Guarded twin of ``_run_quantized_pool``. Low-bit wires saturate
        at the grid clip instead of overflowing to Inf, so the reduced
        payload can never carry the poison in-band — the health channel is
        the census psum itself (any rank's NaN/Inf taints its chunk's L1;
        the psum the scales already need makes the verdict global, still
        zero extra collectives). The error-feedback residual joins
        params/momentum in the atomic skip set: a rejected step keeps the
        pre-step residual bit-identically."""
        from repro.core import guard as guard_mod

        cfg = self.gf.cfg
        chunk = cfg.chunk_elems
        scale = scaler_state.scale
        q, scales, census_sum, residual = self._quantize_wire(
            gpool, gfstate, plan.reduce_axes, census, scale)
        flags = guard_mod.flags_from_census(census_sum, limit)
        segs = []
        for task in plan.tasks:
            segs.append(lazy_mod.reduce_bucket(
                q, task.start, task.end, plan.reduce_axes, None,
                algo=task.algo) / plan.num_data_shards)

        def commit():
            outs = []
            for t in plan.tasks:
                red = wire_mod.dequantize_segment(
                    segs[t.index], scales, t.start, t.end, chunk) / scale
                outs.append(self._update_span(t.update_span, red, master,
                                              opt_state, lr, None))
            new_params, opt2 = self._assemble(outs)
            return new_params, opt2, gfstate._replace(residual=residual)

        new_params, opt2, gf2 = guard_mod.guarded_commit(
            ~guard_mod.tripped(flags), commit,
            (params_tree, opt_state, gfstate))
        return new_params, opt2, gf2, flags

    def _guarded_csc(self, plan, gpool, master, params_tree, opt_state,
                     gfstate, scaler_state, lr, limit):
        """Sparse CSC guarded stage: same reduce_i ∥ scatter_{i-1}
        pipeline and the same two census collectives as ``_run_csc``; the
        chunk-selection census doubles as the health channel (NaN/Inf
        anywhere in the post-reduce pool — wire-reduced chunks and the
        locally-kept hg side alike — taints its chunk's allreduced L1).
        On a trip the hg residual and the norm census keep their pre-step
        values, so Algorithm 1 conservation holds across the skip.

        Quantized wire formats: the compacted buffer travels int8/fp8
        (scales from the previous iteration's census, exactly as
        ``_run_csc``), the error-feedback residual joins the atomic skip
        set, and the overflow limit becomes PER-CHUNK
        (``guard.per_chunk_limit``): a chunk whose fresh census jumps far
        past its scale basis is mass-saturating the wire grid — a
        condition the saturating int8 clip never surfaces as Inf."""
        from repro.core import guard as guard_mod
        from repro.core.gradientflow import GFState

        cfg = self.gf.cfg
        spec = self.gf.wire_spec
        feedback = cfg.feedback_enabled
        chunk = plan.chunk_elems
        g = gpool.astype(jnp.float32) / scaler_state.scale + gfstate.hg
        idx, chunk_mask = csc_mod.select_chunks(gfstate.chunk_norms,
                                                plan.num_selected)
        elem_mask = jnp.repeat(chunk_mask, chunk)
        # CSC runs unscaled past entry, so the (unscaled) residual adds
        # directly to the send values of the selected chunks.
        g_send = g + gfstate.residual if (spec is not None and feedback) \
            else g
        wire = csc_mod.compact_chunks(g_send, idx, chunk,
                                      use_kernels=cfg.use_kernels)
        scales = None
        send_l1 = None
        residual_new = gfstate.residual
        wire_dt = cfg.wire_dtype
        if spec is not None:
            scales = wire_mod.scales_from_census(
                jnp.take(gfstate.chunk_norms, idx), chunk_elems=chunk,
                num_shards=plan.num_data_shards, spec=spec)
            # Pre-quant send census — the only place NaN and the 512x
            # saturation jump still exist on an int8 wire (the round/clip
            # eats both); it feeds the selected chunks of norms_new below.
            send_l1 = csc_mod.chunk_l1_norms(wire, chunk)
            wire, err = wire_mod.quantize_pool(
                wire, scales, chunk_elems=chunk, spec=spec,
                num_shards=plan.num_data_shards)
            if feedback:
                residual_new = csc_mod.scatter_chunks(gfstate.residual,
                                                      idx, err, chunk)
            wire_dt = None  # already wire-packed (scaled domain)
            limit = guard_mod.per_chunk_limit(gfstate.chunk_norms,
                                              cfg.guard, limit)

        g_out, g_update = g, jnp.zeros(g.shape, g.dtype)
        pending = None
        for task in plan.tasks:
            red = lazy_mod.reduce_bucket(
                wire, task.start, task.end, plan.reduce_axes,
                wire_dt, algo=task.algo) / plan.num_data_shards
            if spec is not None:
                red = wire_mod.dequantize_segment(red, scales, task.start,
                                                  task.end, chunk)
            if pending is not None:
                g_out, g_update = self._scatter_task(
                    g_out, g_update, pending[0], pending[1], idx, chunk)
            pending = (task, red)
        g_out, g_update = self._scatter_task(g_out, g_update, pending[0],
                                             pending[1], idx, chunk)

        hg_new = jnp.where(elem_mask, 0.0,
                           cfg.momentum * g_out).astype(gfstate.hg.dtype)
        l1 = csc_mod.chunk_l1_norms(g_out, chunk,
                                    use_kernels=cfg.use_kernels)
        if send_l1 is not None:
            l1 = l1.at[idx].set(send_l1)
        norms_new = reduce_pool(l1, plan.reduce_axes)
        flags = guard_mod.flags_from_census(norms_new, limit)

        def commit():
            outs = [self._update_span(span, _seg(g_update, *span), master,
                                      opt_state, lr, elem_mask)
                    for span in plan.update_spans]
            new_params, opt2 = self._assemble(outs)
            return new_params, opt2, GFState(hg=hg_new,
                                             chunk_norms=norms_new,
                                             residual=residual_new)

        new_params, opt2, gf2 = guard_mod.guarded_commit(
            ~guard_mod.tripped(flags), commit,
            (params_tree, opt_state, gfstate))
        return new_params, opt2, gf2, flags

    def _guarded_csc_warmup(self, plan, gpool, master, params_tree,
                            opt_state, gfstate, scaler_state, lr, limit):
        """CSC dense warm-up, guarded: lazy-bucket reduces of the
        hg-corrected (unscaled) pool, the norm-census refresh as the
        health channel, one atomic commit of update + census + hg."""
        from repro.core import guard as guard_mod
        from repro.core.gradientflow import GFState
        from repro.parallel.sharding import match_vma

        cfg = self.gf.cfg
        g = gpool.astype(jnp.float32) / scaler_state.scale + gfstate.hg
        segs = []
        for task in plan.tasks:
            segs.append(lazy_mod.reduce_bucket(
                g, task.start, task.end, plan.reduce_axes, cfg.wire_dtype,
                algo=task.algo) / plan.num_data_shards)
        mean = segs[0] if len(segs) == 1 else jnp.concatenate(segs)
        l1 = csc_mod.chunk_l1_norms(mean, cfg.chunk_elems)
        norms = reduce_pool(l1, plan.reduce_axes)
        flags = guard_mod.flags_from_census(norms, limit)
        hg_new = match_vma(jnp.zeros_like(gfstate.hg), gpool)

        def commit():
            outs = [self._update_span(t.update_span, segs[t.index], master,
                                      opt_state, lr, None)
                    for t in plan.tasks]
            new_params, opt2 = self._assemble(outs)
            return new_params, opt2, GFState(hg=hg_new, chunk_norms=norms,
                                             residual=gfstate.residual)

        new_params, opt2, gf2 = guard_mod.guarded_commit(
            ~guard_mod.tripped(flags), commit,
            (params_tree, opt_state, gfstate))
        return new_params, opt2, gf2, flags

    # -- dense / lazy ---------------------------------------------------------

    def _run_pool_pipeline(self, plan, gpool, master, opt_state, lr, *,
                           prepacked: bool, mask,
                           reduced_segs: Optional[list] = None,
                           xform=None):
        """The staged loop over pool-space tasks: issue reduce_i, then
        emit update_{i-1} while it is in flight. ``mask`` is an optional
        pool-sized element mask (CSC); ``reduced_segs`` (when given) is
        filled with each task's mean segment for callers that need the
        whole reduced pool afterwards (the warm-up norm census);
        ``xform(red, task)`` (when given) post-processes each mean
        segment before its update — the quantized path's per-bucket
        dequantization."""
        cfg = self.gf.cfg
        wire = None if prepacked else cfg.wire_dtype
        outs: List[Any] = [None] * len(plan.tasks)
        pending = None
        for task in plan.tasks:
            red = lazy_mod.reduce_bucket(
                gpool, task.start, task.end, plan.reduce_axes, wire,
                algo=task.algo) / plan.num_data_shards
            if xform is not None:
                red = xform(red, task)
            if reduced_segs is not None:
                reduced_segs.append(red)
            if pending is not None:
                pt, pr = pending
                outs[pt.index] = self._update_span(
                    pt.update_span, pr, master, opt_state, lr, mask)
            pending = (task, red)
        pt, pr = pending
        outs[pt.index] = self._update_span(pt.update_span, pr, master,
                                           opt_state, lr, mask)
        return self._assemble(outs)

    # -- CSC ------------------------------------------------------------------

    def _run_csc(self, plan, gpool, master, opt_state, gfstate, lr):
        """Sparse CSC stage: pipeline reduce_i ∥ scatter_{i-1} over the
        compacted wire buffer, then the segmented masked update. Same math
        as ``csc.csc_reduce`` + the monolithic update — Algorithm 1 with
        the collectives and scatters interleaved. Quantized wire formats
        transport the compacted buffer in int8/fp8 with per-chunk scales
        from the PREVIOUS iteration's allreduced census (zero extra
        collectives) and error feedback at the selected chunks."""
        cfg = self.gf.cfg
        spec = self.gf.wire_spec
        feedback = cfg.feedback_enabled
        chunk = plan.chunk_elems
        g = gpool.astype(jnp.float32) + gfstate.hg
        idx, chunk_mask = csc_mod.select_chunks(gfstate.chunk_norms,
                                                plan.num_selected)
        elem_mask = jnp.repeat(chunk_mask, chunk)
        g_send = g + gfstate.residual if (spec is not None and feedback) \
            else g
        wire = csc_mod.compact_chunks(g_send, idx, chunk,
                                      use_kernels=cfg.use_kernels)
        scales = None
        residual_new = gfstate.residual
        wire_dt = cfg.wire_dtype
        send_l1 = None
        if spec is not None:
            scales = wire_mod.scales_from_census(
                jnp.take(gfstate.chunk_norms, idx), chunk_elems=chunk,
                num_shards=plan.num_data_shards, spec=spec)
            # Pre-quant send census: the health/scale-basis source for the
            # selected chunks (csc.csc_reduce documents why post-dequant
            # norms cannot carry NaN or the saturation jump on int8).
            send_l1 = csc_mod.chunk_l1_norms(wire, chunk)
            wire, err = wire_mod.quantize_pool(
                wire, scales, chunk_elems=chunk, spec=spec,
                num_shards=plan.num_data_shards)
            if feedback:
                residual_new = csc_mod.scatter_chunks(gfstate.residual,
                                                      idx, err, chunk)
            wire_dt = None  # already wire-packed (scaled domain)

        g_out, g_update = g, jnp.zeros(g.shape, g.dtype)
        pending = None
        for task in plan.tasks:
            red = lazy_mod.reduce_bucket(
                wire, task.start, task.end, plan.reduce_axes,
                wire_dt, algo=task.algo) / plan.num_data_shards
            if spec is not None:
                red = wire_mod.dequantize_segment(red, scales, task.start,
                                                  task.end, chunk)
            if pending is not None:
                g_out, g_update = self._scatter_task(
                    g_out, g_update, pending[0], pending[1], idx, chunk)
            pending = (task, red)
        g_out, g_update = self._scatter_task(g_out, g_update, pending[0],
                                             pending[1], idx, chunk)

        # Algorithm 1 lines 8-11 + the Fig 18 census; both collectives are
        # issued BEFORE the update spans so they overlap the update sweep.
        hg_new = jnp.where(elem_mask, 0.0,
                           cfg.momentum * g_out).astype(gfstate.hg.dtype)
        l1 = csc_mod.chunk_l1_norms(g_out, chunk,
                                    use_kernels=cfg.use_kernels)
        if send_l1 is not None:
            l1 = l1.at[idx].set(send_l1)
        norms_new = reduce_pool(l1, plan.reduce_axes)

        outs = [self._update_span(span, _seg(g_update, *span), master,
                                  opt_state, lr, elem_mask)
                for span in plan.update_spans]
        new_params, opt2 = self._assemble(outs)
        from repro.core.gradientflow import GFState
        return new_params, opt2, GFState(hg=hg_new, chunk_norms=norms_new,
                                         residual=residual_new)

    @staticmethod
    def _scatter_task(g_out, g_update, task, red, idx, chunk):
        """Retire one wire bucket: write its reduced chunks back into the
        post-reduce view and the update-ready view (the per-bucket form of
        ``csc.scatter_chunks`` — compacted positions [start, end) map to
        the sorted chunk ids idx[start/chunk : end/chunk))."""
        ids = jax.lax.slice_in_dim(idx, task.start // chunk,
                                   task.end // chunk)
        vals = red.reshape((-1, chunk))
        g_out = g_out.reshape((-1, chunk)).at[ids].set(vals).reshape(-1)
        g_update = g_update.reshape((-1, chunk)).at[ids].set(
            vals).reshape(-1)
        return g_out, g_update

    def _run_csc_warmup(self, plan, gpool, master, opt_state, gfstate, lr):
        """CSC's dense warm-up stage, staged: the hg-corrected f32 pool is
        reduced in lazy buckets pipelined against the update, and the norm
        census runs on the reassembled mean pool (it must keep tracking
        norms for the sparse handoff — ``GradientFlow.
        _dense_or_lazy_with_norms`` is the monolithic twin)."""
        from repro.core.gradientflow import GFState
        from repro.parallel.sharding import match_vma

        cfg = self.gf.cfg
        g = gpool.astype(jnp.float32) + gfstate.hg
        segs: List[jax.Array] = []
        new_params, opt2 = self._run_pool_pipeline(
            plan, g, master, opt_state, lr, prepacked=False, mask=None,
            reduced_segs=segs)
        mean = segs[0] if len(segs) == 1 else jnp.concatenate(segs)
        l1 = csc_mod.chunk_l1_norms(mean, cfg.chunk_elems)
        norms = reduce_pool(l1, plan.reduce_axes)
        hg_new = match_vma(jnp.zeros_like(gfstate.hg), gpool)
        return new_params, opt2, GFState(hg=hg_new, chunk_norms=norms,
                                         residual=gfstate.residual)

    # -- the per-bucket update -------------------------------------------------

    def _update_span(self, span, red_seg, master, opt_state, lr, mask):
        """Emit one update span's fused optimizer step: slice the master /
        optimizer-state pools to the span, compute LARS ratios for the
        span's tensors (tensors never cross buckets, so per-tensor norms
        are complete), and run the segment update through the same
        kernels as the whole-pool path (the streaming TilePlan restricted
        to the bucket span). Returns (leaves, new_state_seg)."""
        start, end = span
        view = self.pool.bucket_view(start, end)
        with jax.named_scope("gf.update"):
            m_seg = _seg(master, start, end)
            st_seg = jax.tree_util.tree_map(lambda a: _seg(a, start, end),
                                            opt_state)
            mask_seg = jnp.ones((view.size,), jnp.bool_) if mask is None \
                else _seg(mask, start, end)
            return self._update_view_seg(view, m_seg, red_seg, st_seg, lr,
                                         mask_seg)

    def _update_view_seg(self, view, m_seg, red_seg, st_seg, lr, mask_seg):
        """The span update on pre-sliced segments — shared by the in-step
        path (``_update_span``), the guarded commit branch, and the
        cross-step lane apply. ``optimization_barrier`` fences both sides
        so the update math is an isolated fusion island with identical
        ops in every calling context: XLA's FMA-contraction decisions
        depend on what an elementwise chain fuses with (a ``lax.cond``
        branch fuses differently from the main computation), and the
        cross-step pipeline's bit-identity guarantee needs the SAME bits
        whether a span commits in-step, inside a guarded commit, or one
        step later from the carry."""
        from repro import optim

        cfg = self.gf.cfg
        scale = ratios = None
        if self.lars is not None:
            r = self.lars.ratios_view(view, m_seg, red_seg, self.opt_cfg,
                                      mask_seg)
            if cfg.use_kernels:
                ratios = r
            else:
                from repro.kernels import ref
                scale = ref.expand_ratios(r, view.sizes, view.size)
        m_seg, red_seg, st_seg, mask_seg, lr, scale, ratios = \
            jax.lax.optimization_barrier(
                (m_seg, red_seg, st_seg, mask_seg,
                 jnp.asarray(lr, jnp.float32), scale, ratios))
        leaves, st2 = optim.update_view(
            self.opt_name, view, m_seg, red_seg, st_seg, mask_seg,
            self.opt_cfg, lr, scale=scale, ratios=ratios,
            use_kernels=cfg.use_kernels)
        return jax.lax.optimization_barrier((leaves, st2))

    def _assemble(self, outs):
        """Stitch the per-span outputs back together: leaves concatenate
        across spans into the full segment-table order (then unflatten to
        the parameter pytree); optimizer-state segments concatenate back
        into pool form."""
        all_leaves = [leaf for leaves, _ in outs for leaf in leaves]
        assert len(all_leaves) == self.pool.num_tensors, (
            len(all_leaves), self.pool.num_tensors)
        with jax.named_scope("gf.update"):
            new_params = self.pool.unflatten(all_leaves)
            states = [st for _, st in outs]
            if len(states) == 1:
                opt2 = states[0]
            else:
                opt2 = jax.tree_util.tree_map(
                    lambda *segs: jnp.concatenate(segs), *states)
        return new_params, opt2

    # -- cross-step pipelining (the deferred-tail lane) -----------------------

    def lane_dtype(self, *, guarded: bool):
        """Carry dtype of the lane segments. Unguarded native plans carry
        the reduced mean exactly as the wire delivered it; guarded runs
        divide the f32 loss scale out at emit time, which promotes to
        f32 — the same value ``run_guarded``'s commit would have used."""
        return jnp.float32 if guarded \
            else jnp.dtype(self.gf.cfg.wire_dtype)

    def empty_inflight(self, plan: StepPlan, *,
                       guarded: bool = False) -> InflightLane:
        """The window-prologue lane: zero segments, ok=False (nothing to
        apply). Shape/dtype-stable with every lane ``run_pipelined``
        emits, so it can seed the scan carry."""
        dt = self.lane_dtype(guarded=guarded)
        segs = tuple(jnp.zeros((t.size,), dt) for t in plan.tail_tasks)
        return InflightLane(segs=segs, lr=jnp.zeros((), jnp.float32),
                            ok=jnp.zeros((), jnp.bool_))

    def _apply_lane_tree(self, plan, params_tree, opt_state, lane):
        """The lane apply itself (ungated): each deferred span's update,
        in pool (= fwd consumption) order, from the carried segments and
        the emitting step's lr. Bit-for-bit the update the unpipelined
        loop emitted in-step — same segments, same all-true mask, the
        master slice rebuilt from params exactly as ``pool.pack`` lays it
        out (zero-filled padding included, so momentum over the padding
        advances identically)."""
        leaves = self.pool.flat_leaves(params_tree)
        new_leaves = list(leaves)
        opt2 = opt_state
        for task, red in zip(plan.tail_tasks, lane.segs):
            start, end = task.update_span
            view = self.pool.bucket_view(start, end)
            parts = [new_leaves[j].astype(jnp.float32)
                     for j in range(view.leaf_lo, view.leaf_hi)]
            if view.padding:
                parts.append(jnp.zeros((view.padding,), jnp.float32))
            m_seg = parts[0] if len(parts) == 1 \
                else jnp.concatenate(parts)
            st_seg = jax.tree_util.tree_map(
                lambda a: _seg(a, start, end), opt_state)
            out_leaves, st2 = self._update_view_seg(
                view, m_seg, red, st_seg, lane.lr,
                jnp.ones((view.size,), jnp.bool_))
            for k, nl in enumerate(out_leaves):
                new_leaves[view.leaf_lo + k] = nl
            opt2 = jax.tree_util.tree_map(
                lambda full, s: jax.lax.dynamic_update_slice(
                    full, s.astype(full.dtype), (start,)), opt2, st2)
        return self.pool.unflatten(new_leaves), opt2

    def apply_inflight(self, plan: StepPlan, params_tree, opt_state,
                       lane: InflightLane):
        """Apply the PREVIOUS step's carried tail-bucket updates before
        this step's forward pass touches those spans; a prologue or
        rejected lane (``ok=False``) applies nothing.

        The gate is an ``optimization_barrier`` + ``where``-select, NOT a
        ``lax.cond``: XLA contracts mul+add into FMA differently inside a
        cond branch than in the main computation, and bit-identity with
        the unpipelined loop requires the lane's update math to codegen in
        the same (main-computation) context every baseline emits it in —
        ``run`` directly, ``run_guarded`` via ``_guarded_pool``'s own
        where-select. The update is computed unconditionally and the
        select takes old values wholesale on a dead lane, so a rejected
        emitter's segments can never perturb params."""
        if not plan.pipeline_tail:
            return params_tree, opt_state
        with jax.named_scope("gf.apply_inflight"):
            new_params, opt2 = jax.lax.optimization_barrier(
                self._apply_lane_tree(plan, params_tree, opt_state, lane))
            pick = lambda new, old: jnp.where(lane.ok, new, old)
            return (jax.tree_util.tree_map(pick, new_params, params_tree),
                    jax.tree_util.tree_map(pick, opt2, opt_state))

    def _identity_span(self, span, master, opt_state):
        """The no-op twin of ``_update_span``: the span's current master
        leaves (cast back to their spec dtype — exact for f32 and for
        any dtype that round-trips through f32) and its optimizer-state
        slice, unchanged. What a deferred task contributes to THIS
        step's assembly."""
        start, end = span
        view = self.pool.bucket_view(start, end)
        with jax.named_scope("gf.update"):
            leaves = [_seg(master, start + o, start + o + s).astype(
                spec.dtype) for spec, o, s in zip(view.specs, view.offsets,
                                                  view.sizes)]
            st_seg = jax.tree_util.tree_map(lambda a: _seg(a, start, end),
                                            opt_state)
        return leaves, st_seg

    def _pipelined_pool_stage(self, plan, gpool, master, opt_state, lr):
        """Staged loop with a deferred suffix: head tasks run the usual
        reduce_i ∥ update_{i-1} pipeline; tail tasks still reduce (their
        collectives overlap the release schedule exactly as before) but
        contribute identity spans and park their mean segments in the
        returned lane."""
        outs: List[Any] = [None] * len(plan.tasks)
        pending = None
        tail_segs = []
        for task in plan.tasks:
            red = lazy_mod.reduce_bucket(
                gpool, task.start, task.end, plan.reduce_axes, None,
                algo=task.algo) / plan.num_data_shards
            if pending is not None:
                pt, pr = pending
                outs[pt.index] = self._update_span(
                    pt.update_span, pr, master, opt_state, lr, None)
                pending = None
            if task.commit_epoch:
                tail_segs.append(red)
                outs[task.index] = self._identity_span(
                    task.update_span, master, opt_state)
            else:
                pending = (task, red)
        if pending is not None:
            pt, pr = pending
            outs[pt.index] = self._update_span(pt.update_span, pr, master,
                                               opt_state, lr, None)
        lane = InflightLane(segs=tuple(tail_segs),
                            lr=jnp.asarray(lr, jnp.float32),
                            ok=jnp.ones((), jnp.bool_))
        return outs, lane

    def run_pipelined(self, plan: StepPlan, gpool, params_tree, opt_state,
                      gfstate, lr, census=None):
        """Pipelined twin of ``run`` for plans with a deferred tail:
        commits head buckets in-step (same staged loop) and returns the
        tail buckets' reduced segments in an ``InflightLane`` instead of
        applying them. The caller owns applying the lane at the start of
        the NEXT step (``apply_inflight``) and flushing it at window
        edges. Native dense/lazy only. Returns (new_params_tree,
        new_opt_state, new_gfstate, lane)."""
        cfg = self.gf.cfg
        assert plan.pipeline_tail and cfg.mode in ("dense", "lazy") \
            and self.gf.wire_spec is None, plan
        master = self.master_pool(params_tree)
        outs, lane = self._pipelined_pool_stage(plan, gpool, master,
                                                opt_state, lr)
        new_params, opt2 = self._assemble(outs)
        return new_params, opt2, gfstate, lane

    def run_pipelined_guarded(self, plan: StepPlan, gpool, params_tree,
                              opt_state, gfstate, scaler_state, lr,
                              census=None):
        """Guarded twin of ``run_pipelined``. The verdict covers EVERY
        bucket's reduced segment — deferred ones included — and gates
        both commit epochs: head updates go through the same atomic
        ``where``-select as ``_guarded_pool`` (identical codegen context,
        so head spans are bit-for-bit the unpipelined guarded commit) and
        the lane is emitted with ``ok = verdict``, so a tripped step's
        carried segments are rejected by the next step's
        ``apply_inflight`` select. The carried segments divide the loss
        scale out at emit time — exact, the scaler scale is a power of
        two — so a backoff between emit and apply cannot skew them. The
        scaler advances exactly as in ``run_guarded``. Returns
        (new_params_tree, new_opt_state, new_gfstate, new_scaler_state,
        lane, HealthFlags)."""
        from repro.core import guard as guard_mod
        from repro.optim import scaler as scaler_mod

        cfg = self.gf.cfg
        gcfg = cfg.guard
        assert gcfg is not None, \
            "run_pipelined_guarded needs GradientFlowConfig.guard"
        assert plan.pipeline_tail and cfg.mode in ("dense", "lazy") \
            and self.gf.wire_spec is None, plan
        limit = guard_mod.overflow_limit(gcfg, cfg.wire_dtype)
        master = self.master_pool(params_tree)
        segs = []
        for task in plan.tasks:
            segs.append(lazy_mod.reduce_bucket(
                gpool, task.start, task.end, plan.reduce_axes, None,
                algo=task.algo) / plan.num_data_shards)
        flags = guard_mod.flags_from_words(
            [guard_mod.health_word(s) for s in segs], limit)
        ok = ~guard_mod.tripped(flags)
        scale = scaler_state.scale
        outs = [self._identity_span(t.update_span, master, opt_state)
                if t.commit_epoch else
                self._update_span(t.update_span, segs[t.index] / scale,
                                  master, opt_state, lr, None)
                for t in plan.tasks]
        new_params, opt2 = jax.lax.optimization_barrier(
            self._assemble(outs))
        pick = lambda new, old: jnp.where(ok, new, old)
        with jax.named_scope("gf.update"):
            new_params = jax.tree_util.tree_map(pick, new_params,
                                                params_tree)
            opt2 = jax.tree_util.tree_map(pick, opt2, opt_state)
        lane = InflightLane(
            segs=tuple(segs[t.index] / scale for t in plan.tail_tasks),
            lr=jnp.asarray(lr, jnp.float32), ok=ok)
        new_scaler = scaler_mod.update(scaler_state, ok, gcfg)
        return new_params, opt2, gfstate, new_scaler, lane, flags

    # -- segment-carry pipelined entry points (the zero-copy window form) -----

    def pool_split(self, plan: StepPlan, master, opt_state):
        """Window-entry for the segment-carry form: the resident f32
        master and optimizer pools sliced into per-task segments. The
        scan then carries the tuples instead of the pools, so a step
        never writes (or copies) anything bigger than the spans it
        actually updates — no dynamic-update-slice chain for XLA to
        materialize full-pool copies around."""
        spans = [t.update_span for t in plan.tasks]
        m_segs = tuple(_seg(master, s, e) for s, e in spans)
        st_segs = tuple(
            jax.tree_util.tree_map(lambda a: _seg(a, s, e), opt_state)
            for s, e in spans)
        return m_segs, st_segs

    def pool_join(self, plan: StepPlan, m_segs, st_segs):
        """Window-edge inverse of ``pool_split``: task spans tile the
        pool in order, so one concatenation per pool rebuilds the
        master/optimizer state for checkpoints, replan, and the
        unflatten back to tree form."""
        master = m_segs[0] if len(m_segs) == 1 \
            else jnp.concatenate(m_segs)
        opt = st_segs[0] if len(st_segs) == 1 \
            else jax.tree_util.tree_map(
                lambda *segs: jnp.concatenate(segs), *st_segs)
        return master, opt

    def _seg_update(self, task, m_seg, st_seg, red, lr, ok):
        """One task's updated segment pair, in segment space. ``ok``
        (when given) gates with a span-sized ``where``-select — old
        bytes pass through wholesale on a dead/rejected lane. Bucket
        padding passes through from the old segment (the master's
        padding is pinned at pack-time zeros; the optimizer state over
        padding advances inside ``st2`` exactly as the in-step commit
        would have advanced it)."""
        start, end = task.update_span
        view = self.pool.bucket_view(start, end)
        leaves, st2 = self._update_view_seg(
            view, m_seg, red, st_seg, lr,
            jnp.ones((view.size,), jnp.bool_))
        new = leaves[0] if len(leaves) == 1 else jnp.concatenate(leaves)
        new = new.astype(m_seg.dtype)
        if ok is not None:
            new = jnp.where(ok, new, m_seg[:new.shape[0]])
            st2 = jax.tree_util.tree_map(
                lambda n, o: jnp.where(ok, n.astype(o.dtype), o),
                st2, st_seg)
        if new.shape[0] != m_seg.shape[0]:
            new = jnp.concatenate([new, m_seg[new.shape[0]:]])
        return new, st2

    def run_pipelined_segs(self, plan: StepPlan, gpool, m_segs, st_segs,
                           lr, lane: InflightLane):
        """Segment-carry pipelined step — the formulation the
        ``--pipeline-check`` bench scans. The staged loop runs as usual
        (reduce_i ∥ update_{i-1}); head tasks' new segments replace
        their carry slots functionally, tail tasks park their mean
        segment in the outgoing lane, and the INCOMING lane's updates
        land in the tail slots from the same pre-step segments (head
        and tail spans are disjoint, so this is bit-identical to
        apply-then-stage). Returns (new_m_segs, new_st_segs,
        new_lane)."""
        nds = plan.num_data_shards
        new_m, new_st = list(m_segs), list(st_segs)
        pending = None
        tail_segs = []
        for task in plan.tasks:
            red = lazy_mod.reduce_bucket(
                gpool, task.start, task.end, plan.reduce_axes, None,
                algo=task.algo) / nds
            if pending is not None:
                pt, pr = pending
                with jax.named_scope("gf.update"):
                    new_m[pt.index], new_st[pt.index] = self._seg_update(
                        pt, m_segs[pt.index], st_segs[pt.index], pr, lr,
                        None)
                pending = None
            if task.commit_epoch:
                tail_segs.append(red)
            else:
                pending = (task, red)
        if pending is not None:
            pt, pr = pending
            with jax.named_scope("gf.update"):
                new_m[pt.index], new_st[pt.index] = self._seg_update(
                    pt, m_segs[pt.index], st_segs[pt.index], pr, lr, None)
        with jax.named_scope("gf.apply_inflight"):
            for task, red in zip(plan.tail_tasks, lane.segs):
                new_m[task.index], new_st[task.index] = self._seg_update(
                    task, m_segs[task.index], st_segs[task.index], red,
                    lane.lr, lane.ok)
        lane2 = InflightLane(segs=tuple(tail_segs),
                             lr=jnp.asarray(lr, jnp.float32),
                             ok=jnp.ones((), jnp.bool_))
        return tuple(new_m), tuple(new_st), lane2

    def apply_inflight_segs(self, plan: StepPlan, m_segs, st_segs,
                            lane: InflightLane):
        """Segment-carry lane flush (the window epilogue): the carried
        tail updates land in their slots, gated exactly like the in-scan
        apply."""
        if not plan.pipeline_tail:
            return m_segs, st_segs
        new_m, new_st = list(m_segs), list(st_segs)
        with jax.named_scope("gf.apply_inflight"):
            for task, red in zip(plan.tail_tasks, lane.segs):
                new_m[task.index], new_st[task.index] = self._seg_update(
                    task, m_segs[task.index], st_segs[task.index], red,
                    lane.lr, lane.ok)
        return tuple(new_m), tuple(new_st)


# -- the analytic twin (timeline simulation) ---------------------------------


def simulate_plan(plan: StepPlan, topo, *,
                  backward_s: Optional[float] = None,
                  hbm_bw: float = cost_model.HBM_BW) -> dict:
    """Price a StepPlan on a Topology with the cost model's two-engine
    timeline: per-bucket comm times from each task's own ReduceAlgorithm,
    releases at the uniform backward rate, update times from the HBM
    sweep model. Returns {rows, summary, backward_s, monolithic_finish_s}
    — ``monolithic_finish_s`` is the same buckets WITHOUT the staged
    update (comm finishes, then one barrier update sweep), the number the
    pipeline must beat."""
    elt = jnp.dtype(plan.wire_dtype).itemsize
    sizes = [t.size * elt for t in plan.tasks]
    if backward_s is None:
        backward_s = cost_model.ring_allreduce_time(
            plan.payload_elems * elt, topo.num_devices, topo.slowest_fabric)
    comm = [t.algo.predicted_time(b, topo) for t, b in zip(plan.tasks,
                                                           sizes)]
    rel = cost_model.bucket_release_times(sizes, backward_s)
    if plan.mode == "csc" and not plan.warmup:
        # The update side is its own segmented pass (spans ≠ tasks):
        # charge it as one post-comm sweep of the pool.
        upd = [0.0] * len(plan.tasks)
        rows = cost_model.staged_timeline(comm, rel, upd)
        tail = cost_model.update_time(plan.pool_size, hbm_bw)
        finish = rows[-1].update_end_s + tail if rows else backward_s
        summary = cost_model.timeline_summary(rows, backward_s)
        summary["finish_s"] = finish
        mono = finish
    else:
        upd = [cost_model.update_time(t.size, hbm_bw) for t in plan.tasks]
        rows = cost_model.staged_timeline(comm, rel, upd)
        summary = cost_model.timeline_summary(rows, backward_s)
        mono = cost_model.overlapped_finish_time(comm, rel) + sum(upd)
    return {"rows": rows, "summary": summary, "backward_s": backward_s,
            "monolithic_finish_s": mono}


def render_timeline(plan: StepPlan, topo, *,
                    backward_s: Optional[float] = None) -> str:
    """Human-readable compute/comm timeline of a plan — the dryrun
    ``--timeline`` table (per-bucket comm/update start+end in ms, the
    per-bucket exposed comm, and the overlap-efficiency summary)."""
    sim = simulate_plan(plan, topo, backward_s=backward_s)
    rows, summary = sim["rows"], sim["summary"]
    bw = sim["backward_s"]
    ms = 1e3
    lines = [
        f"StepPlan[{plan.mode}{' warmup' if plan.warmup else ''}] "
        f"{len(plan.tasks)} buckets, payload "
        f"{plan.payload_elems * jnp.dtype(plan.wire_dtype).itemsize / 2**20:.1f}"
        f" MiB ({plan.wire_dtype}) over {topo.num_devices} devices",
        f"{'bkt':>3} {'elems':>10} {'algo':>11} {'rel':>8} "
        f"{'comm_start':>10} {'comm_end':>9} {'upd_start':>9} "
        f"{'upd_end':>8} {'exposed':>8}   (ms)",
    ]
    for t, r in zip(plan.tasks, rows):
        lines.append(
            f"{r.index:>3} {t.size:>10} {t.algo.name:>11} "
            f"{r.release_s * ms:>8.2f} {r.comm_start_s * ms:>10.2f} "
            f"{r.comm_end_s * ms:>9.2f} {r.update_start_s * ms:>9.2f} "
            f"{r.update_end_s * ms:>8.2f} "
            f"{r.exposed_comm_s(bw) * ms:>8.2f}")
    lines.append(
        f"backward {bw * ms:.2f} ms | finish {summary['finish_s'] * ms:.2f}"
        f" ms (monolithic {sim['monolithic_finish_s'] * ms:.2f} ms) | "
        f"comm busy {summary['comm_busy_s'] * ms:.2f} ms | exposed comm "
        f"{summary['exposed_comm_s'] * ms:.2f} ms | overlap efficiency "
        f"{summary['overlap_efficiency'] * 100:.1f}%")
    return "\n".join(lines)


def simulate_plan_pipelined(plan: StepPlan, topo, *,
                            tail: Optional[int] = None,
                            backward_s: Optional[float] = None,
                            hbm_bw: float = cost_model.HBM_BW) -> dict:
    """Price the cross-step pipelined execution of a dense/lazy plan:
    the cost model's two-row timeline where the last ``tail`` buckets'
    updates retire during the NEXT step's forward window, each gated by
    its span's fwd need-time. ``tail`` defaults to the plan's own
    ``pipeline_tail`` (auto-selected when that is 0 — the what-if the
    dryrun table shows). Returns the ``cross_step_timeline`` dict plus
    the staged (within-step) baseline for comparison."""
    assert plan.mode in ("dense", "lazy") or plan.warmup, plan.mode
    elt = jnp.dtype(plan.wire_dtype).itemsize
    sizes = [t.size * elt for t in plan.tasks]
    if backward_s is None:
        backward_s = cost_model.ring_allreduce_time(
            plan.payload_elems * elt, topo.num_devices, topo.slowest_fabric)
    comm = [t.algo.predicted_time(b, topo) for t, b in zip(plan.tasks,
                                                           sizes)]
    rel = cost_model.bucket_release_times(sizes, backward_s)
    upd = [cost_model.update_time(t.size, hbm_bw) for t in plan.tasks]
    if tail is None:
        tail = plan.pipeline_tail or cost_model.select_pipeline_tail(
            comm, rel, upd, backward_s)
    sim = cost_model.cross_step_timeline(comm, rel, upd, tail, backward_s)
    sim["backward_s"] = backward_s
    sim["staged_finish_s"] = cost_model.staged_finish_time(comm, rel, upd)
    rows = cost_model.staged_timeline(comm, rel, upd)
    sim["staged_exposed_comm_s"] = cost_model.timeline_summary(
        rows, backward_s)["exposed_comm_s"]
    return sim


def render_cross_step_timeline(plan: StepPlan, topo, *,
                               backward_s: Optional[float] = None) -> str:
    """Human-readable cross-step (two-row) schedule: one steady-state
    step with carried tail applies up front, head buckets committing
    in-step, and the new tail handed to step t+1 — the second table
    ``launch/dryrun.py --timeline`` prints for pipelineable plans."""
    sim = simulate_plan_pipelined(plan, topo, backward_s=backward_s)
    ms = 1e3
    lines = [
        f"cross-step pipeline: tail={sim['tail']} of {len(plan.tasks)} "
        f"buckets deferred into the scan carry",
        f"{'bkt':>3} {'lane':>8} {'comm_start':>10} {'comm_end':>9} "
        f"{'retire':>8}   (ms)",
    ]
    for idx, deferred, cs, ce, retire in sim["rows"]:
        lane = "carry" if deferred else "in-step"
        lines.append(f"{idx:>3} {lane:>8} {cs * ms:>10.2f} "
                     f"{ce * ms:>9.2f} {retire * ms:>8.2f}")
    lines.append(
        f"steady-state period {sim['period_s'] * ms:.2f} ms vs staged "
        f"{sim['staged_finish_s'] * ms:.2f} ms | exposed comm "
        f"{sim['exposed_comm_s'] * ms:.2f} ms vs staged "
        f"{sim['staged_exposed_comm_s'] * ms:.2f} ms | window prologue "
        f"{sim['prologue_s'] * ms:.2f} ms")
    return "\n".join(lines)
