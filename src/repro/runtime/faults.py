"""Data-plane fault injection: corrupt the REAL numeric path, not the
analytic timeline.

PR 6's soak exercised the control plane (failures, stragglers, elastic
remesh) against the simulated step clock; nothing ever corrupted an
actual gradient. This module injects the three wire-level fault classes
the guard rail (repro.core.guard + repro.optim.scaler) must catch —

  'nan'      — a poisoned gradient segment (NaN), the classic silent
               run-killer: one bad loss, every parameter NaN two steps
               later;
  'overflow' — a segment forced to huge-but-finite magnitude, the
               precursor state the loss scaler must back off from BEFORE
               the wire cast starts emitting Inf;
  'bitflip'  — an exponent-MSB flip of the wire words in a segment (a
               transit corruption). For a word with |x| in [2^-8, 2) —
               the envelope gradients live in at working loss scales —
               the flip lands at magnitude >= 2^119 (bf16/f32) or Inf,
               far above GuardConfig's census limit, so it trips the
               overflow/nonfinite flag deterministically. Flips of words
               outside that envelope can shrink the value instead (an
               exponent flip is roughly a reciprocal) — that subset is
               fundamentally invisible to magnitude-based detection and
               is out of scope here.

Faults are TRACED: ``make_hook(events)`` builds a
``fault_hook(gpool, step)`` for ``Trainer.build_train_step`` that gates
each corruption on the step counter with ``jnp.where`` — one compiled
program covers the whole schedule, and the corruption lands on the
packed local pool right before the reduce, i.e. on the bytes that would
have crossed the wire.

``GuardLane`` is the miniature real-numeric harness the soak and the
``--guard-check`` CI gate share: a pool + OverlapEngine guarded step on
a one-device mesh, stepped against a fault schedule, recording per step
the verdict, the scaler trajectory, and a host-side bit-identity check
of the atomic skip. Every recorded value is an int, a bool, or a
power-of-two float, so traces compare verbatim across machines and jax
versions (the BENCH_soak.json contract).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import GuardConfig


@dataclasses.dataclass(frozen=True)
class FaultEvent:
    """One data-plane corruption: pool elements [offset, offset+width)
    at ``step``."""

    step: int
    kind: str  # 'nan' | 'overflow' | 'bitflip'
    offset: int = 0
    width: int = 4


def _flip_exponent_msb(seg: jax.Array) -> jax.Array:
    """XOR the exponent MSB of each wire word (bit 14 of 16-bit floats —
    bf16 and f16 alike — bit 30 of f32)."""
    dt = seg.dtype
    if jnp.dtype(dt).itemsize == 2:
        u = jax.lax.bitcast_convert_type(seg, jnp.uint16)
        return jax.lax.bitcast_convert_type(u ^ jnp.uint16(1 << 14), dt)
    u = jax.lax.bitcast_convert_type(seg.astype(jnp.float32), jnp.uint32)
    return jax.lax.bitcast_convert_type(
        u ^ jnp.uint32(1 << 30), jnp.float32).astype(dt)


def _corrupt(gpool: jax.Array, ev: FaultEvent) -> jax.Array:
    seg = jax.lax.slice_in_dim(gpool, ev.offset, ev.offset + ev.width)
    if ev.kind == "nan":
        bad = jnp.full(seg.shape, jnp.nan, gpool.dtype)
    elif ev.kind == "overflow":
        # Huge but finite in bf16/f32 (2^120): the census lands above the
        # overflow limit without going Inf — the pre-saturation state.
        # (In f16 the cast itself saturates to Inf; the nonfinite flag
        # catches it instead — see guard.overflow_limit.)
        bad = jnp.full(seg.shape, 2.0 ** 120, gpool.dtype)
    elif ev.kind == "bitflip":
        bad = _flip_exponent_msb(seg)
    else:
        raise ValueError(f"unknown fault kind: {ev.kind!r}")
    return jax.lax.dynamic_update_slice(gpool, bad,
                                        (jnp.int32(ev.offset),))


def apply_faults(gpool: jax.Array, step: jax.Array,
                 events: Sequence[FaultEvent]) -> jax.Array:
    """Traced: apply every event whose step matches the (traced) step
    counter. Static schedule, one compiled program."""
    for ev in events:
        gpool = jnp.where(jnp.equal(step, ev.step), _corrupt(gpool, ev),
                          gpool)
    return gpool


def make_hook(events: Sequence[FaultEvent]) -> Callable:
    """Build the ``fault_hook(gpool, step)`` for
    ``Trainer.build_train_step(fault_hook=...)``."""
    events = tuple(events)

    def hook(gpool, step):
        return apply_faults(gpool, step, events)

    return hook


# -- the guard lane -----------------------------------------------------------


# Lane defaults: grads are drawn from U[0.25, 1) and the scale is capped
# at 2, so every wire word stays inside the bitflip-detectable envelope
# [2^-8, 2) while the grow (1 -> 2) and backoff (2 -> 1) transitions
# still both occur within a short soak window.
LANE_GUARD = GuardConfig(init_scale=1.0, growth_interval=6,
                         growth_factor=2.0, backoff_factor=0.5,
                         min_scale=1.0, max_scale=2.0)


class GuardLane:
    """A miniature guarded training lane over the REAL numeric path.

    One-device mesh, a small gradient pool, the actual
    ``OverlapEngine.run_guarded`` staged pipeline (or the monolithic
    trainer path's engine twin) — stepped against a ``FaultEvent``
    schedule. Each step records:

      fault        — the injected kind, or None (clean step)
      tripped      — did the in-band census verdict reject the step?
      state_frozen — host-side ``np.array_equal`` proof that a rejected
                     step left params AND momentum bit-identical (True
                     on clean steps by convention: nothing to check)
      scale        — the loss scale after the step (power of two)
      skipped      — cumulative guard-rejected steps

    The records are machine-independent (ints/bools/power-of-two floats
    only), so the soak trace can embed them verbatim.
    """

    POOL_SIZES = ((96,), (32,))
    CHUNK = 32

    def __init__(self, guard: Optional[GuardConfig] = None, *,
                 mode: str = "lazy", wire_dtype: str = "bfloat16",
                 wire_format: str = "native", seed: int = 0):
        from repro.configs.base import GradientFlowConfig, OptimizerConfig
        from repro.core.engine import OverlapEngine
        from repro.core.gradientflow import GradientFlow
        from repro.core.pool import GradientPool

        self.guard = guard or LANE_GUARD
        self.cfg = GradientFlowConfig(
            mode=mode, bucket_elems=64, chunk_elems=self.CHUNK,
            sparsity=0.5, warmup_steps=0, wire_dtype=wire_dtype,
            reduce_axes=("data",), collective_algo="flat",
            overlap="staged", wire_format=wire_format, guard=self.guard)
        rng = np.random.default_rng(seed)
        tree = {f"t{i}": jnp.asarray(rng.uniform(0.25, 1.0, s),
                                     jnp.float32)
                for i, s in enumerate(self.POOL_SIZES)}
        self.params = tree
        self.pool = GradientPool(
            jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree),
            pad_to=self.CHUNK
            if (mode == "csc" or self.cfg.quantized) else 1)
        self.gf = GradientFlow(self.cfg, self.pool, num_data_shards=1)
        opt_cfg = OptimizerConfig(name="momentum_sgd", momentum=0.9,
                                  weight_decay=0.0)
        self.opt_cfg = opt_cfg
        self.engine = OverlapEngine(self.gf, "momentum_sgd", opt_cfg)
        # Base gradients in the detectable envelope (see LANE_GUARD).
        self.base_grads = jnp.asarray(
            rng.uniform(0.25, 1.0, self.pool.size) *
            rng.choice([-1.0, 1.0], self.pool.size), jnp.float32)

    def run(self, num_steps: int, events: Sequence[FaultEvent] = (),
            window: int = 1) -> List[dict]:
        from repro.core.gradientflow import GFState
        from repro.optim import init_state as opt_init_state
        from repro.optim import scaler as scaler_mod
        from repro.parallel.collectives import (compat_make_mesh,
                                                compat_set_mesh,
                                                compat_shard_map)
        from jax.sharding import PartitionSpec as P

        events = tuple(events)
        by_step = {ev.step: ev for ev in events}
        plan = self.engine.plan_for()
        csc = self.cfg.csc_enabled
        # CSC and the quantized wire formats consume the f32 pool (the
        # wire cast / quantization happens inside the guarded engine).
        prepack_dtype = jnp.float32 if (csc or self.cfg.quantized) \
            else jnp.dtype(self.cfg.wire_dtype)

        def body(params, opt, gfstate, scaler, step):
            # The lane's "backward pass": the fixed base gradients times
            # the live loss scale, packed to the wire dtype — exactly
            # the trainer's scaled-pack handoff.
            gpool = (self.base_grads * scaler.scale).astype(prepack_dtype)
            gpool = apply_faults(gpool, step, events)
            return self.engine.run_guarded(plan, gpool, params, opt,
                                           gfstate, scaler, 0.05)

        mesh = compat_make_mesh((1,), ("data",))
        sm = compat_shard_map(
            body, mesh=mesh,
            in_specs=(P(None), P(None), P(None), P(), P()),
            out_specs=(P(None), P(None), P(None), P(), P()),
            axis_names={"data"}, check_vma=False)

        params = self.params
        opt = opt_init_state("momentum_sgd", self.pool.size)
        gfstate = self.gf.init_state()
        scaler = scaler_mod.init(self.guard)
        records: List[dict] = []
        with compat_set_mesh(mesh):
            if window > 1:
                return self._run_windows(sm, params, opt, gfstate, scaler,
                                         num_steps, window, by_step)
            stepped = jax.jit(sm)
            for t in range(num_steps):
                before = (np.asarray(self.pool.pack(
                              params, dtype=jnp.float32)[0]),
                          np.asarray(opt.momentum),
                          np.asarray(gfstate.hg),
                          np.asarray(gfstate.residual))
                params, opt, gfstate, scaler, flags = stepped(
                    params, opt, gfstate, scaler, jnp.int32(t))
                tripped = bool(np.asarray(flags.nonfinite) |
                               np.asarray(flags.overflow))
                frozen = True
                if tripped:
                    after = (np.asarray(self.pool.pack(
                                 params, dtype=jnp.float32)[0]),
                             np.asarray(opt.momentum),
                             np.asarray(gfstate.hg),
                             np.asarray(gfstate.residual))
                    frozen = all(np.array_equal(a, b, equal_nan=True)
                                 for a, b in zip(before, after))
                ev = by_step.get(t)
                records.append({
                    "step": t,
                    "fault": ev.kind if ev is not None else None,
                    "tripped": tripped,
                    "state_frozen": frozen,
                    "scale": float(np.asarray(scaler.scale)),
                    "skipped": int(np.asarray(scaler.skipped)),
                })
        return records

    def _run_windows(self, sm, params, opt, gfstate, scaler, num_steps,
                     window, by_step) -> List[dict]:
        """The compile-once lane: ``lax.scan`` over the shard_mapped
        guarded body (scan OUTSIDE the manual region, as the trainer's
        windows place it), the (params, opt, gf, scaler)
        carry threaded through the scan, and per-step state snapshots
        returned STACKED so the host syncs once per window yet still
        reconstructs the exact per-step record stream — including the
        bit-identity frozen proof, checked against the previous step's
        stacked snapshot instead of a host read before every step.
        Faults keyed off the in-carry step counter fire mid-window."""

        def body(carry, step):
            p, o, g, s = carry
            p2, o2, g2, s2, flags = sm(p, o, g, s, step)
            snap = (self.pool.pack(p2, dtype=jnp.float32)[0],
                    o2.momentum, g2.hg, g2.residual, s2.scale,
                    s2.skipped, flags.nonfinite | flags.overflow)
            return (p2, o2, g2, s2), snap

        win = jax.jit(lambda c, steps: jax.lax.scan(body, c, steps))
        carry = (params, opt, gfstate, scaler)
        prev = (np.asarray(self.pool.pack(params, dtype=jnp.float32)[0]),
                np.asarray(opt.momentum), np.asarray(gfstate.hg),
                np.asarray(gfstate.residual))
        records: List[dict] = []
        t = 0
        while t < num_steps:
            n = min(window, num_steps - t)
            carry, snaps = win(carry,
                               jnp.arange(t, t + n, dtype=jnp.int32))
            pools, moms, hgs, residuals, scales, skipped, tripped = \
                jax.device_get(snaps)  # ONE sync for the whole window
            for i in range(n):
                cur = (pools[i], moms[i], hgs[i], residuals[i])
                trip = bool(tripped[i])
                frozen = True
                if trip:
                    frozen = all(np.array_equal(a, b, equal_nan=True)
                                 for a, b in zip(prev, cur))
                ev = by_step.get(t + i)
                records.append({
                    "step": t + i,
                    "fault": ev.kind if ev is not None else None,
                    "tripped": trip,
                    "state_frozen": frozen,
                    "scale": float(scales[i]),
                    "skipped": int(skipped[i]),
                })
                prev = cur
            t += n
        return records


def truth_table(records: Sequence[dict]) -> dict:
    """Collapse lane records into the detection truth table: per fault
    class, injected vs caught (caught = tripped AND bit-identical skip);
    plus false trips on clean steps."""
    table: dict = {}
    false_trips = 0
    for r in records:
        if r["fault"] is None:
            false_trips += int(r["tripped"])
            continue
        row = table.setdefault(r["fault"],
                               {"injected": 0, "caught": 0})
        row["injected"] += 1
        row["caught"] += int(r["tripped"] and r["state_frozen"])
    return {"classes": table, "false_trips": false_trips,
            "clean_steps": sum(1 for r in records if r["fault"] is None)}
