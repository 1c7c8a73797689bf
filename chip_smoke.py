#!/usr/bin/env python3
"""Smoke run of the GradientFlow trainer on TPU v5e.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # one host with four chips

One chip: smollm-135m at its published widths (30 layers, d_model 576,
vocab 49,152), seq_len 2048, random weights from a seed, Pallas kernels
on, through the entry points a user calls: ``repro.launch.train.build``,
``Trainer.build_train_window`` (via ``train.WindowLoop``, compiled under
the mesh context as ``train.main`` compiles it) and
``TrainSupervisor.run_windows`` with ``max_restarts=0``. Two settings:
``lazy`` with the bf16 wire, and ``csc`` with the warm-up cut to zero so
every step runs the sparse stage (chunk census and compaction kernels).
The global batch is the largest at which the compiled lazy window and
that of its jnp twins fit the chip (``memory_analysis()`` against the
device's ``bytes_limit``), found by a search that compiles each batch it
tries; csc runs at that batch or the largest below it that fits.

Four chips (``--chips 4``): one pool bucket reduced by XLA's all-reduce,
the Pallas RDMA ring and its ppermute twin on a 2x2 data x model mesh
and on the 4x1 data mesh, compared with a host sum; then the trainer on
the 4x1 ``data`` mesh at ``PER_CHIP_BATCH`` per chip, ``lazy`` with
``collective_algo="pallas_ring"`` and with ``"flat"`` (XLA's
all-reduce).

Every trainer setting runs the same steps through the jnp twins
(``use_kernels=False``, ``flat``) on the chip and checks that the losses
are finite and match the twins' at every step, that the state after step
1 matches within the stated limits (and that those limits reject no
update and, on four chips, 1/4 of the update), and that
``ops.dispatch_counts`` holds no ``.ref`` entry. Step times
printed here are a smoke, not a benchmark.

The script runs in one process and starts none. It exits non-zero, and
prints no result line, when JAX finds no TPU or any check fails; on
success the last line of stdout is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "smollm-135m"
SEQ_LEN = 2048
STEPS = 4              # steps per setting; windows of WINDOW steps
WINDOW = 1             # one step per window: the first window IS step 1
CHUNK_ELEMS = 32768    # the paper's CSC chunk
# The one-chip phase finds the largest global batch in [BATCH_MIN,
# BATCH_MAX] at which the compiled lazy window and its jnp twins' both
# fit the chip. Memory grows with the batch, so the search starts at
# BATCH_HINT (the answer on a TPU v5e with 16 GB) and walks up while
# batches fit, down while they do not: a fitting batch next to a refused
# one above it ends the search.
BATCH_MIN, BATCH_MAX = 1, 32
BATCH_HINT = 18
# Per-chip batch of the four-chip phase (checked to fit).
PER_CHIP_BATCH = 16
# Kernel path vs jnp twins after step 1, per parameter tensor (a
# segment of the pool) and per tree (params, momentum, CSC state): the
# largest |kernel - twin| is at most LEAF_RTOL of the largest |twin
# update| (update = after step 1 less before). The model runs in bf16,
# and the two programs place their bf16 roundings differently, so the
# gradients differ by a few bf16 ulps; on a TPU v5e the largest
# difference was 0.017 of the largest update at smollm-135m, batch 18.
# A pallas_ring step adds the bf16 rounding of its three hops (3 *
# 2**-8 of the partial sums). No update and 1/N of the update must fail
# the same rule. Under csc, chunks that one side selected and the other
# did not (near-ties of the chunk norms) are left out, at most
# CSC_FLIPS_MAX of them. Losses of step 1 (a forward pass from the same
# params) must agree to LOSS_RTOL, later steps' losses to
# LATER_LOSS_RTOL.
LEAF_RTOL = 2.0 ** -4
CSC_FLIPS_MAX = 8
LOSS_RTOL = 1e-5
LATER_LOSS_RTOL = 2e-4


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> "NoReturn":  # noqa: F821
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def require_tpu():
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: jax.devices()[0].platform is "
             f"{devices[0].platform!r}")
    return devices


def device_limit(device) -> int:
    """Bytes one program may hold on ``device``."""
    return device.memory_stats()["bytes_limit"]


def train_argv(mode: str, batch: int, mesh: str, *, kernels: bool,
               algo: str = "flat") -> list:
    argv = ["--arch", ARCH, "--seq-len", str(SEQ_LEN),
            "--batch", str(batch), "--mesh", mesh, "--gf-mode", mode,
            "--window-steps", str(WINDOW), "--steps", str(STEPS),
            "--chunk-elems", str(CHUNK_ELEMS), "--csc-warmup", "0",
            "--collective-algo", algo, "--wire-format", "native"]
    return argv + (["--use-kernels"] if kernels else [])


class Setting:
    """One trainer configuration, kernel path and twin, built once."""

    def __init__(self, name: str, argv: list):
        from repro.data.pipeline import DataPipeline
        from repro.data.synthetic import SyntheticLM
        from repro.launch import train

        self.name = name
        self.args = train._parser().parse_args(argv)
        self.trainer, self.cfg, self.mesh = train.build(self.args)
        data = SyntheticLM(self.cfg.model.vocab_size, seed=self.args.seed)
        self.pipe = DataPipeline(data, self.cfg.global_batch,
                                 self.cfg.seq_len)
        self.loop = train.WindowLoop(self.trainer, self.pipe, WINDOW)

    def init_state(self, trainer=None):
        import jax
        return (trainer or self.trainer).init_state(
            jax.random.PRNGKey(self.args.seed))

    def compile_first(self):
        """AOT-compile the window program of step 0 (cached in the loop,
        so the supervised run reuses it). Returns its memory analysis."""
        state = self.init_state()
        batch = self.loop.batches(0, WINDOW)
        exe = self.loop.executable(self.loop.stage_at(0), state, batch)
        return exe.memory_analysis()


def pair(name: str, mode: str, batch: int, mesh: str,
         algo: str = "flat") -> tuple:
    """The kernel path and its jnp twins (``use_kernels=False``, XLA's
    all-reduce) at one size."""
    return (Setting(name, train_argv(mode, batch, mesh, kernels=True,
                                     algo=algo)),
            Setting(f"{name} twins", train_argv(mode, batch, mesh,
                                                kernels=False)))


def window_bytes(ma) -> int:
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def fit_batch(make_pair, hint: int, limit: int, lo: int, hi: int):
    """The largest batch in [lo, hi] at which the kernel path's first
    window and its twins' both compile within ``limit`` bytes, with that
    (already compiled) pair. Starts at ``hint``; walks up while batches
    fit and down while they do not. The twins go first: they hold the
    packed pool in jnp temporaries and need more. Each window is compiled
    under its mesh context, as ``train.main`` compiles it."""
    import jax
    from repro.parallel.collectives import compat_set_mesh

    def fits(setting, batch):
        with compat_set_mesh(setting.mesh):
            try:
                ma = setting.compile_first()
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                log(f"  batch {batch} {setting.name}: compile refused, "
                    f"does not fit ({str(e).splitlines()[0][:160]})")
                return False
        need = window_bytes(ma)
        key = next(iter(setting.loop.compile_seconds))
        log(f"  batch {batch} {setting.name}: compiled window needs "
            f"{need / 1e9:.3f} GB (arg {ma.argument_size_in_bytes / 1e9:.3f}"
            f" out {ma.output_size_in_bytes / 1e9:.3f} temp "
            f"{ma.temp_size_in_bytes / 1e9:.3f} alias "
            f"{ma.alias_size_in_bytes / 1e9:.3f}) of {limit / 1e9:.3f} GB; "
            f"{setting.loop.lower_seconds[key]:.1f} s trace and lowering, "
            f"{setting.loop.compile_seconds[key]:.1f} s compile")
        return need <= limit

    def attempt(batch):
        kernel, twin = make_pair(batch)
        if fits(twin, batch) and fits(kernel, batch):
            return kernel, twin
        return None

    batch, best = hint, attempt(hint)
    if best is not None:
        while batch < hi:
            bigger = attempt(batch + 1)
            if bigger is None:
                break
            batch, best = batch + 1, bigger
        return batch, best
    for batch in range(hint - 1, lo - 1, -1):
        best = attempt(batch)
        if best is not None:
            return batch, best
    fail(f"no batch in [{lo}, {hint}] fits {limit} bytes")


def snapshot(state, pool) -> dict:
    """Host copies, in pool space, of what step 1 changes: params,
    momentum and CSC state (historical gradients, chunk norms)."""
    import jax
    import numpy as np
    params = np.zeros((pool.size,), np.float32)
    for leaf, spec in zip(pool.flat_leaves(state.params), pool.specs):
        params[spec.offset:spec.offset + spec.size] = np.asarray(
            leaf, np.float32).reshape(-1)
    (mom,) = jax.tree_util.tree_leaves(state.opt)

    def flat(x):
        return np.asarray(x, np.float32).reshape(-1)
    return {"params": params, "opt": flat(mom), "hg": flat(state.gf.hg),
            "norms": flat(state.gf.chunk_norms)}


def worst_ratio(got, want, base, segments, keep=None) -> float:
    """Largest over ``segments`` of max|got - want| / max|want - base|
    (elements outside ``keep`` left out)."""
    import numpy as np
    worst = 0.0
    for lo, n in segments:
        d = np.abs(got[lo:lo + n] - want[lo:lo + n])
        if keep is not None:
            d = np.where(keep[lo:lo + n], d, 0.0)
        dmax = float(d.max(initial=0.0))
        umax = float(np.abs(want[lo:lo + n] - base[lo:lo + n]).max(
            initial=0.0))
        if dmax > 0:
            worst = max(worst, dmax / umax if umax > 0 else np.inf)
    return worst


def check_first_step(setting, got: dict, twin: dict, ranks: int) -> None:
    """Step 1 through the kernel path vs the twins (see LEAF_RTOL). Also
    checks that the same rule rejects what a broken reduce or update
    would give: no update at all and, on more than one rank, 1/N of the
    update (one rank's share where the ranks' gradients agree)."""
    import numpy as np
    pool = setting.trainer.pool
    before, after = twin["before"], twin["after"]
    leaves = [(sp.offset, sp.size) for sp in pool.specs]
    keep, flips = None, 0
    if setting.cfg.gradientflow.mode == "csc":
        chunk = setting.cfg.gradientflow.chunk_elems

        def chosen(mom):  # a chunk was sent iff its momentum moved
            return np.any(mom.reshape(-1, chunk) != 0, axis=1)
        k_sel, t_sel = chosen(got["opt"]), chosen(after["opt"])
        flips = int(np.sum(k_sel != t_sel))
        keep = np.repeat(k_sel == t_sel, chunk)
        if flips > CSC_FLIPS_MAX:
            fail(f"{setting.name}: {flips} chunks selected by only one "
                 f"side (of {int(t_sel.sum())} the twins selected)")
    parts = []
    trees = [("params", leaves), ("opt", leaves), ("hg", leaves),
             ("norms", [(0, after["norms"].size)])]
    for key, segs in trees:
        if after[key].size == 0:
            continue
        k_keep = keep if key != "norms" else None
        ratio = worst_ratio(got[key], after[key], before[key], segs,
                            k_keep)
        if ratio > LEAF_RTOL:
            fail(f"{setting.name}: {key} of the kernel path differ from "
                 f"the jnp twins by {ratio:.3e} of the largest update of "
                 f"a tensor (limit {LEAF_RTOL})")
        parts.append(f"{key} {ratio:.3e}")
    teeth = []
    for key in ("params", "opt"):
        fakes = [("no update", before[key])]
        if ranks > 1:
            fakes.append((f"1/{ranks} update", before[key]
                          + (after[key] - before[key]) / ranks))
        for label, fake in fakes:
            ratio = worst_ratio(fake, after[key], before[key], leaves)
            if ratio <= LEAF_RTOL:
                fail(f"{setting.name}: the {key} limit accepts {label}")
            teeth.append(f"{label} {key} {ratio:.3e}")
    log(f"  step 1 kernel vs jnp twins, largest |diff| per tensor over "
        f"its largest update (limit {LEAF_RTOL}): {'; '.join(parts)}"
        + (f"; csc chunks selected by one side only: {flips}"
           if keep is not None else "")
        + f"; the same ratio for {'; '.join(teeth)}")


def check_losses(setting, twin: dict) -> None:
    import numpy as np
    got = np.asarray(setting.loop.losses[-STEPS:], np.float64)
    want = np.asarray(twin["losses"], np.float64)
    if got.size != STEPS or not np.all(np.isfinite(got)):
        fail(f"{setting.name}: losses not finite: {got.tolist()}")
    rel = np.abs(got - want) / np.abs(want)
    limit = np.array([LOSS_RTOL] + [LATER_LOSS_RTOL] * (STEPS - 1))
    if not np.all(rel <= limit):
        fail(f"{setting.name}: losses {got.tolist()} differ from the jnp "
             f"twins' {want.tolist()} (relative {rel.tolist()})")
    idle = twin["no_update_loss"]
    log(f"  losses {got.tolist()} (finite); jnp twins "
        f"{want.tolist()}; max relative diff {float(rel.max()):.3e} "
        f"(limit {LOSS_RTOL} at step 1, {LATER_LOSS_RTOL} after); step 2 "
        f"with no step-1 update: {idle} (relative "
        f"{abs(idle - want[1]) / abs(want[1]):.3e})")


def kernel_first_step(setting: Setting) -> dict:
    """Step 1 through the kernel path's compiled window, from the seed."""
    loop = setting.loop
    batch = loop.batches(0, WINDOW)
    state = setting.init_state()
    out, _ = loop.executable(loop.stage_at(0), state, batch)(state, batch)
    return snapshot(out, setting.trainer.pool)


def run_twin(setting: Setting, twin: Setting) -> dict:
    """The jnp twins over ``setting``'s STEPS steps, batches and seed,
    compiled and run on the chip: the state before and after step 1,
    every step's loss, and the loss of step 2 taken from the seed's
    params (what no step-1 update gives)."""
    import numpy as np

    loop, losses = setting.loop, []
    pool = setting.trainer.pool
    state = twin.init_state()
    result = {"before": snapshot(state, pool)}
    for step in range(0, STEPS, WINDOW):
        stage, batch = loop.stage_at(step), loop.batches(step, WINDOW)
        state, metrics = twin.loop.executable(stage, state, batch)(
            state, batch)
        losses.extend(float(x) for x in np.asarray(metrics["loss"]))
        if step == 0:
            result["after"] = snapshot(state, pool)
    del state
    fresh, batch = twin.init_state(), loop.batches(WINDOW, WINDOW)
    _, metrics = twin.loop.executable(loop.stage_at(WINDOW), fresh,
                                      batch)(fresh, batch)
    result["no_update_loss"] = float(np.asarray(metrics["loss"])[0])
    result["losses"] = losses
    log(f"  jnp twins: {STEPS} steps; compile seconds "
        f"{[round(v, 1) for v in twin.loop.compile_seconds.values()]} (+ "
        f"{[round(v, 1) for v in twin.loop.lower_seconds.values()]} s "
        f"trace and lowering)")
    return result


def supervised_run(setting: Setting) -> None:
    """STEPS steps through TrainSupervisor.run_windows, no restarts."""
    from repro.checkpoint.manager import CheckpointManager
    from repro.runtime.fault_tolerance import (SupervisorConfig,
                                               TrainSupervisor)

    ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        sup = TrainSupervisor(
            CheckpointManager(ckpt_dir, keep=1),
            SupervisorConfig(checkpoint_every=10 ** 9, max_restarts=0))
        state = setting.init_state()
        setting.pipe.start(0)
        t0 = time.perf_counter()
        state = sup.run_windows(state, 0, STEPS, setting.loop, WINDOW,
                                on_restore=setting.pipe.skip_to)
        wall = time.perf_counter() - t0
        setting.pipe.stop()
        sup.ckpt.wait()
        del state
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    if sup.restarts:
        fail(f"{setting.name}: supervisor restarted {sup.restarts}x: "
             f"{sup.restart_causes}")
    win = setting.loop.window_seconds[-STEPS // WINDOW:]
    log(f"  step seconds (smoke, not a benchmark): "
        f"{[round(t / WINDOW, 4) for t in win]}; supervised run "
        f"{wall:.1f} s incl. final checkpoint")


def check_dispatch(expect: set) -> dict:
    """No dispatch of this process (they happen while a window is
    traced, i.e. compiled) took a ``.ref`` twin, and each path in
    ``expect`` (``<op>.kernel``, or ``pool_pack.xla`` for the no-census
    pack) was dispatched."""
    from repro.kernels import ops
    counts = dict(ops.dispatch_counts)
    refs = sorted(k for k in counts if k.endswith(".ref"))
    if refs:
        fail(f"ref-twin dispatch on the trainer path: {refs} ({counts})")
    missing = sorted(k for k in expect if k not in counts)
    if missing:
        fail(f"paths never dispatched: {missing} ({counts})")
    return counts


def run_setting(setting: Setting, twin: dict, expect: set) -> None:
    import jax
    from repro.parallel.collectives import compat_set_mesh

    log(f"[{setting.name}] mesh {dict(setting.mesh.shape)}, global batch "
        f"{setting.cfg.global_batch}, seq {setting.cfg.seq_len}, "
        f"pool {setting.trainer.pool.size} elems")
    with compat_set_mesh(setting.mesh):
        check_first_step(setting, kernel_first_step(setting), twin,
                         setting.mesh.shape["data"])
        supervised_run(setting)
    check_losses(setting, twin)
    for key, secs in setting.loop.compile_seconds.items():
        log(f"  compile seconds (stage {key[0]}, window {key[1]}): "
            f"{secs:.1f} (+ {setting.loop.lower_seconds[key]:.1f} s trace "
            f"and lowering)")
    log(f"  dispatch_counts: {check_dispatch(expect)}")
    log(f"  memory_stats: {jax.devices()[0].memory_stats()}")


def twin_for(setting: Setting, twin: Setting) -> dict:
    from repro.parallel.collectives import compat_set_mesh
    with compat_set_mesh(twin.mesh):
        return run_twin(setting, twin)


def bucket_check(mesh, elems: int) -> None:
    """One pool bucket reduced over ``data`` by XLA's all-reduce, the
    Pallas ring and its ppermute twin, on every column of ``mesh`` at
    once (each ``model`` position holds its own data), against a host f32
    sum per column: exact on integer data (every partial sum is
    representable in bf16), and within the bf16 rounding of N-1 hops on
    normal data."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.kernels import ops, ref

    n, m = mesh.shape["data"], mesh.shape["model"]
    spec = P("data", "model")
    rng = np.random.RandomState(0)
    reducers = {
        "flat": lambda v: jax.lax.psum(v, "data"),
        "pallas_ring": lambda v: ops.ring_allreduce(v, ("data",)),
        "ring twin": lambda v: ref.ring_allreduce(v, "data"),
    }
    for kind in ("integer", "normal"):
        if kind == "integer":
            host = rng.randint(-8, 9, size=(n, m, elems)).astype(np.float32)
        else:
            host = rng.standard_normal((n, m, elems)).astype(np.float32)
        x = jax.device_put(jnp.asarray(host, jnp.bfloat16),
                           NamedSharding(mesh, spec))
        xs = np.asarray(x, np.float32)
        want = xs.sum(axis=0)
        bound = (n - 1) * 2.0 ** -8 * np.abs(xs).sum(axis=0)
        for name, red in reducers.items():
            f = jax.jit(jax.shard_map(
                lambda v, red=red: red(v[0, 0])[None, None], mesh=mesh,
                in_specs=spec, out_specs=spec, check_vma=False))
            got = np.asarray(f(x), np.float32)
            if not np.all(got == got[0:1]):
                fail(f"bucket {name}: ranks of a column disagree")
            err = np.abs(got[0] - want)
            ok = np.all(err == 0) if kind == "integer" else \
                np.all(err <= bound)
            if not ok:
                fail(f"bucket {name} ({kind}) on mesh "
                     f"{dict(mesh.shape)}: max |err| "
                     f"{float(err.max()):.3e} vs host sum")
            log(f"  bucket ({elems} elems, bf16, {kind}) {name} on mesh "
                f"{dict(mesh.shape)}: max |err| vs host f32 sum "
                f"{float(err.max()):.3e}")


def kernel_exactness(setting: Setting) -> None:
    """The pack (the no-census XLA pass) and the per-bucket update
    kernels, compiled for the chip at the trainer's pool layout, against
    their jnp twins on the same random inputs: the pack (a copy and a
    cast) bit for bit, the update (the same elementwise f32 math, fused
    differently) to 2**-20 of each output's largest value."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    pool, rng = setting.trainer.pool, np.random.RandomState(1)
    t0 = time.perf_counter()
    leaves = [jnp.asarray(rng.standard_normal(n).astype(np.float32) * 1e-3)
              for n in pool.sizes]
    got = np.asarray(ops.pool_pack(leaves, pool.offsets, pool.sizes,
                                   pool.size, 0, jnp.bfloat16)[0])
    want = np.asarray(ref.pool_pack(leaves, pool.offsets, pool.size, 0,
                                    jnp.bfloat16)[0])
    if not np.array_equal(got, want):
        fail(f"pool_pack differs from its jnp twin at "
             f"{int(np.sum(got != want))} of {got.size} elements")
    del leaves, got, want
    worst = 0.0
    for start, end in setting.trainer.gf._lazy_bounds:
        view, n = pool.bucket_view(start, end), end - start
        args = [jnp.asarray(rng.standard_normal(n).astype(np.float32) * s)
                for s in (0.02, 1e-3)] + [jnp.zeros((n,), jnp.float32),
                                          jnp.ones((n,), jnp.bool_)]
        kw = dict(lr=0.2, momentum=0.9, weight_decay=1e-4)
        k_leaves, k_mom = ops.update_unpack(*args, view.offsets,
                                            view.sizes, **kw)
        r_leaves, r_mom = ref.pool_unpack_update(*args, view.offsets,
                                                 view.sizes, **kw)
        for k, r in zip(list(k_leaves) + [k_mom], list(r_leaves) + [r_mom]):
            k, r = np.asarray(k), np.asarray(r)
            diff = float(np.abs(k - r).max(initial=0.0))
            worst = max(worst, diff)
            if diff > 2.0 ** -20 * float(np.abs(r).max(initial=0.0)):
                fail(f"update_unpack on bucket [{start}, {end}) differs "
                     f"from its jnp twin by {diff:.3e}")
    log(f"[kernels] on random inputs, pool_pack (bf16 wire) over the "
        f"{pool.size}-element pool equals its jnp twin bit for bit, and "
        f"update_unpack on each of the "
        f"{len(setting.trainer.gf._lazy_bounds)} buckets is within "
        f"{worst:.3e} of its twin ({time.perf_counter() - t0:.1f} s incl. "
        f"compiles)")


def one_chip(devices) -> None:
    limit = device_limit(devices[0])
    log(f"device {devices[0].device_kind} x{len(devices)}, bytes_limit "
        f"{limit}")
    log(f"[fit] largest global batch whose compiled lazy windows fit "
        f"(search from {BATCH_HINT}):")
    batch, (lazy, twin) = fit_batch(
        lambda b: pair("lazy bf16 wire", "lazy", b, "1x1"), BATCH_HINT,
        limit, BATCH_MIN, BATCH_MAX)
    log(f"  -> global batch {batch} (seq {SEQ_LEN})")
    kernel_exactness(lazy)
    run_setting(lazy, twin_for(lazy, twin),
                {"pool_pack.xla", "update_unpack.kernel"})
    del lazy, twin
    log(f"[fit] csc at global batch {batch}, or the largest below it "
        f"whose compiled windows fit:")
    batch, (csc, twin) = fit_batch(
        lambda b: pair("csc", "csc", b, "1x1"), batch, limit, BATCH_MIN,
        batch)
    run_setting(csc, twin_for(csc, twin),
                {"pool_pack.xla", "update_unpack.kernel",
                 "csc_compact.kernel", "chunk_l1norm.kernel"})


def four_chips(devices) -> None:
    import numpy as np
    from jax.sharding import Mesh
    from repro.parallel.collectives import compat_set_mesh

    if len(devices) < 4:
        fail(f"--chips 4 needs four devices, found {len(devices)}")
    limit = device_limit(devices[0])
    log(f"device {devices[0].device_kind} x{len(devices)}, bytes_limit "
        f"{limit}")
    # Cheapest and newest first: the collectives alone (on a 2x2
    # data x model mesh, where the ring runs inside each model column,
    # and on the trainer's 4x1 mesh), then the ring trainer, then the
    # flat one (same kernels as the one-chip phase).
    ring, twin = pair("lazy pallas_ring x4", "lazy", 4 * PER_CHIP_BATCH,
                      "4x1", algo="pallas_ring")
    lo, hi = ring.trainer.gf._lazy_bounds[0]
    square = Mesh(np.array(devices[:4]).reshape(2, 2), ("data", "model"))
    for mesh in (square, ring.mesh):
        with compat_set_mesh(mesh):
            bucket_check(mesh, hi - lo)
    log(f"[fit] {PER_CHIP_BATCH} per chip in the compiled 4-chip windows:")
    fit_batch(lambda b: (ring, twin), PER_CHIP_BATCH, limit,
              PER_CHIP_BATCH, PER_CHIP_BATCH)
    result = twin_for(ring, twin)
    del twin
    run_setting(ring, result, {"pool_pack.xla", "update_unpack.kernel",
                               "ring_allreduce.kernel"})
    del ring
    flat = Setting("lazy flat x4", train_argv("lazy", 4 * PER_CHIP_BATCH,
                                              "4x1", kernels=True))
    run_setting(flat, result, {"pool_pack.xla", "update_unpack.kernel"})


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: run only the four-chip data-parallel phase")
    args = p.parse_args(argv)

    devices = require_tpu()
    from repro.launch import train
    cache = train.configure_compile_cache()
    log(f"compile cache: {cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(devices)
    else:
        one_chip(devices)
    log(f"total seconds {time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
