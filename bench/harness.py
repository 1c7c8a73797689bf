"""One run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (``setup_s``, from process start to the first timed step): build
the cell's job through the program's own construction
(``repro.launch.train.build`` with the configuration file's model
block in place of the registered ModelConfig's keys), make the weights on the device
from the seed, start the program's ``DataPipeline`` on the benchmark's
token source, and run the first window through ``WindowLoop`` — which
traces, lowers and compiles the window program (the persistent cache
skips the compile) and drives steps 0..K-1 from the seed. What the
comparison reads of that window is taken now.

Then windows of K steps run back to back for ``--seconds``; the rate is
all tokens of all steps completed over the whole measured time. With
``--trace 1`` one more window runs under the profiler and the per-layer
metrics are read from it (from another, where the profiler dropped
events). Last, with the program's state freed, the plain
reference runs the first window's steps and ``bench.check`` decides
``correct``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
import unittest.mock
from typing import Dict, List, Optional

from bench import check, counts, devices as devices_mod, reference
from bench import tracing, traffic as traffic_mod
from bench.spec import ROOT, Spec

sys.path.insert(0, os.path.join(ROOT, "src"))


TRACE_TRIES = 3


class NoChip(Exception):
    """JAX finds no accelerator the cell can run on."""


def process_start() -> float:
    """Wall-clock time at which this process started (10 ms ticks)."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))


def parse(argv: List[str]) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def job_argv(config: Dict, traffic: Dict) -> List[str]:
    """The training job of a cell as ``repro.launch.train``'s flags."""
    gf, opt = traffic["gradientflow"], traffic["optimizer"]
    argv = ["--arch", config["arch"],
            "--seq-len", str(traffic["seq_len"]),
            "--batch", str(traffic["global_batch"]),
            "--mesh", traffic["mesh"],
            "--window-steps", str(traffic["window_steps"]),
            "--attn-chunk", str(traffic["attn_chunk"]),
            "--gf-mode", gf["mode"],
            "--sparsity", str(gf["sparsity"]),
            "--chunk-elems", str(gf["chunk_elems"]),
            "--csc-warmup", str(gf["csc_warmup"]),
            "--collective-algo", gf["collective_algo"],
            "--wire-format", gf["wire_format"],
            "--optimizer", opt["name"],
            "--lr", repr(opt["lr"]),
            "--momentum", repr(opt["momentum"]),
            "--steps", str(opt["schedule_steps"])]
    return argv + (["--use-kernels"] if gf["use_kernels"] else [])


def build(config: Dict, traffic: Dict):
    """(trainer, TrainConfig, mesh) of the cell, through
    ``repro.launch.train.build``. The file's ``model`` block states the
    ModelConfig as it runs: its keys replace those of the registered
    ``arch``, whose model code and sharding rules run."""
    from repro.configs import get_arch
    from repro.launch import train

    base, rules = get_arch(config["arch"])
    model = dataclasses.replace(base, **config["model"])
    args = train._parser().parse_args(job_argv(config, traffic))
    with unittest.mock.patch.object(train, "get_arch",
                                    lambda arch: (model, rules)):
        trainer, cfg, mesh = train.build(args)
    opt = traffic["optimizer"]
    stated = {"weight_decay": opt["weight_decay"],
              "schedule": "warmup_cosine",
              "warmup_steps": max(opt["schedule_steps"] // 20, 1)}
    for key, want in stated.items():
        if getattr(cfg.optimizer, key) != want:
            raise ValueError(f"the job runs {key} "
                             f"{getattr(cfg.optimizer, key)!r}, the "
                             f"reference follows {want!r}")
    return trainer, cfg, mesh


def require_devices(chips: int):
    """The devices of the cell, or NoChip."""
    import jax
    devs = jax.devices()
    try:
        devices_mod.peaks_for(devs[0].platform, devs[0].device_kind)
    except devices_mod.UnknownDevice as e:
        raise NoChip(str(e)) from None
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{len(devs)}")
    return devs


def configure_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else the checkout's fixed ``.jax_cache``."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class _Dispatch:
    """A compiled window whose call is a host span."""

    def __init__(self, exe):
        self.exe = exe

    def __call__(self, *args):
        import jax
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            return self.exe(*args)


def _span_batches(loop) -> None:
    import jax
    inner = loop.batches

    def batches(step, length):
        with jax.profiler.TraceAnnotation("bench.batches"):
            return inner(step, length)
    loop.batches = batches


def _window(loop, step, length, state):
    import jax
    with jax.profiler.TraceAnnotation("bench.window"):
        return loop(step, length, state)


def snapshot(trainer, state, p0, mesh) -> Dict:
    """What the comparison reads of the program after its first window:
    per leaf (in the params' flatten order), the norm of the params'
    change, of the momentum and, under CSC, of the historical gradients;
    the chunks the last step selected (those whose hg it zeroed); on more
    than one chip, the spread of the params' replicas."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    names = reference.leaf_names(state.params)
    spans = {s.name: (s.offset, s.size) for s in trainer.pool.specs}
    csc = trainer.gf_cfg.csc_enabled
    chunk = trainer.gf_cfg.chunk_elems
    (mom,) = jax.tree_util.tree_leaves(state.opt)

    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    def pool_norms(pool):
        return [norm(jax.lax.dynamic_slice_in_dim(pool, *spans[n]))
                for n in names]

    def fn(params, p0, mom, hg):
        out = {"update": [norm(a - b) for a, b in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(p0))],
            "momentum": pool_norms(mom)}
        if csc:
            out["hg"] = pool_norms(hg[0])
            out["selected"] = jnp.all(hg[0].reshape(-1, chunk) == 0,
                                      axis=1)
        return out

    hg = state.gf.hg if csc else jnp.zeros((1, 0), jnp.float32)
    got = jax.jit(fn)(state.params, p0, mom, hg)
    out = {"names": names,
           "update": [float(x) for x in got["update"]],
           "momentum": [float(x) for x in got["momentum"]]}
    if csc:
        out["hg"] = [float(x) for x in got["hg"]]
        out["selected"] = [bool(x) for x in got["selected"]]
    if mesh.devices.size > 1:
        axes = tuple(mesh.axis_names)

        def spread(x):
            # Against the first replica, broadcast by a psum of zeros
            # elsewhere: exact, so equal replicas read exactly 0.
            x = x.astype(jnp.float32)
            first = jax.lax.psum(
                jnp.where(jax.lax.axis_index(axes) == 0, x, 0.0), axes)
            return jnp.sqrt(jax.lax.psum(
                jnp.sum(jnp.square(x - first)), axes))
        fn_rep = jax.jit(jax.shard_map(
            lambda t: [spread(x) for x in jax.tree_util.tree_leaves(t)],
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
        out["replicas"] = [float(x) for x in fn_rep(state.params)]
    return out


def memory_peak_bytes(devs) -> Optional[int]:
    """Peak of the fullest chip: bytes in use plus bytes the runtime
    reserved for programs' temporaries, at their peaks."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            return None
        peaks.append(stats["peak_bytes_in_use"]
                     + stats.get("peak_bytes_reserved", 0))
    return max(peaks)


class Job:
    """A cell's training job, built once: the trainer, its mesh, and the
    jitted maker of the seed's weights. ``first_window(seed)`` starts a
    run from the seed; every run of one Job shares the compiled window."""

    def __init__(self, config: Dict, traffic: Dict):
        import jax
        from repro.parallel.collectives import compat_set_mesh

        self.config, self.traffic = config, traffic
        self.trainer, self.cfg, self.mesh = build(config, traffic)
        self.K = traffic["window_steps"]
        rmodel = reference.reference_model(config)
        mod = reference.model_module(config)

        def is_shape(x):
            return isinstance(x, tuple)
        with compat_set_mesh(self.mesh):
            want = jax.tree_util.tree_map(
                lambda s: tuple(s.shape), self.trainer.abstract_state().params)
        have = mod.param_shapes(rmodel)
        if jax.tree_util.tree_structure(want, is_leaf=is_shape) != \
                jax.tree_util.tree_structure(have, is_leaf=is_shape) or \
                want != have:
            raise ValueError(f"the trainer's params {want} are not the "
                             f"reference's {have}")
        self.make = jax.jit(lambda k: mod.init_params(rmodel, k),
                            out_shardings=self.trainer.param_shardings)
        self.executables, self.lower_seconds, self.compile_seconds = \
            {}, {}, {}

    def first_window(self, seed: int):
        """(state, loop, pipeline, snapshot) after the window of steps
        0..K-1 from ``seed``, run through the window's own call. The
        pipeline is running; the caller stops it."""
        from repro.data.pipeline import DataPipeline
        from repro.launch import train

        key = reference.seed_key(seed)
        state = self.trainer.init_state(key)
        state = state._replace(params=self.make(key))
        source = traffic_mod.source(
            self.traffic, self.config["model"]["vocab_size"], seed)
        pipe = DataPipeline(source, self.cfg.global_batch, self.cfg.seq_len)
        loop = train.WindowLoop(self.trainer, pipe, self.K)
        loop.executables = self.executables
        loop.lower_seconds = self.lower_seconds
        loop.compile_seconds = self.compile_seconds
        _span_batches(loop)
        pipe.start(0)
        try:
            state = _window(loop, 0, self.K, state)
            prog = snapshot(self.trainer, state, self.make(key), self.mesh)
        except BaseException:
            pipe.stop()
            raise
        prog["losses"] = list(loop.losses[:self.K])
        return state, loop, pipe, prog


def main(argv: List[str], *, root: str = ROOT, require_chip: bool = True,
         started: Optional[float] = None) -> int:
    started = process_start() if started is None else started
    args = parse(argv)
    spec = Spec(root)
    work = spec.workload(args.workload)
    config = spec.config(work["config"])
    traffic = spec.traffic(work["traffic"])
    limits = spec.limits(work["name"])
    chips = work["chips"]

    import jax
    try:
        devs = require_devices(chips) if require_chip else jax.devices()
    except NoChip as e:
        print(f"bench: no result: {e}", file=sys.stderr, flush=True)
        return 3
    configure_cache(root)
    from repro.parallel.collectives import compat_set_mesh

    job = Job(config, traffic)
    K = job.K
    used = list(job.mesh.devices.flat)
    tokens_per_step = job.cfg.global_batch * job.cfg.seq_len
    with compat_set_mesh(job.mesh):
        state, loop, pipe, prog = job.first_window(args.seed)
        try:
            for k, exe in list(loop.executables.items()):
                loop.executables[k] = _Dispatch(exe)
            step = K
            setup_s = time.time() - started

            t0 = time.perf_counter()
            windows = 0
            while True:
                state = _window(loop, step, K, state)
                step += K
                windows += 1
                elapsed = time.perf_counter() - t0
                if elapsed + elapsed / windows > args.seconds:
                    break
            window_losses = loop.losses[K:]
            rate = windows * K * tokens_per_step / elapsed / chips
            peak = memory_peak_bytes(used)

            per_layer, dev_extra, brk = {}, {}, None
            if args.trace:
                per_layer, dev_extra, brk = traced_window(
                    spec, work, loop, step, K, state, rate,
                    elapsed / windows, config, traffic, job.trainer, devs,
                    chips)
        finally:
            pipe.stop()
        del state
    lowered, compiled = dict(job.lower_seconds), dict(job.compile_seconds)
    del loop, pipe, job
    gc.collect()

    t_ref = time.perf_counter()
    ref = reference.Reference(config, traffic).run(args.seed, K)
    ref_s = time.perf_counter() - t_ref
    nums = check.numbers(prog, ref)
    correct, rows = check.judge(nums, limits)
    failed = int(sum(not math.isfinite(x) for x in window_losses))
    correct = correct and failed == 0

    metrics = {}
    if args.trace:
        metrics = per_layer
    else:
        e2e = {"tokens_per_s_per_chip": (rate, "tokens/s"),
               "setup_s": (setup_s, "s")}
        if peak is not None:
            e2e["hbm_peak_gb"] = (peak / 1e9, "GB")
        for m in spec.end_to_end(work["name"]):
            if m["name"] in e2e:
                value, unit = e2e[m["name"]]
                metrics[m["name"]] = {"value": value, "unit": unit}
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(used), "memory_peak_bytes": peak, **dev_extra}
    result = {"correct": correct, "attempted": len(window_losses),
              "failed": failed, "metrics": metrics, "device": device}
    if brk is not None:
        result["breakdown"] = brk
    result["checks"] = {n: {"value": v, "limit": l} for n, v, l in rows}

    err = sys.stderr
    print(f"bench: {work['name']} seed {args.seed}: {windows} windows of "
          f"{K} steps in {elapsed:.3f} s; set-up {setup_s:.3f} s "
          f"(lowering {lowered}, compile {compiled}); reference "
          f"{ref_s:.1f} s; first-window losses {prog['losses']} vs "
          f"reference {ref['losses']}; window losses {window_losses}",
          file=err)
    for n, v, l in rows:
        print(f"check {n} {v!r} limit {l!r}", file=err)
    err.flush()
    print(json.dumps(result), flush=True)
    return 0


def traced_window(spec, work, loop, step, K, state, rate, window_s, config,
                  traffic, trainer, devs, chips):
    """One more window under the profiler (another where the trace came
    back damaged); the per-layer metrics, the device's busy and window
    seconds, and the breakdown. ``window_s``: an untraced window's
    seconds."""
    import jax

    def sound(trace):
        return tracing.complete(trace, K) and tracing.steady(trace, window_s)

    # The profiler can drop a stretch of events or stall the device; trace
    # another window when it did, at most TRACE_TRIES in all.
    for attempt in range(TRACE_TRIES):
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            jax.profiler.start_trace(tmp)
            try:
                state = _window(loop, step, K, state)
            finally:
                jax.profiler.stop_trace()
            trace = tracing.compact(tracing.newest_xplane(tmp))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        step += K
        if sound(trace):
            break
    print(f"bench: traced {attempt + 1} window(s) of {window_s:.3f} s "
          f"untraced; the last took {tracing.window_s(trace):.3f} s and is "
          f"{'sound' if sound(trace) else 'DAMAGED'}", file=sys.stderr)
    trace["ops"] = {}
    for exe in loop.executables.values():
        exe = getattr(exe, "exe", exe)
        trace["ops"].update(tracing.hlo_opcodes(exe.as_text()))
    rmodel = reference.reference_model(config)
    n = counts.param_count(
        reference.model_module(config).param_shapes(rmodel))
    gf = dict(traffic["gradientflow"],
              wire_dtype=trainer.gf_cfg.wire_dtype)
    ctx = {"trace": trace, "steps": K, "tokens_per_s_per_chip": rate,
           "flops_per_token": counts.flops_per_token(
               config["model"], traffic["seq_len"], n),
           "peaks": devices_mod.peaks_for(devs[0].platform,
                                          devs[0].device_kind),
           "chips": chips,
           "kernel_bytes": counts.pool_kernel_bytes(n, gf)
           if gf["use_kernels"] else {}}
    out = {}
    for m in spec.per_layer(work["name"]):
        value = spec.reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    extra = {"busy_s": tracing.busy_s(trace),
             "window_s": tracing.window_s(trace)}
    return out, extra, tracing.breakdown(trace)
