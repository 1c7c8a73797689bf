"""End-to-end training driver.

Wires Trainer + synthetic data pipeline + checkpointing + fault-tolerant
supervision + CSC warm-up stage switching into a runnable loop. Scales from
a single CPU device (reduced configs; examples/) to the production mesh
(real deployment) with no code changes — mesh shape and config are flags.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \\
      --reduced --steps 200 --mesh 1x1 --gf-mode csc
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_arch, get_smoke
from repro.configs.base import (GradientFlowConfig, OptimizerConfig,
                                TrainConfig)
from repro.core.schedule import (snap_stages_to_window, stage_at,
                                 stage_first_steps)
from repro.data.pipeline import DataPipeline
from repro.data.synthetic import SyntheticLM
from repro.launch.mesh import make_mesh
from repro.launch.trainer import Trainer
from repro.runtime.fault_tolerance import SupervisorConfig, TrainSupervisor
from repro.parallel.collectives import compat_set_mesh
from repro.parallel.topology import REGISTRY


class ThroughputMeter:
    """tok/s over steps executed in THIS process, with the first
    completed window (the one that pays compilation) excluded: the clock
    starts when that window finishes. Fixes the two historical log lies
    — a resumed run crediting itself with the pre-resume steps
    (``(step + 1) * batch * seq`` from a clock started this process),
    and the compile time of step 0 folded into every later rate. The
    clock is ``time.perf_counter``, which never steps."""

    def __init__(self, tokens_per_step: float):
        self.tokens_per_step = tokens_per_step
        self._t0: Optional[float] = None
        self._steps = 0

    def note(self, n_steps: int, now: Optional[float] = None) -> None:
        """Record ``n_steps`` just finished."""
        now = time.perf_counter() if now is None else now
        if self._t0 is None:
            self._t0 = now  # first (compile) window only starts the clock
        else:
            self._steps += n_steps

    def rate(self, now: Optional[float] = None) -> Optional[float]:
        """tok/s, or None until any post-compile step has finished."""
        if self._t0 is None or self._steps == 0:
            return None
        now = time.perf_counter() if now is None else now
        return self._steps * self.tokens_per_step / (now - self._t0)


def build(args):
    cfg_fn = get_smoke if args.reduced else get_arch
    model_cfg, rules = cfg_fn(args.arch)
    shape = tuple(int(x) for x in args.mesh.split("x"))
    axes = ("data", "model")[:len(shape)] if len(shape) <= 2 else \
        ("pod", "data", "model")
    mesh = make_mesh(shape, axes)

    gf = GradientFlowConfig(
        mode=args.gf_mode, bucket_elems=args.bucket_elems,
        chunk_elems=args.chunk_elems, sparsity=args.sparsity,
        momentum=args.momentum, warmup_steps=args.csc_warmup,
        warmup_stages=4, use_kernels=args.use_kernels,
        collective_algo=args.collective_algo,
        wire_format=args.wire_format,
        error_feedback=not args.no_error_feedback)
    opt = OptimizerConfig(
        name=args.optimizer, learning_rate=args.lr, momentum=args.momentum,
        warmup_steps=max(args.steps // 20, 1), total_steps=args.steps,
        schedule="warmup_cosine")
    cfg = TrainConfig(model=model_cfg, gradientflow=gf, optimizer=opt,
                      seq_len=args.seq_len, global_batch=args.batch,
                      attn_chunk=args.attn_chunk, seed=args.seed,
                      window_steps=args.window_steps)
    return Trainer(cfg, mesh, rules), cfg, mesh


def _parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--reduced", action="store_true",
                   help="use the smoke-scale config")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--mesh", default="1x1")
    p.add_argument("--gf-mode", default="csc",
                   choices=["dense", "lazy", "csc"])
    p.add_argument("--sparsity", type=float, default=0.85)
    p.add_argument("--chunk-elems", type=int, default=2048)
    p.add_argument("--bucket-elems", type=int, default=1 << 22)
    p.add_argument("--csc-warmup", type=int, default=20)
    p.add_argument("--optimizer", default="momentum_sgd",
                   choices=["momentum_sgd", "lars", "adamw"])
    p.add_argument("--lr", type=float, default=0.2)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--attn-chunk", type=int, default=0)
    p.add_argument("--use-kernels", action="store_true")
    p.add_argument("--collective-algo", default="auto",
                   choices=sorted(REGISTRY) + ["auto"],
                   help="data-parallel reduction per bucket; 'auto' "
                        "picks from the cost model")
    p.add_argument("--wire-format", default="native",
                   choices=["native", "int8", "fp8_e4m3"],
                   help="low-bit wire with per-chunk scales; 'native' "
                        "keeps the plain wire_dtype cast")
    p.add_argument("--no-error-feedback", action="store_true",
                   help="drop the quantization-error residual "
                        "(ablation; biased wire)")
    p.add_argument("--window-steps", type=int, default=8,
                   help="K: steps per compiled lax.scan window (one XLA "
                        "program, one host sync per window); 1 = per-step "
                        "dispatch")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--ckpt-dir", default=None,
                   help="default: a fresh temp dir (pass a path to resume)")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--log-every", type=int, default=10)
    return p


def configure_compile_cache() -> str:
    """Persistent compilation cache, one rule for every entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives at ONE fixed path in
    the checkout, ``<checkout>/.jax_cache`` (git ignores it): the path is
    part of the cache key, so a path that moved between runs would never
    hit. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def window_sharding(trainer: Trainer, batch) -> object:
    """Shardings of a window's stacked batch: the leading scan axis
    replicated, the batch dim split over the data axes exactly as the
    step's ``in_specs`` expect (``Trainer.batch_pspec``)."""
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(trainer.mesh, P(None, *spec)),
        trainer.batch_pspec(batch),
        is_leaf=lambda x: isinstance(x, P))


class WindowLoop:
    """The compile-once training loop's ``window_fn`` for
    ``TrainSupervisor.run_windows``: one compiled program per (CSC stage,
    window length), batches fetched BY STEP INDEX (a supervisor replay
    re-reads exactly the batches the failed attempt saw), stacked on the
    leading scan axis and placed with the data sharding, and ONE host
    sync per window (the stacked [length] losses).

    Stage boundaries are snapped to the window grid: no K-step window
    ever straddles a sparsity stage, so each stage costs exactly one
    compiled window executable (snapping can shadow a warm-up stage
    entirely — those are never built). ``lower_seconds`` and
    ``compile_seconds`` record each ahead-of-time trace+lowering and
    compile (the part a persistent-cache hit skips), ``window_seconds``
    each window's wall time after its compile."""

    def __init__(self, trainer: Trainer, pipe: DataPipeline, window: int,
                 log_every: int = 0):
        self.trainer = trainer
        self.pipe = pipe
        self.window = max(window, 1)
        self.stages = snap_stages_to_window(trainer.gf.stages, self.window)
        self.firsts = stage_first_steps(self.stages)
        self.log_every = log_every
        self.executables = {}
        self.lower_seconds = {}
        self.compile_seconds = {}
        self.window_seconds = []
        self.losses = []
        self.meter = ThroughputMeter(trainer.cfg.global_batch
                                     * trainer.cfg.seq_len)

    def executable(self, stage, state, batches):
        key = (stage.index, jax.tree_util.tree_leaves(batches)[0].shape[0])
        if key not in self.executables:
            t0 = time.perf_counter()
            lowered = self.trainer.build_train_window(
                self.window, stage=stage).lower(state, batches)
            t1 = time.perf_counter()
            self.executables[key] = lowered.compile()
            self.lower_seconds[key] = t1 - t0
            self.compile_seconds[key] = time.perf_counter() - t1
        return self.executables[key]

    def stage_at(self, step: int):
        return stage_at(self.stages, step, self.firsts)

    def batches(self, step: int, length: int):
        """The window's batches, stacked and placed with the data
        sharding. Host spans: ``repro.loop.batches`` around it all, with
        the pipeline's ``repro.data.get`` waits and ``repro.loop.place``
        (stack and ``device_put``) inside."""
        with jax.profiler.TraceAnnotation("repro.loop.batches"):
            batches = [self.pipe.next_at(step + i) for i in range(length)]
            with jax.profiler.TraceAnnotation("repro.loop.place"):
                return jax.device_put(
                    jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                           *batches),
                    window_sharding(self.trainer, batches[0]))

    def __call__(self, step: int, length: int, state):
        stage = self.stage_at(step)
        stacked = self.batches(step, length)
        exe = self.executable(stage, state, stacked)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("repro.loop.dispatch"):
            state, metrics = exe(state, stacked)
        with jax.profiler.TraceAnnotation("repro.loop.loss_read"):
            win_losses = np.asarray(metrics["loss"], np.float32)
        self.window_seconds.append(time.perf_counter() - t0)
        self.losses.extend(float(x) for x in win_losses)
        self.meter.note(length)
        due = [s for s in range(step, step + length)
               if self.log_every and s % self.log_every == 0]
        if due:
            s = due[-1]
            tok_s = self.meter.rate()
            tail = f"({tok_s:,.0f} tok/s)" if tok_s is not None \
                else "(compiling)"
            print(f"step {s:5d} stage {stage.index} "
                  f"sparsity {stage.sparsity:.2f} "
                  f"loss {win_losses[s - step]:.4f} {tail}")
        return state


def main(argv=None):
    args = _parser().parse_args(argv)
    configure_compile_cache()

    trainer, cfg, mesh = build(args)
    data = SyntheticLM(cfg.model.vocab_size, seed=args.seed,
                       num_codebooks=cfg.model.num_codebooks)
    pipe = DataPipeline(data, cfg.global_batch, cfg.seq_len)
    ckpt_dir = args.ckpt_dir
    if ckpt_dir is None:
        import tempfile
        ckpt_dir = tempfile.mkdtemp(prefix="repro_ckpt_")
    ckpt = CheckpointManager(ckpt_dir, keep=3)
    sup = TrainSupervisor(ckpt, SupervisorConfig(
        checkpoint_every=args.ckpt_every))

    K = max(cfg.window_steps, 1)
    with compat_set_mesh(mesh):
        state = trainer.init_state(jax.random.PRNGKey(args.seed))
        t_wall = time.time()
        loop = WindowLoop(trainer, pipe, K, log_every=args.log_every)
        losses = loop.losses

        # `is not None`, not truthiness: a checkpoint saved at step 0 is
        # a real checkpoint and must restore (latest_step() is None only
        # when the directory holds no checkpoint at all).
        start = ckpt.latest_step()
        if start is not None:
            start, state = ckpt.restore(state)
            print(f"resumed from checkpoint step {start}")
        else:
            start = 0
        if start >= args.steps:
            print(f"nothing to do: restored step {start} >= "
                  f"--steps {args.steps}")
            return losses
        pipe.start(start)
        state = sup.run_windows(state, start, args.steps, loop, K,
                                on_restore=pipe.skip_to)
        pipe.stop()
        print(f"done: final loss {losses[-1]:.4f} "
              f"(start {losses[0]:.4f}) in {time.time()-t_wall:.1f}s")
        return losses


if __name__ == "__main__":
    main()
