"""Measured micro-benchmarks (CPU wall time) for the pool-space hot path:
ravel/unravel, bucket slicing, CSC select/compact/scatter, kernels (interp)
vs refs, fused update. These are the operations GradientFlow adds on top of
the collectives — the paper's 'minimal GPU memory copy overhead' claim
(§3.1) corresponds to these staying trivially cheap vs the wire time.

``pool_pipeline`` additionally compares the legacy ravel+cast+norm chain
against the single-pass pack on an AlexNet-sized pool, counting HLO
concatenate/dynamic-slice/copy ops and wall time, and emits
``BENCH_pool.json`` so CI (and future PRs) can detect copy-op regressions:

    python benchmarks/micro.py --pool-json BENCH_pool.json   # refresh baseline
    python benchmarks/micro.py --pool-check                  # CI gate

``--kernel-check`` gates the streaming tiled pack/unpack kernels the same
way against ``BENCH_kernels.json``: it re-validates kernel-vs-ref
equivalence on a >4M-element pool (past the retired whole-pool-in-VMEM
bound) and pins the streaming property itself — tile count, peak
VMEM-resident bytes (must stay O(tile), never O(pool)), and the static
copy-schedule size — so the kernels cannot silently regress to
pool-resident variants. The same gate covers the ring allreduce behind
``pallas_ring`` on an 8-rank placeholder CPU mesh: ring-vs-psum max
error (f32 and bf16 wire), the executed neighbor-exchange count vs the
planned 2(N-1) ``exchange_steps`` (and zero hidden psums), and the
ragged-pool ``wire_bytes_per_step`` segmentation. ``--kernel-json``
refreshes the baseline (adds wall time, informational only).

This module must import clean with no dev extras installed (the CI bench
jobs run ``pip install -e .`` without ``[dev]`` and assert exactly that):
runtime deps only — jax + numpy + repro.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _harness

from repro.core import csc
from repro.core.pool import GradientPool
from repro.kernels import ops, ref

CHUNK = 32768
# src path handed to the placeholder-mesh subprocess scripts.
_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")

# AlexNet's gradient tensors — the paper's headline workload; single
# source of truth in repro.configs.shapes (shared with the dryrun
# timeline so the gated benchmark and the rendered table can never
# model different pools).
from repro.configs.shapes import ALEXNET_GRAD_SHAPES  # noqa: E402


def timeit(fn: Callable, *args, warmup: int = 2, iters: int = 5) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def run() -> List[Dict]:
    rows = []
    params = {f"t{i}": jnp.zeros((s,), jnp.float32)
              for i, s in enumerate([4_000_000, 1_000_000, 250_000,
                                     60_000, 4_096])}
    pool = GradientPool(params, pad_to=CHUNK)
    grads = jax.tree_util.tree_map(
        lambda x: jnp.ones_like(x), params)

    ravel = jax.jit(lambda g: pool.ravel(g))
    rows.append({"name": "pool_ravel_5.3M", "us": timeit(ravel, grads),
                 "derived": f"{pool.size} elems"})
    flat = ravel(grads)
    unravel = jax.jit(lambda p: pool.unravel(p))
    rows.append({"name": "pool_unravel_5.3M", "us": timeit(unravel, flat),
                 "derived": ""})

    n_chunks = pool.size // CHUNK
    idx = jnp.arange(0, n_chunks, 4, dtype=jnp.int32)
    l1_ref = jax.jit(lambda p: ref.chunk_l1norm(p, CHUNK))
    rows.append({"name": "chunk_l1norm_ref", "us": timeit(l1_ref, flat),
                 "derived": f"{n_chunks} chunks"})
    rows.append({"name": "chunk_l1norm_kernel(interp)",
                 "us": timeit(lambda p: ops.chunk_l1norm(p, CHUNK), flat),
                 "derived": "CPU interpret mode"})
    comp_ref = jax.jit(lambda p, i: ref.csc_compact(p, i, CHUNK))
    rows.append({"name": "csc_compact_ref", "us": timeit(comp_ref, flat,
                                                         idx),
                 "derived": f"k={idx.shape[0]}"})

    mom = jnp.zeros_like(flat)
    mask = jnp.ones(flat.shape, bool)
    upd = jax.jit(lambda m, g, mo, ma: ref.fused_update(
        m, g, mo, ma, lr=0.1, momentum=0.9, weight_decay=1e-4))
    rows.append({"name": "fused_update_ref",
                 "us": timeit(upd, flat, flat, mom, mask), "derived": ""})

    sel = jax.jit(lambda n: csc.select_chunks(n, max(n_chunks // 8, 1)))
    norms = jnp.arange(float(n_chunks))
    rows.append({"name": "csc_select_topk", "us": timeit(sel, norms),
                 "derived": f"top-{max(n_chunks // 8, 1)}"})
    return rows


# -- pool-pipeline benchmark (single-pass pack vs legacy chain) -------------

_HLO_OPS = ("concatenate", "dynamic-slice", "dynamic-update-slice", "copy")


def hlo_op_counts(fn: Callable, *args, donate=()) -> Dict[str, int]:
    """Counts of copy-class ops + total ops in the optimized HLO of
    ``jit(fn)(*args)`` (includes ops inside fusion computations)."""
    text = jax.jit(fn, donate_argnums=donate).lower(
        *args).compile().as_text()
    counts = {op: len(re.findall(rf"= [^\s]+ {op}\(", text))
              for op in _HLO_OPS}
    counts["total_ops"] = len(re.findall(r"^\s+(?:ROOT )?%?\S+ = ", text,
                                         re.M))
    return counts


def _legacy_ravel_cast_norm(grads, pool: GradientPool, wire_dtype):
    """The pre-pipeline data path, kept verbatim as the benchmark baseline:
    pass 1 builds the pool from a reshape+concatenate chain, pass 2 casts
    to the wire dtype, pass 3 reads everything again for the chunk-L1
    census."""
    flat = [leaf.reshape((-1,))
            for leaf in reversed(jax.tree_util.tree_leaves(grads))]
    if pool.padding:
        flat.append(jnp.zeros((pool.padding,), flat[-1].dtype))
    p = jnp.concatenate(flat)              # pass 1: gather
    p = p.astype(wire_dtype)               # pass 2: wire cast
    norms = csc.chunk_l1_norms(p, CHUNK)   # pass 3: census
    return p, norms


def pool_pipeline(measure_time: bool = True) -> Dict:
    """Legacy chain vs fused single-pass pack on the AlexNet-sized pool.

    The fused path runs the production shape: the staging pool is threaded
    through a donated jit argument (zero-filled once, then written fully
    in place every step), exactly as a steady-state train step donates its
    pool-form state."""
    grads = {f"t{i}": jnp.ones(s, jnp.float32)
             for i, s in enumerate(ALEXNET_GRAD_SHAPES)}
    pool = GradientPool(grads, pad_to=CHUNK)
    wire = jnp.bfloat16

    legacy = lambda g: _legacy_ravel_cast_norm(g, pool, wire)

    def fused(staging, g):
        p, norms, staging = pool.pack_into(staging, g, dtype=wire,
                                           norms_chunk=CHUNK)
        return p, norms, staging

    staging0 = jnp.zeros((pool.size,), jnp.float32)
    result = {
        "workload": "alexnet",
        "pool_elems": pool.size,
        "num_tensors": pool.num_tensors,
        "wire_dtype": "bfloat16",
        "jax_version": jax.__version__,
        "legacy": hlo_op_counts(legacy, grads),
        "fused": hlo_op_counts(fused, staging0, grads, donate=(0,)),
    }
    if measure_time:
        result["legacy"]["wall_us"] = timeit(jax.jit(legacy), grads,
                                             warmup=1, iters=5)
        jf = jax.jit(fused, donate_argnums=(0,))
        staging = staging0
        _, _, staging = jax.block_until_ready(jf(staging, grads))  # warmup
        t0 = time.perf_counter()
        iters = 5
        for _ in range(iters):
            _, _, staging = jf(staging, grads)
        jax.block_until_ready(staging)
        result["fused"]["wall_us"] = (time.perf_counter() - t0) / iters * 1e6
    return result


# -- streaming kernel benchmark (tile count / VMEM residency gate) ----------

# >4M elements — past the retired 4M whole-pool-in-VMEM bound — with odd
# tensor sizes so segments straddle tile boundaries in the copy schedule.
KERNEL_BENCH_SHAPES = [
    (1024, 1024), (1536, 1024), (999, 777), (640_000,),
    (131_072,), (50_000,), (4096,), (1000,), (31,),
]


def kernel_bench(measure_time: bool = True) -> Dict:
    """Streaming tiled pack/unpack vs the ref oracles on a >4M pool.

    Records the properties the CI gate pins: kernel/ref equivalence, tile
    count (>1 = actually streaming), static copy-schedule size, and the
    analytic peak VMEM-resident bytes (O(tile), pool-size independent).
    Wall time is recorded for trend-watching but never gated (interpret
    mode on CPU is not the production execution model)."""
    from repro.kernels import pool_pack as pp_mod
    from repro.kernels import pool_unpack as pu_mod

    grads = {f"t{i}": jnp.ones(s, jnp.float32)
             for i, s in enumerate(KERNEL_BENCH_SHAPES)}
    pool = GradientPool(grads, pad_to=CHUNK)
    assert pool.size > 4 * 1024 * 1024, pool.size
    leaves = pool.flat_leaves(grads)
    key = jax.random.PRNGKey(0)
    leaves = [jax.random.normal(k, x.shape)
              for k, x in zip(jax.random.split(key, len(leaves)), leaves)]

    pack_plan = pp_mod.plan(pool.offsets, pool.sizes, pool.size, CHUNK,
                            jnp.float32, jnp.bfloat16)
    k_pack = lambda: ops.pool_pack(leaves, pool.offsets, pool.sizes,
                                   pool.size, CHUNK, jnp.bfloat16)
    counts_before = dict(ops.dispatch_counts)
    got_p, got_n, _ = k_pack()
    want_p, want_n, _ = ref.pool_pack(leaves, pool.offsets, pool.size,
                                      CHUNK, jnp.bfloat16)
    norms_err = float(jnp.max(jnp.abs(got_n - want_n) /
                              jnp.maximum(jnp.abs(want_n), 1e-6)))
    def took_kernel_path(name, before):
        """ops counts its kernel/ref decision in python at call time —
        this is the proof the streaming kernel is the path actually
        dispatched (output equality alone can't tell: ref == kernel)."""
        kern = ops.dispatch_counts.get(f"{name}.kernel", 0) \
            - before.get(f"{name}.kernel", 0)
        fell_back = ops.dispatch_counts.get(f"{name}.ref", 0) \
            - before.get(f"{name}.ref", 0)
        return kern > 0 and fell_back == 0

    pack_row = {
        "tile_elems": pack_plan["tile_elems"],
        "num_tiles": pack_plan["num_tiles"],
        "num_copies": pack_plan["num_copies"],
        "vmem_bytes": pack_plan["vmem_bytes"],
        "kernel_dispatched": took_kernel_path("pool_pack", counts_before),
        "pool_exact": bool(jnp.array_equal(got_p, want_p)),
        "norms_rel_err": norms_err,
    }

    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    master = jax.random.normal(ks[0], (pool.size,))
    rgrads = jax.random.normal(ks[1], (pool.size,))
    mom = jax.random.normal(ks[2], (pool.size,))
    mask = jax.random.bernoulli(ks[3], 0.5, (pool.size,))
    ratios = jnp.abs(jax.random.normal(jax.random.PRNGKey(2),
                                       (pool.num_tensors,))) + 0.1
    upd_plan = pu_mod.plan(pool.offsets, pool.sizes, pool.size,
                           jnp.float32, has_ratios=True)
    kw = dict(lr=0.05, momentum=0.9, weight_decay=1e-4, ratios=ratios)
    k_upd = lambda: ops.update_unpack(master, rgrads, mom, mask,
                                      pool.offsets, pool.sizes, **kw)
    counts_before_upd = dict(ops.dispatch_counts)
    got_l, got_m = k_upd()
    want_l, want_m = ref.pool_unpack_update(master, rgrads, mom, mask,
                                            pool.offsets, pool.sizes, **kw)
    leaf_err = max(float(jnp.max(jnp.abs(g - w)))
                   for g, w in zip(got_l, want_l))
    upd_row = {
        "tile_elems": upd_plan["tile_elems"],
        "num_tiles": upd_plan["num_tiles"],
        "num_copies": upd_plan["num_copies"],
        "vmem_bytes": upd_plan["vmem_bytes"],
        "kernel_dispatched": took_kernel_path("update_unpack",
                                              counts_before_upd),
        # Not gated bit-exact: XLA may fuse the multiply-adds differently
        # in the two graphs; the test-suite tolerance (1e-6) applies.
        "mom_max_abs_err": float(jnp.max(jnp.abs(got_m - want_m))),
        "leaves_max_abs_err": leaf_err,
    }

    if measure_time:
        pack_row["wall_us_kernel"] = timeit(lambda: k_pack()[0], warmup=1,
                                            iters=3)
        pack_row["wall_us_ref"] = timeit(
            jax.jit(lambda ls: ref.pool_pack(ls, pool.offsets, pool.size,
                                             CHUNK, jnp.bfloat16)[0]),
            leaves, warmup=1, iters=3)
        upd_row["wall_us_kernel"] = timeit(lambda: k_upd()[1], warmup=1,
                                           iters=3)
        upd_row["wall_us_ref"] = timeit(
            jax.jit(lambda m, g, mo, ma: ref.pool_unpack_update(
                m, g, mo, ma, pool.offsets, pool.sizes, **kw)[1]),
            master, rgrads, mom, mask, warmup=1, iters=3)
    return {
        "workload": "straddle_4M",
        "pool_elems": pool.size,
        "num_tensors": pool.num_tensors,
        "chunk_elems": CHUNK,
        "jax_version": jax.__version__,
        "pack": pack_row,
        "unpack": upd_row,
        "ring": ring_bench(),
        "wire": wire_bench(),
    }


# -- ring allreduce gate (pallas_ring vs flat psum on a CPU mesh) -----------

# 8 ranks (the paper's GPUs-per-node), a deliberately ragged pool (not a
# multiple of the ring: exercises the short final segment), bf16 wire.
RING_DEVICES = 8
RING_POOL_ELEMS = 8 * 1237 + 5

_RING_BENCH_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys, json
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.parallel.collectives import (compat_make_mesh, compat_set_mesh,
                                        compat_shard_map)
from repro.parallel.topology import get_algorithm

N = {devices}
POOL = {pool}
mesh = compat_make_mesh((N,), ("data",))
algo = get_algorithm("pallas_ring")
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=N * POOL), jnp.float32)
out = {{}}
for wire in ("float32", "bfloat16"):
    wd = jnp.dtype(wire)
    def f(g):
        gw = g.astype(wd)
        ring = algo.reduce(gw, ("data",))
        flat = jax.lax.psum(gw, "data")
        return ring.astype(jnp.float32), flat.astype(jnp.float32)
    sm = compat_shard_map(f, mesh=mesh, in_specs=P("data"),
                          out_specs=(P(None), P(None)),
                          axis_names={{"data"}})
    with compat_set_mesh(mesh):
        ring, flat = jax.jit(sm)(x)
    out["max_abs_err_" + ("f32" if wd == jnp.float32 else "bf16")] = \\
        float(jnp.max(jnp.abs(ring - flat)))
# Low-bit wires: quantize per rank (per-chunk scales from the summed
# census), ring-reduce the 1-byte words, compare against the exact f32
# sum of the same words. int8 must be LOSSLESS (integer partial sums
# stay on the grid, rank_clip keeps them in range); fp8-e4m3 rounds
# per hop (bounded).
from repro.core import wire as wire_mod
QCHUNK = 64
QPOOL = QCHUNK * 155
xq = jnp.asarray(rng.normal(size=N * QPOOL), jnp.float32)
census = jnp.sum(jnp.abs(xq.reshape((N, -1, QCHUNK))), axis=(0, 2))
for fmt in ("int8", "fp8_e4m3"):
    if fmt not in wire_mod.supported_formats():
        continue
    spec = wire_mod.resolve(fmt)
    scales = wire_mod.scales_from_census(census, chunk_elems=QCHUNK,
                                         num_shards=N, spec=spec)
    def fq(g):
        q, _ = wire_mod.quantize_pool(g, scales, chunk_elems=QCHUNK,
                                      spec=spec, num_shards=N)
        ring = algo.reduce(q, ("data",)).astype(jnp.float32)
        exact = jax.lax.psum(q.astype(jnp.float32), "data")
        return (wire_mod.dequantize_pool(ring, scales, QCHUNK),
                wire_mod.dequantize_pool(exact, scales, QCHUNK))
    smq = compat_shard_map(fq, mesh=mesh, in_specs=P("data"),
                           out_specs=(P(None), P(None)),
                           axis_names={{"data"}})
    with compat_set_mesh(mesh):
        ringq, exactq = jax.jit(smq)(xq)
    out["ring_max_err_" + fmt] = float(jnp.max(jnp.abs(ringq - exactq)))
    out["ring_scale_max_" + fmt] = float(jnp.max(scales))
# Step count: the full-ring twin under check_vma=False (pins the
# 2(N-1)-exchange schedule on every jax version; no hidden psum).
from repro.kernels import ref
def g(v):
    return ref.ring_allreduce(v.astype(jnp.bfloat16), "data")
sm = compat_shard_map(g, mesh=mesh, in_specs=P("data"),
                      out_specs=P("data"), axis_names={{"data"}},
                      check_vma=False)
jaxpr = str(jax.make_jaxpr(sm)(x))
out["ppermute_count"] = jaxpr.count("ppermute")
out["psum_count_in_ring"] = jaxpr.count("psum")
print(json.dumps(out))
"""


def ring_bench() -> Dict:
    """pallas_ring vs flat psum on a RING_DEVICES-rank (8) placeholder
    CPU mesh (subprocess: the bench process itself must keep the single
    real device), merged with the static ring plan.

    Records what the CI gate pins: ring/psum max error at f32 and bf16
    wire, the executed neighbor-exchange count vs the planned 2(N-1), the
    absence of any hidden psum on the full-ring path, and the per-step
    wire bytes of the ragged-pool segmentation."""
    from repro.kernels import ring_reduce
    from repro.parallel.cost_model import ring_exchange_steps

    script = _RING_BENCH_SCRIPT.format(devices=RING_DEVICES,
                                       pool=RING_POOL_ELEMS, src=_SRC)
    measured = _harness.run_py_subprocess(script, label="ring bench")
    p = ring_reduce.plan(RING_POOL_ELEMS, RING_DEVICES, "bfloat16")
    p8 = ring_reduce.plan(RING_POOL_ELEMS, RING_DEVICES, "int8")
    return {
        "devices": RING_DEVICES,
        "pool_elems": RING_POOL_ELEMS,
        "seg_elems": p["seg_elems"],
        "exchange_steps": ring_exchange_steps(RING_DEVICES),
        "wire_bytes_per_step": p["wire_bytes_per_step"],
        "total_wire_bytes": p["total_wire_bytes"],
        "wire_bytes_per_step_int8": p8["wire_bytes_per_step"],
        **measured,
    }


# -- low-bit wire gate (bytes accounting + matched-loss train twin) ----------


def _wire_gf(mode, wire_format, sparsity=0.5):
    from repro.configs.base import GradientFlowConfig
    from repro.core.gradientflow import GradientFlow

    pool = GradientPool({f"t{i}": jnp.zeros(s, jnp.float32)
                         for i, s in enumerate(ALEXNET_GRAD_SHAPES)},
                        pad_to=32768)
    cfg = GradientFlowConfig(
        mode=mode, bucket_elems=1 << 22, chunk_elems=32768,
        sparsity=sparsity, warmup_steps=0, wire_dtype="bfloat16",
        wire_format=wire_format, reduce_axes=("data",),
        collective_algo="flat")
    # Cluster-V: 64 nodes x 8 V100s (parallel.topology.Topology.cluster_v)
    return GradientFlow(cfg, pool, num_data_shards=512)


_WIRE_TRAIN_ARGS = [
    "--arch", "smollm-135m", "--reduced", "--steps", "24",
    "--seq-len", "64", "--batch", "4", "--gf-mode", "csc",
    "--sparsity", "0.85", "--chunk-elems", "2048", "--csc-warmup", "4",
    "--lr", "0.1", "--log-every", "1000",
]


def wire_bench() -> Dict:
    """Low-bit wire accounting + convergence twin.

    Bytes: the AlexNet/Cluster-V pool priced by GradientFlow's own wire
    accounting (census collectives included) — CSC-int8 vs the bf16
    dense baseline is the headline reduction (sparsity x byte-width);
    the same-mode lazy ratio isolates the byte-width factor alone.

    Convergence: the 100m example's reduced twin (same flags at smoke
    scale) trained with native bf16 vs the int8 wire — final losses must
    match to rtol 1e-2 (error feedback keeps the quantizer unbiased)."""
    dense_bf16 = _wire_gf("dense", "native").wire_bytes_per_step()
    lazy_bf16 = _wire_gf("lazy", "native").wire_bytes_per_step()
    lazy_int8 = _wire_gf("lazy", "int8").wire_bytes_per_step()
    gf_csc = _wire_gf("csc", "int8")
    csc_int8 = gf_csc.wire_bytes_per_step(gf_csc.stages[-1])

    from repro.launch.train import main as train_main
    losses_native = train_main(_WIRE_TRAIN_ARGS)
    losses_int8 = train_main(_WIRE_TRAIN_ARGS + ["--wire-format", "int8"])
    ln, lq = losses_native[-1], losses_int8[-1]
    return {
        "workload": "alexnet",
        "devices": 512,
        "bytes_dense_bf16": int(dense_bf16),
        "bytes_lazy_bf16": int(lazy_bf16),
        "bytes_lazy_int8": int(lazy_int8),
        "bytes_csc_int8": int(csc_int8),
        "reduction_csc_int8_vs_dense_bf16": round(
            dense_bf16 / csc_int8, 4),
        "reduction_lazy_int8_vs_lazy_bf16": round(
            lazy_bf16 / lazy_int8, 4),
        "train_steps": 24,
        "final_loss_native": round(float(ln), 6),
        "final_loss_int8": round(float(lq), 6),
        "final_loss_rel_diff": round(abs(ln - lq) / abs(ln), 6),
    }


# -- overlap gate (staged pipeline issue order + cost-model timeline) --------

# 4 ranks, UNIQUE per-tensor sizes: dense mode then yields one bucket per
# tensor whose psum / select_n result shapes are unambiguous in the
# jaxpr, so the issue-order assertion can anchor on f32[size] alone.
OVERLAP_DEVICES = 4
OVERLAP_SHAPES = [(771,), (1285,), (1799,), (2313,)]

_OVERLAP_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys, json, re
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs.base import GradientFlowConfig, OptimizerConfig
from repro.core.engine import OverlapEngine
from repro.core.gradientflow import GradientFlow
from repro.core.pool import GradientPool
from repro.optim import sgd
from repro.parallel.collectives import (compat_make_mesh, compat_set_mesh,
                                        compat_shard_map)

N = {devices}
params = {{f"t{{i}}": jnp.zeros(s, jnp.float32)
          for i, s in enumerate({shapes!r})}}
pool = GradientPool(params)
cfg = GradientFlowConfig(mode="dense", wire_dtype="float32",
                         reduce_axes=("data",), collective_algo="flat",
                         overlap="staged")
gf = GradientFlow(cfg, pool, num_data_shards=N)
eng = OverlapEngine(gf, "momentum_sgd",
                    OptimizerConfig(name="momentum_sgd"))
plan = eng.plan_for()
plan.validate()
mesh = compat_make_mesh((N,), ("data",))

def step(gpool, mom):
    st = sgd.SGDState(momentum=mom)
    new_params, opt2, _ = eng.run(plan, gpool, params, st,
                                  gf.init_state(), 0.1)
    return jax.tree_util.tree_leaves(new_params)[0], opt2.momentum

sm = compat_shard_map(step, mesh=mesh, in_specs=(P("data"), P(None)),
                      out_specs=(P(None), P(None)), axis_names={{"data"}},
                      check_vma=False)
gpool = jnp.zeros((N * pool.size,), jnp.float32)
mom = jnp.zeros((pool.size,), jnp.float32)
with compat_set_mesh(mesh):
    lines = str(jax.make_jaxpr(sm)(gpool, mom)).splitlines()

# Scan only the shard_map BODY: jaxpr printing may hoist jnp.where into
# named `_where` closures above the main jaxpr — eqn order is meaningful
# only from the shard_map call on, where those closures are invoked.
body_at = next(i for i, ln in enumerate(lines) if "shard_map[" in ln)
lines = lines[body_at:]

sizes = [t.size for t in plan.tasks]
def first_psum(size):
    for i, ln in enumerate(lines):
        if "psum[" in ln and f":f32[{{size}}]" in ln:
            return i
    return -1
def last_update_op(size):
    idx = -1
    for i, ln in enumerate(lines):
        if ("select_n" in ln or "_where" in ln) and \
                f":f32[{{size}}]" in ln:
            idx = i
    return idx
reduce_at = [first_psum(s) for s in sizes]
update_done_at = [last_update_op(s) for s in sizes]
ok = all(i >= 0 for i in reduce_at) and all(i >= 0 for i in update_done_at)
# The staged contract: bucket i's reduce is ISSUED (traced) before bucket
# i-1's update completes.
interleaved = ok and all(
    reduce_at[i] < update_done_at[i - 1] for i in range(1, len(sizes)))
# And it is a genuine pipeline, not a barrier: the first update starts
# before the LAST reduce is issued (fails if someone re-serializes it).
pipelined = ok and update_done_at[0] < reduce_at[-1]
print(json.dumps({{"sizes": sizes, "reduce_at": reduce_at,
                  "update_done_at": update_done_at,
                  "interleaved": bool(interleaved),
                  "pipelined": bool(pipelined)}}))
"""


def overlap_bench() -> Dict:
    """The overlap engine's two gated surfaces:

    * jaxpr issue order — a 4-rank subprocess traces the staged pipeline
      (dense mode: one bucket per tensor, unique sizes) and asserts
      bucket i's psum appears BEFORE bucket i-1's last update op, i.e.
      reduce_i is issued while update_{i-1} is still in flight;
    * the cost-model timeline — the AlexNet-class plan on Cluster-V
      (pure python, deterministic): per-bucket exposed-comm seconds,
      overlap efficiency, and staged-vs-monolithic finish.
    """
    script = _OVERLAP_SCRIPT.format(devices=OVERLAP_DEVICES, src=_SRC,
                                    shapes=OVERLAP_SHAPES)
    order = _harness.run_py_subprocess(script, label="overlap bench")

    from repro.configs.base import GradientFlowConfig
    from repro.core import engine
    from repro.core.gradientflow import GradientFlow
    from repro.core.pool import GradientPool
    from repro.parallel.topology import Topology

    topo = Topology.cluster_v()
    pool = GradientPool({f"t{i}": jnp.zeros(s, jnp.float32)
                         for i, s in enumerate(ALEXNET_GRAD_SHAPES)})
    gf = GradientFlow(
        GradientFlowConfig(mode="lazy", wire_dtype="float16",
                           warmup_steps=0, auto_bucket=True, topology=topo,
                           reduce_axes=("node", "gpu"),
                           collective_algo="auto", overlap="staged"),
        pool, num_data_shards=topo.num_devices)
    plan = gf.plan()
    plan.validate()
    sim = engine.simulate_plan(plan, topo)
    rows, summary = sim["rows"], sim["summary"]
    rnd = lambda x: round(float(x), 9)
    return {
        "jax_version": jax.__version__,
        "issue_order": order,
        "timeline": {
            "workload": "alexnet",
            "devices": topo.num_devices,
            "num_buckets": len(plan.tasks),
            "bucket_elems": [t.size for t in plan.tasks],
            "algos": [t.algo.name for t in plan.tasks],
            "per_bucket_exposed_comm_s": [
                rnd(r.exposed_comm_s(sim["backward_s"])) for r in rows],
            "backward_s": rnd(sim["backward_s"]),
            "finish_s": rnd(summary["finish_s"]),
            "monolithic_finish_s": rnd(sim["monolithic_finish_s"]),
            "exposed_comm_s": rnd(summary["exposed_comm_s"]),
            "overlap_efficiency": rnd(summary["overlap_efficiency"]),
        },
    }


def check_overlap_regression(baseline_path: str) -> int:
    """CI gate: fail (exit 1) if the staged pipeline loses its interleaved
    issue order (reduce_i no longer traced before update_{i-1} completes,
    or the pipeline re-serialized into a barrier), if the staged finish
    stops beating the monolithic barrier on the modeled AlexNet/Cluster-V
    timeline, or if the deterministic timeline numbers drift from the
    committed BENCH_overlap.json without a baseline refresh."""
    with open(baseline_path) as f:
        base = json.load(f)
    cur = overlap_bench()
    failures = []
    if not cur["issue_order"]["interleaved"]:
        failures.append(
            "staged pipeline lost its issue order: some bucket's reduce "
            "is no longer traced before the previous bucket's update "
            f"completes ({cur['issue_order']})")
    if not cur["issue_order"]["pipelined"]:
        failures.append(
            "staged pipeline re-serialized into a barrier (first update "
            f"after the last reduce: {cur['issue_order']})")
    tl, base_tl = cur["timeline"], base.get("timeline", {})
    if tl["finish_s"] > tl["monolithic_finish_s"] + 1e-12:
        failures.append(
            f"staged finish {tl['finish_s']} no longer beats the "
            f"monolithic barrier {tl['monolithic_finish_s']}")
    # The timeline is pure-python cost-model arithmetic — machine
    # independent — so drift means the model or the plan changed and the
    # committed baseline must be refreshed alongside.
    _harness.drift_check(
        failures, tl, base_tl,
        ("devices", "num_buckets", "bucket_elems", "algos",
         "per_bucket_exposed_comm_s", "backward_s", "finish_s",
         "monolithic_finish_s", "exposed_comm_s", "overlap_efficiency"),
        baseline="BENCH_overlap.json", section="timeline")
    return _harness.report(
        "overlap", failures,
        f"issue_order={cur['issue_order']} "
        f"exposed={tl['exposed_comm_s']}s "
        f"efficiency={tl['overlap_efficiency']}")


# -- elastic soak gate (fault-injected churn + StepPlan replan) --------------


def soak_bench() -> Dict:
    """One deterministic run of the simulated elastic soak
    (``repro.runtime.soak``): 64 hosts × 8 GPUs, seeded fault schedule
    (hard failures, a persistent straggler, a preemption notice), the
    supervisor checkpoint-resharding onto each proposed mesh and
    ``GradientFlow.replan``-ing the StepPlan for the new topology.

    The returned trace is integers + cost-model floats rounded to 9 dp —
    machine-independent, so CI compares it verbatim against the committed
    ``BENCH_soak.json``. Checkpoints go to a throwaway tempdir."""
    import tempfile

    # Lazy import keeps the bench module import-clean and device-free
    # until the soak actually runs.
    from repro.runtime.soak import SoakConfig, SoakHarness

    with tempfile.TemporaryDirectory() as d:
        trace = SoakHarness(SoakConfig(),
                            os.path.join(d, "ckpt")).run()
    trace["jax_version"] = jax.__version__
    return trace


def check_soak_regression(baseline_path: str) -> int:
    """CI gate: re-run the seeded soak and fail (exit 1) if

    * the run no longer completes (abort / restart-budget exhaustion),
    * any event type goes missing (the schedule must keep exercising
      straggler remesh AND preemption AND hard failure),
    * any elastic event stops recompiling the StepPlan for the new
      topology (plan_key unchanged, plan invalid, or the staged finish
      losing to the monolithic barrier on the shrunken mesh), or
    * the deterministic trace (events + final summary) drifts from the
      committed BENCH_soak.json without a baseline refresh.
    """
    with open(baseline_path) as f:
        base = json.load(f)
    cur = soak_bench()
    failures = []
    fin = cur["final"]
    if fin["aborted"] is not None:
        failures.append(f"soak aborted: {fin['aborted']}")
    if fin["completed_steps"] != cur["config"]["num_steps"]:
        failures.append(
            f"soak completed {fin['completed_steps']} of "
            f"{cur['config']['num_steps']} steps")
    required_kinds = {"straggler_remesh", "preemption", "hard_failure"}
    missing = required_kinds - set(fin["event_kinds"])
    if missing:
        failures.append(f"event kinds missing from the soak: "
                        f"{sorted(missing)} (have {fin['event_kinds']})")
    elastic = [e for e in cur["events"] if e.get("mesh_changed")]
    if not elastic:
        failures.append("no elastic event changed the mesh")
    for e in elastic:
        where = f"{e['kind']} @ step {e['step']}"
        if not e.get("replanned") or not e.get("plan_valid"):
            failures.append(f"{where}: StepPlan not recompiled/validated "
                            "for the new topology")
        if e.get("plan_key_after") == e.get("plan_key_before"):
            failures.append(f"{where}: plan cache key unchanged across "
                            "the remesh")
        if not e.get("staged_beats_monolithic"):
            failures.append(
                f"{where}: staged finish {e['predicted_step_after_s']} "
                f"lost to monolithic {e['monolithic_after_s']} on the "
                "shrunken mesh")
    guard = cur.get("guard")
    if not guard:
        failures.append("soak trace has no guard section (guard lane "
                        "not run)")
    else:
        for mode in ("lazy", "csc"):
            tt = guard[mode]["truth_table"]
            for kind, row in tt["classes"].items():
                if row["caught"] != row["injected"]:
                    failures.append(
                        f"soak guard[{mode}]: {kind} caught "
                        f"{row['caught']}/{row['injected']}")
            if tt["false_trips"]:
                failures.append(f"soak guard[{mode}]: "
                                f"{tt['false_trips']} false trip(s)")
    # The trace is pure-python control flow + cost-model arithmetic —
    # machine independent (the guard lane records only ints/bools/
    # power-of-two floats) — so any drift means the schedule, the
    # controller, or the model changed and the committed baseline must be
    # refreshed alongside.
    _harness.drift_check(
        failures, cur, base,
        ("config", "schedule", "events", "guard", "final"),
        baseline="BENCH_soak.json", section="soak trace")
    return _harness.report(
        "soak", failures,
        f"{fin['completed_steps']} steps, "
        f"{fin['elastic_events']} elastic events "
        f"({fin['event_kinds']}), {fin['restarts_consumed']} "
        f"restarts, final plan {fin['final_plan_key']}")


# -- numeric guard gate (detection truth table + zero-extra-collectives) -----

# 4 ranks, a few odd-sized tensors (pool padded to the CSC chunk); both
# wire modes are traced guarded AND unguarded and their collective
# primitive counts must match exactly.
GUARD_DEVICES = 4
GUARD_SHAPES = [(777,), (1281,), (2049,)]

_GUARD_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys, json, re
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs.base import (GradientFlowConfig, GuardConfig,
                                OptimizerConfig)
from repro.core.engine import OverlapEngine
from repro.core.gradientflow import GradientFlow
from repro.core.pool import GradientPool
from repro.optim import scaler as scaler_mod
from repro.optim import sgd
from repro.parallel.collectives import (compat_make_mesh, compat_set_mesh,
                                        compat_shard_map)

N = {devices}
COLL = re.compile(
    r"(psum|ppermute|all_gather|all_to_all|reduce_scatter)\\[")
out = {{}}
for mode in ("lazy", "csc"):
    params = {{f"t{{i}}": jnp.zeros(s, jnp.float32)
              for i, s in enumerate({shapes!r})}}
    pool = GradientPool(params, pad_to=64 if mode == "csc" else 1)
    cfg = GradientFlowConfig(mode=mode, bucket_elems=2048, chunk_elems=64,
                             sparsity=0.5, warmup_steps=0,
                             wire_dtype="bfloat16", reduce_axes=("data",),
                             collective_algo="flat", overlap="staged",
                             guard=GuardConfig())
    gf = GradientFlow(cfg, pool, num_data_shards=N)
    eng = OverlapEngine(gf, "momentum_sgd",
                        OptimizerConfig(name="momentum_sgd"))
    plan = eng.plan_for()
    mesh = compat_make_mesh((N,), ("data",))
    gdtype = jnp.float32 if mode == "csc" else jnp.bfloat16

    def unguarded(gpool, mom):
        st = sgd.SGDState(momentum=mom)
        p2, o2, g2 = eng.run(plan, gpool, params, st, gf.init_state(),
                             0.1)
        return jax.tree_util.tree_leaves(p2)[0], o2.momentum

    def guarded(gpool, mom, sc):
        st = sgd.SGDState(momentum=mom)
        p2, o2, g2, sc2, flags = eng.run_guarded(
            plan, gpool, params, st, gf.init_state(), sc, 0.1)
        return jax.tree_util.tree_leaves(p2)[0], o2.momentum, sc2

    gpool = jnp.zeros((N * pool.size,), gdtype)
    mom = jnp.zeros((pool.size,), jnp.float32)
    sc = scaler_mod.init(cfg.guard)
    with compat_set_mesh(mesh):
        sm_u = compat_shard_map(unguarded, mesh=mesh,
                                in_specs=(P("data"), P(None)),
                                out_specs=(P(None), P(None)),
                                axis_names={{"data"}}, check_vma=False)
        sm_g = compat_shard_map(guarded, mesh=mesh,
                                in_specs=(P("data"), P(None), P()),
                                out_specs=(P(None), P(None), P()),
                                axis_names={{"data"}}, check_vma=False)
        ju = str(jax.make_jaxpr(sm_u)(gpool, mom))
        jg = str(jax.make_jaxpr(sm_g)(gpool, mom, sc))
    cu, cg = {{}}, {{}}
    for m in COLL.finditer(ju):
        cu[m.group(1)] = cu.get(m.group(1), 0) + 1
    for m in COLL.finditer(jg):
        cg[m.group(1)] = cg.get(m.group(1), 0) + 1
    out[mode] = {{"unguarded": cu, "guarded": cg,
                 "extra": sum(cg.values()) - sum(cu.values())}}
print(json.dumps(out))
"""


def _guard_collectives() -> Dict:
    """Subprocess (placeholder multi-device mesh) tracing the guarded and
    unguarded engine steps and counting collective primitives in each
    jaxpr — the proof the in-band health flags ride the collectives
    already issued: the counts must be IDENTICAL."""
    script = _GUARD_SCRIPT.format(devices=GUARD_DEVICES, src=_SRC,
                                  shapes=GUARD_SHAPES)
    return _harness.run_py_subprocess(script, label="guard bench")


def _census_flags_overhead(measure_time: bool) -> Dict:
    """Deriving HealthFlags from the census the PR-3 single-pass pack
    already emits, vs that pack alone, on the AlexNet pool: the HLO op
    delta (a handful of scalar reductions/compares — no pool-sized pass,
    no collective) and optionally wall time."""
    from repro.configs.base import GuardConfig
    from repro.core import guard as guard_mod

    grads = {f"t{i}": jnp.ones(s, jnp.float32)
             for i, s in enumerate(ALEXNET_GRAD_SHAPES)}
    pool = GradientPool(grads, pad_to=CHUNK)
    staging0 = jnp.zeros((pool.size,), jnp.float32)
    limit = guard_mod.overflow_limit(GuardConfig(), "bfloat16")

    def pack_only(staging, g):
        return pool.pack_into(staging, g, dtype=jnp.bfloat16,
                              norms_chunk=CHUNK)

    def pack_flags(staging, g):
        p, norms, staging = pool.pack_into(staging, g, dtype=jnp.bfloat16,
                                           norms_chunk=CHUNK)
        flags = guard_mod.flags_from_census(norms, limit)
        return p, norms, staging, flags.nonfinite, flags.overflow

    base_ops = hlo_op_counts(pack_only, staging0, grads, donate=(0,))
    flag_ops = hlo_op_counts(pack_flags, staging0, grads, donate=(0,))
    out = {
        "pool_elems": pool.size,
        "pack_total_ops": base_ops["total_ops"],
        "pack_plus_flags_total_ops": flag_ops["total_ops"],
        "extra_ops": flag_ops["total_ops"] - base_ops["total_ops"],
    }
    if measure_time:
        out["pack_wall_us"] = timeit(
            jax.jit(lambda g: pool.pack_into(staging0, g,
                                             dtype=jnp.bfloat16,
                                             norms_chunk=CHUNK)[:2]),
            grads, warmup=1, iters=5)
        out["pack_plus_flags_wall_us"] = timeit(
            jax.jit(lambda g: pack_flags(staging0, g)[3:]), grads,
            warmup=1, iters=5)
    return out


def guard_bench(measure_time: bool = True) -> Dict:
    """The numeric guard rail's gated surfaces:

    * detection truth table — the real-numeric ``GuardLane`` (both wire
      modes) against one injected fault of each data-plane class: every
      fault must trip the in-band verdict AND leave the state
      bit-identical (the atomic skip);
    * zero false trips — a clean 100-step lane run: no rejection, no
      skip, only the scheduled loss-scale growth;
    * zero extra collectives — guarded vs unguarded engine jaxprs on a
      4-rank mesh must contain identical collective primitive counts;
    * census overhead — flags-from-census vs the PR-3 pack baseline on
      the AlexNet pool (HLO op delta; wall time informational).
    """
    from repro.runtime.faults import FaultEvent, GuardLane, truth_table

    faults = (FaultEvent(step=4, kind="nan", offset=8, width=4),
              FaultEvent(step=9, kind="overflow", offset=40, width=4),
              FaultEvent(step=14, kind="bitflip", offset=100, width=6))
    tt = {}
    for mode in ("lazy", "csc"):
        recs = GuardLane(mode=mode).run(20, faults)
        tt[mode] = truth_table(recs)
    clean = GuardLane().run(100, ())
    clean_tt = truth_table(clean)
    scales = [r["scale"] for r in clean]
    return {
        "jax_version": jax.__version__,
        "fault_schedule": [
            {"step": f.step, "kind": f.kind, "offset": f.offset,
             "width": f.width} for f in faults],
        "truth_table": tt,
        "clean_run": {
            "steps": len(clean),
            "false_trips": clean_tt["false_trips"],
            "skipped": clean[-1]["skipped"],
            "final_scale": scales[-1],
            "growth_events": sum(1 for a, b in zip(scales, scales[1:])
                                 if b > a),
        },
        "collectives": _guard_collectives(),
        "census_overhead": _census_flags_overhead(measure_time),
    }


def check_guard_regression(baseline_path: str) -> int:
    """CI gate: fail (exit 1) if any injected fault class escapes
    detection (or a rejected step mutates state), a clean 100-step run
    false-trips, the guarded step launches even one collective more than
    the unguarded step, or the machine-independent sections drift from
    the committed BENCH_guard.json without a refresh."""
    with open(baseline_path) as f:
        base = json.load(f)
    cur = guard_bench(measure_time=False)
    failures = []
    for mode in ("lazy", "csc"):
        classes = cur["truth_table"][mode]["classes"]
        for kind in ("nan", "overflow", "bitflip"):
            row = classes.get(kind)
            if row is None:
                failures.append(f"{mode}: fault class {kind!r} not "
                                "exercised")
            elif row["caught"] != row["injected"]:
                failures.append(
                    f"{mode}: {kind} caught {row['caught']}/"
                    f"{row['injected']} (undetected fault or "
                    "non-atomic skip)")
        if cur["truth_table"][mode]["false_trips"]:
            failures.append(
                f"{mode}: {cur['truth_table'][mode]['false_trips']} "
                "false trip(s) on clean steps of the faulted run")
    cr = cur["clean_run"]
    if cr["false_trips"] or cr["skipped"]:
        failures.append(
            f"clean 100-step run tripped: false_trips="
            f"{cr['false_trips']} skipped={cr['skipped']}")
    for mode in ("lazy", "csc"):
        col = cur["collectives"][mode]
        if col["extra"] != 0:
            failures.append(
                f"{mode}: guarded step launches {col['extra']} extra "
                f"collective(s): {col['guarded']} vs {col['unguarded']}")
    # Truth table + clean run are ints/bools/power-of-two floats —
    # machine-independent — so drift always means a behavior change.
    _harness.drift_check(
        failures, cur, base,
        ("fault_schedule", "truth_table", "clean_run"),
        baseline="BENCH_guard.json", section="guard")
    same_jax = base.get("jax_version") == jax.__version__
    if same_jax:
        if cur["collectives"] != base.get("collectives"):
            failures.append(
                f"collective counts drifted: {cur['collectives']} != "
                f"baseline {base.get('collectives')} (refresh "
                "BENCH_guard.json if intentional)")
        cur_extra = cur["census_overhead"]["extra_ops"]
        base_extra = base.get("census_overhead", {}).get("extra_ops")
        if cur_extra != base_extra:
            failures.append(
                f"census flag op delta drifted: {cur_extra} != baseline "
                f"{base_extra} (refresh BENCH_guard.json if intentional)")
    else:
        print(f"guard bench: baseline from jax "
              f"{base.get('jax_version', '<unrecorded>')}, running "
              f"{jax.__version__} — HLO/jaxpr-count drift comparison "
              "skipped (structural gates still enforced)")
    return _harness.report(
        "guard", failures,
        f"truth_table={cur['truth_table']} clean={cr} "
        f"collectives_extra=0 "
        f"census_extra_ops={cur['census_overhead']['extra_ops']}")


# -- compile-once loop gate (scan-over-steps windows) ------------------------

# The AlexNet pool scaled 1/1024 (layer skew preserved): small enough
# that per-step dispatch + the per-step host sync dominate wall time on
# CPU — which is exactly the overhead the scanned window removes — while
# still driving the real staged engine through the real CSC stage
# schedule. The full pool's compute would drown the dispatch delta and
# gate nothing.
LOOP_SCALE = 1024
LOOP_CHUNK = 256
LOOP_WINDOWS = (1, 8, 32)
LOOP_MEASURE_STEPS = 64  # per window size; multiple of max(LOOP_WINDOWS)


class _LoopLane:
    """Mini-trainer over the REAL OverlapEngine: CSC mode with a 2-stage
    warm-up, momentum SGD, per-step synthetic gradients derived from the
    in-carry step counter. One shard_mapped step fn per sparsity stage;
    ``window(K, stage)`` wraps it in ``lax.scan`` (scan OUTSIDE the
    manual region) under a trace-counting closure, so the bench can
    PROVE compile-once: traces == distinct (stage, K) executables, and
    zero retraces during the timed pass."""

    def __init__(self, seed: int = 0):
        from repro.configs.base import GradientFlowConfig, OptimizerConfig
        from repro.core.engine import OverlapEngine
        from repro.core.gradientflow import GradientFlow
        from repro.parallel.collectives import compat_make_mesh

        sizes = [max(int(np.prod(s)) // LOOP_SCALE, 32)
                 for s in ALEXNET_GRAD_SHAPES]
        rng = np.random.default_rng(seed)
        self.params_np = {f"t{i}": rng.normal(size=n).astype(np.float32)
                          for i, n in enumerate(sizes)}
        self.pool = GradientPool(
            {k: jax.ShapeDtypeStruct(v.shape, jnp.float32)
             for k, v in self.params_np.items()}, pad_to=LOOP_CHUNK)
        self.cfg = GradientFlowConfig(
            mode="csc", bucket_elems=1 << 14, chunk_elems=LOOP_CHUNK,
            sparsity=0.85, warmup_steps=32, warmup_stages=2,
            wire_dtype="float32", reduce_axes=("data",),
            collective_algo="flat", overlap="staged")
        self.gf = GradientFlow(self.cfg, self.pool, num_data_shards=1)
        self.engine = OverlapEngine(
            self.gf, "momentum_sgd",
            OptimizerConfig(name="momentum_sgd", momentum=0.9,
                            weight_decay=0.0))
        self.base_grads = jnp.asarray(
            rng.normal(size=self.pool.size), jnp.float32)
        self.mesh = compat_make_mesh((1,), ("data",))
        self.traces = {"n": 0}
        self._windows: Dict = {}

    def fresh_carry(self):
        from repro.optim import init_state as opt_init_state

        params = {k: jnp.asarray(v) for k, v in self.params_np.items()}
        return (params, opt_init_state("momentum_sgd", self.pool.size),
                self.gf.init_state())

    def _step_fn(self, stage):
        from jax.sharding import PartitionSpec as P

        from repro.parallel.collectives import compat_shard_map

        plan = self.engine.plan_for(stage)

        def body(params, opt, gfstate, step):
            # The lane's "backward pass": base gradients modulated by the
            # in-carry step counter, so every step's batch is distinct
            # and the scanned window cannot constant-fold the loop.
            gpool = self.base_grads * (1.0 + 1e-3 * step.astype(jnp.float32))
            return self.engine.run(plan, gpool, params, opt, gfstate, 0.05)

        return compat_shard_map(
            body, mesh=self.mesh,
            in_specs=(P(None), P(None), P(None), P()),
            out_specs=(P(None), P(None), P(None)),
            axis_names={"data"}, check_vma=False)

    def window(self, K, stage):
        """The compiled K-step window for ``stage`` (built once per
        (stage, K): the compile-once invariant this bench gates)."""
        key = (stage.index, K)
        if key not in self._windows:
            sm = self._step_fn(stage)

            def win(carry, steps):
                self.traces["n"] += 1  # fires at TRACE time only

                def body(c, step):
                    p2, o2, g2 = sm(*c, step)
                    return (p2, o2, g2), jnp.sum(jnp.abs(o2.momentum[:64]))

                return jax.lax.scan(body, carry, steps)

            self._windows[key] = jax.jit(win, donate_argnums=(0,))
        return self._windows[key]

    def run_schedule(self, K, stages, num_steps):
        """One pass over the stage-aware window schedule. Returns the
        final carry, the per-step metric stream, and the host-sync count
        (one ``np.asarray`` per window — the whole point)."""
        from repro.core.schedule import window_schedule

        carry = self.fresh_carry()
        metrics = []
        syncs = 0
        for step, length, stage in window_schedule(0, num_steps, K, stages):
            carry, ms = self.window(K, stage)(
                carry, jnp.arange(step, step + length, dtype=jnp.int32))
            metrics.append(np.asarray(ms, np.float32))  # ONE sync/window
            syncs += 1
        return carry, np.concatenate(metrics), syncs


def loop_bench() -> Dict:
    """The compile-once training loop's gated surfaces:

    * steps/sec at K in {1, 8, 32} over the stage-snapped schedule —
      the scanned window amortizes dispatch + host sync, so K=32 must
      beat K=1 by the gated factor;
    * compile-count proof — the trace counter must equal the number of
      distinct (stage, window) executables after the warm pass, and the
      timed pass must add ZERO retraces (one XLA program per stage);
    * equivalence — the K=8 scanned schedule's final params/momentum and
      per-step metric stream match the per-step (K=1) loop run over the
      SAME snapped stages at 1e-6.
    """
    from repro.core.schedule import snap_stages_to_window

    lane = _LoopLane()
    rows = {}
    for K in LOOP_WINDOWS:
        stages = snap_stages_to_window(lane.gf.stages, K)
        before = lane.traces["n"]
        lane.run_schedule(K, stages, LOOP_MEASURE_STEPS)  # compile pass
        exes = sum(1 for (_, k) in lane._windows if k == K)
        traces = lane.traces["n"] - before
        t0 = time.perf_counter()
        _, _, syncs = lane.run_schedule(K, stages, LOOP_MEASURE_STEPS)
        dt = time.perf_counter() - t0
        rows[str(K)] = {
            "window_steps": K,
            "steps": LOOP_MEASURE_STEPS,
            "num_windows": syncs,
            "host_syncs": syncs,
            "executables": exes,
            "traces_compile": traces,
            "retraces_timed": lane.traces["n"] - before - traces,
            "steps_per_s": round(LOOP_MEASURE_STEPS / dt, 2),
            "wall_us_per_step": round(dt / LOOP_MEASURE_STEPS * 1e6, 1),
        }

    # Equivalence: K=8 windows vs a per-step loop over the SAME snapped
    # stages (K=1 windows respect any boundary, so the stage sequence —
    # and therefore the numerics — must be identical).
    stages8 = snap_stages_to_window(lane.gf.stages, 8)
    c8, m8, _ = lane.run_schedule(8, stages8, LOOP_MEASURE_STEPS)
    c1, m1, _ = lane.run_schedule(1, stages8, LOOP_MEASURE_STEPS)
    rel = lambda a, b: float(np.max(np.abs(a - b) /
                                    np.maximum(np.abs(b), 1e-6)))
    pool8 = np.asarray(lane.pool.pack(c8[0], dtype=jnp.float32)[0])
    pool1 = np.asarray(lane.pool.pack(c1[0], dtype=jnp.float32)[0])
    return {
        "workload": f"alexnet/{LOOP_SCALE}",
        "pool_elems": lane.pool.size,
        "num_tensors": lane.pool.num_tensors,
        "chunk_elems": LOOP_CHUNK,
        "mode": "csc",
        "num_stages": len(lane.gf.stages),
        "jax_version": jax.__version__,
        "windows": rows,
        "speedup_8_vs_1": round(rows["8"]["steps_per_s"] /
                                rows["1"]["steps_per_s"], 3),
        "speedup_32_vs_1": round(rows["32"]["steps_per_s"] /
                                 rows["1"]["steps_per_s"], 3),
        "equivalence": {
            "params_max_rel_err": rel(pool8, pool1),
            "momentum_max_rel_err": rel(np.asarray(c8[1].momentum),
                                        np.asarray(c1[1].momentum)),
            "metrics_max_abs_err": float(np.max(np.abs(m8 - m1))),
        },
    }


# ISSUE 9 acceptance: the K=32 scanned window must beat per-step
# dispatch by >= 1.5x on the dispatch-dominated lane.
_LOOP_MIN_SPEEDUP = 1.5


def check_loop_regression(baseline_path: str) -> int:
    """CI gate: fail (exit 1) if the scanned window stops amortizing
    dispatch (K=32 < 1.5x the per-step loop), any (stage, K) window
    retraces (compile-once broken: more traces than executables, or any
    retrace during the timed pass), the host stops syncing once per
    window, the scanned schedule diverges from the per-step loop at
    1e-6, or the machine-independent schedule shape (executables /
    windows / stage count) drifts from the committed BENCH_loop.json
    without a refresh. steps/sec itself is machine-dependent and never
    drift-compared — only the K=32/K=1 ratio is gated."""
    with open(baseline_path) as f:
        base = json.load(f)
    cur = loop_bench()
    failures = []
    if cur["speedup_32_vs_1"] < _LOOP_MIN_SPEEDUP:
        failures.append(
            f"K=32 scanned window only {cur['speedup_32_vs_1']:.2f}x the "
            f"per-step loop (< {_LOOP_MIN_SPEEDUP}x): dispatch no longer "
            "amortized")
    for k, row in cur["windows"].items():
        if row["traces_compile"] != row["executables"]:
            failures.append(
                f"K={k}: {row['traces_compile']} traces for "
                f"{row['executables']} executables (compile-once broken)")
        if row["retraces_timed"] != 0:
            failures.append(
                f"K={k}: {row['retraces_timed']} retrace(s) during the "
                "timed pass")
        if row["host_syncs"] != row["num_windows"]:
            failures.append(
                f"K={k}: {row['host_syncs']} host syncs for "
                f"{row['num_windows']} windows (stacked metrics lost)")
    eq = cur["equivalence"]
    if eq["params_max_rel_err"] > 1e-6 or \
            eq["momentum_max_rel_err"] > 1e-6:
        failures.append(
            f"scanned window diverged from the per-step loop: params "
            f"rel err {eq['params_max_rel_err']:.2e}, momentum rel err "
            f"{eq['momentum_max_rel_err']:.2e} (> 1e-6)")
    # Schedule shape is pure-python arithmetic — machine-independent —
    # so drift always means the loop/stage logic changed and the
    # committed baseline must be refreshed alongside.
    _harness.drift_check(failures, cur, base,
                         ("pool_elems", "num_stages", "chunk_elems"),
                         baseline="BENCH_loop.json")
    for k, row in cur["windows"].items():
        _harness.drift_check(failures, row,
                             base.get("windows", {}).get(k, {}),
                             ("executables", "num_windows", "host_syncs"),
                             baseline="BENCH_loop.json",
                             section=f"windows[{k}]")
    return _harness.report(
        "loop", failures,
        f"speedup_32_vs_1={cur['speedup_32_vs_1']}x "
        f"executables={[r['executables'] for r in cur['windows'].values()]} "
        f"equivalence={eq}")


# -- cross-step pipeline gate (deferred tail buckets in the scan carry) ------

# The same dispatch-dominated AlexNet/1024 lane as the loop gate, but
# lazy mode (the only family the cross-step pipeline covers) with a
# 2-bucket deferred tail. The BASELINE is the PR-9 formulation: a
# scanned window whose body runs the per-step ``OverlapEngine.run`` over
# the params TREE — every step pays the pack/unflatten/assemble sweep.
# The PIPELINED window scans ``run_pipelined_segs`` over a SEGMENT-CARRY
# master (per-bucket slices via ``pool_split``): a step only ever writes
# the spans it updates, the lane flush + ``pool_join`` happen once at
# the window edge, and the tail buckets' updates retire at the START of
# the next scan iteration (where a real cluster hides them under fwd).
PIPE_SCALE = 1024
PIPE_CHUNK = 256
PIPE_TAIL = 2
PIPE_WINDOW = 32
PIPE_MEASURE_STEPS = 64
PIPE_TIMED_ROUNDS = 5
PIPE_BITID_DEVICES = 4

# ISSUE 10 acceptance: the K=32 pipelined (pool-resident) window must
# beat the PR-9 non-pipelined scanned window by >= 1.15x steps/sec.
_PIPE_MIN_SPEEDUP = 1.15

_PIPE_BITID_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys, json
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs.base import (GradientFlowConfig, GuardConfig,
                                OptimizerConfig)
from repro.core.engine import OverlapEngine
from repro.core.gradientflow import GradientFlow
from repro.core.pool import GradientPool
from repro.optim import scaler as scaler_mod
from repro.optim import sgd
from repro.parallel.collectives import compat_make_mesh, compat_shard_map

N = {devices}
SIZES = [(7,), (33, 5), (2, 3, 4), (129,), (64, 2), (300,)]
tree_struct = {{f"t{{i}}": jnp.zeros(s) for i, s in enumerate(SIZES)}}
mesh = compat_make_mesh((N,), ("data",))
rng = np.random.default_rng(0)
pool = GradientPool(tree_struct, pad_to=1)

def build(guard=None):
    cfg = GradientFlowConfig(mode="lazy", bucket_elems=150, chunk_elems=64,
                             sparsity=0.5, warmup_steps=0,
                             wire_dtype="float32", reduce_axes=("data",),
                             collective_algo="flat",
                             pipeline_tail_buckets=2, guard=guard)
    gf = GradientFlow(cfg, pool, num_data_shards=N)
    eng = OverlapEngine(gf, "momentum_sgd",
                        OptimizerConfig(name="momentum_sgd", momentum=0.9,
                                        weight_decay=1e-4))
    return gf, eng, eng.plan_for()

params = {{k: jnp.asarray(rng.normal(size=v.shape), jnp.float32)
          for k, v in tree_struct.items()}}
mom0 = jnp.asarray(rng.normal(size=pool.size), jnp.float32)
K = 4
gpools = np.asarray(rng.normal(size=(K, N * pool.size)), np.float32)
lrs = [0.1, 0.05, 0.2, 0.1]
out = {{}}

# -- unguarded chain: per-step dispatches, flush at the end ------------------
gf, eng, plan = build()
st0 = gf.init_state()

def base_step(gpool_all, params, mom, lr):
    def body(gpool):
        p2, o2, _ = eng.run(plan, gpool, params,
                            sgd.SGDState(momentum=mom), st0, lr)
        return tuple(jax.tree_util.tree_leaves(p2)) + (o2.momentum,)
    return compat_shard_map(body, mesh=mesh, in_specs=(P("data"),),
                            out_specs=P(), axis_names=("data",))(gpool_all)

def pipe_step(gpool_all, params, mom, lr, lane):
    def body(gpool, lane):
        p1, o1 = eng.apply_inflight(plan, params,
                                    sgd.SGDState(momentum=mom), lane)
        p2, o2, _, lane2 = eng.run_pipelined(plan, gpool, p1, o1, st0, lr)
        return tuple(jax.tree_util.tree_leaves(p2)) + (o2.momentum,), lane2
    return compat_shard_map(body, mesh=mesh, in_specs=(P("data"), P()),
                            out_specs=(P(), P()),
                            axis_names=("data",))(gpool_all, lane)

def flush(eng_, plan_, params, mom, lane):
    def body(lane):
        p1, o1 = eng_.apply_inflight(plan_, params,
                                     sgd.SGDState(momentum=mom), lane)
        return tuple(jax.tree_util.tree_leaves(p1)) + (o1.momentum,)
    return compat_shard_map(body, mesh=mesh, in_specs=(P(),),
                            out_specs=P(), axis_names=("data",))(lane)

def unwrap(out_leaves):
    p = {{f"t{{i}}": l for i, l in enumerate(out_leaves[:-1])}}
    return p, out_leaves[-1]

p, m = params, mom0
for k in range(K):
    o = base_step(jnp.asarray(gpools[k]), p, m, lrs[k])
    p, m = unwrap(o)
base_out = [np.asarray(x) for x in o]

p, m = params, mom0
lane = eng.empty_inflight(plan)
for k in range(K):
    o, lane = pipe_step(jnp.asarray(gpools[k]), p, m, lrs[k], lane)
    p, m = unwrap(o)
o = flush(eng, plan, p, m, lane)
pipe_out = [np.asarray(x) for x in o]
out["unguarded_max_abs_diff"] = max(
    float(np.max(np.abs(a - b))) for a, b in zip(base_out, pipe_out))

# -- guarded chain: a NaN fault trips while tail buckets are in flight -------
gcfg = GuardConfig()
gfg, engg, plang = build(gcfg)
stg = gfg.init_state()
gpools_g = gpools.copy()
gpools_g[2, 5] = np.nan

def base_gstep(gpool_all, params, mom, sc, lr):
    def body(gpool):
        p2, o2, _, sc2, fl = engg.run_guarded(
            plang, gpool, params, sgd.SGDState(momentum=mom), stg, sc, lr)
        return tuple(jax.tree_util.tree_leaves(p2)) + (o2.momentum,), \\
            sc2, fl
    return compat_shard_map(body, mesh=mesh, in_specs=(P("data"),),
                            out_specs=(P(), P(), P()),
                            axis_names=("data",))(gpool_all)

def pipe_gstep(gpool_all, params, mom, sc, lr, lane):
    def body(gpool, lane):
        p1, o1 = engg.apply_inflight(plang, params,
                                     sgd.SGDState(momentum=mom), lane)
        p2, o2, _, sc2, lane2, fl = engg.run_pipelined_guarded(
            plang, gpool, p1, o1, stg, sc, lr)
        return tuple(jax.tree_util.tree_leaves(p2)) + (o2.momentum,), \\
            sc2, lane2, fl
    return compat_shard_map(body, mesh=mesh, in_specs=(P("data"), P()),
                            out_specs=(P(), P(), P(), P()),
                            axis_names=("data",))(gpool_all, lane)

sc0 = scaler_mod.init(gcfg)
p, m, sc = params, mom0, sc0
trips_b = []
for k in range(K):
    o, sc, fl = base_gstep(jnp.asarray(gpools_g[k]), p, m, sc, lrs[k])
    trips_b.append(bool(fl.nonfinite | fl.overflow))
    p, m = unwrap(o)
base_out = [np.asarray(x) for x in o] + [np.asarray(sc.scale)]

p, m, sc = params, mom0, sc0
lane = engg.empty_inflight(plang, guarded=True)
trips_p = []
for k in range(K):
    o, sc, lane, fl = pipe_gstep(jnp.asarray(gpools_g[k]), p, m, sc,
                                 lrs[k], lane)
    trips_p.append(bool(fl.nonfinite | fl.overflow))
    p, m = unwrap(o)
o = flush(engg, plang, p, m, lane)
pipe_out = [np.asarray(x) for x in o] + [np.asarray(sc.scale)]
out["guarded_max_abs_diff"] = max(
    float(np.max(np.abs(a - b))) for a, b in zip(base_out, pipe_out))
out["trips_baseline"] = trips_b
out["trips_pipelined"] = trips_p
print(json.dumps(out))
"""


class _PipelineLane:
    """Engine lane for the cross-step pipeline's steps/sec gate: lazy
    mode on the 1/1024 AlexNet pool, flat collective on a 1-rank mesh,
    2 of ~8 buckets deferred. Both windows scan the SAME synthetic
    per-step gradients (base pool modulated by the in-carry step
    counter) so their trained state is comparable at the window edge."""

    def __init__(self, seed: int = 0):
        from repro.configs.base import GradientFlowConfig, OptimizerConfig
        from repro.core.engine import OverlapEngine
        from repro.core.gradientflow import GradientFlow
        from repro.parallel.collectives import compat_make_mesh

        sizes = [max(int(np.prod(s)) // PIPE_SCALE, 32)
                 for s in ALEXNET_GRAD_SHAPES]
        rng = np.random.default_rng(seed)
        self.params_np = {f"t{i}": rng.normal(size=n).astype(np.float32)
                          for i, n in enumerate(sizes)}
        self.pool = GradientPool(
            {k: jax.ShapeDtypeStruct(v.shape, jnp.float32)
             for k, v in self.params_np.items()}, pad_to=PIPE_CHUNK)
        self.cfg = GradientFlowConfig(
            mode="lazy", bucket_elems=1 << 13, chunk_elems=PIPE_CHUNK,
            sparsity=0.5, warmup_steps=0, wire_dtype="float32",
            reduce_axes=("data",), collective_algo="flat",
            overlap="staged", pipeline_tail_buckets=PIPE_TAIL)
        self.gf = GradientFlow(self.cfg, self.pool, num_data_shards=1)
        self.engine = OverlapEngine(
            self.gf, "momentum_sgd",
            OptimizerConfig(name="momentum_sgd", momentum=0.9,
                            weight_decay=0.0))
        self.plan = self.engine.plan_for()
        self.plan.validate()
        self.base_grads = jnp.asarray(
            rng.normal(size=self.pool.size), jnp.float32)
        self.mesh = compat_make_mesh((1,), ("data",))

    def _fresh_opt(self):
        from repro.optim import init_state as opt_init_state

        return opt_init_state("momentum_sgd", self.pool.size)

    def fresh_tree_carry(self):
        params = {k: jnp.asarray(v) for k, v in self.params_np.items()}
        return (params, self._fresh_opt(), self.gf.init_state())

    def fresh_pool_carry(self):
        params = {k: jnp.asarray(v) for k, v in self.params_np.items()}
        master = self.pool.pack(params, dtype=jnp.float32)[0]
        return (master, self._fresh_opt())

    def fresh_seg_carry(self):
        master, opt = self.fresh_pool_carry()
        return self.engine.pool_split(self.plan, master, opt)

    def _grads(self, step):
        # Barrier-islanded so both window bodies consume the same bits:
        # XLA contracts a*(1+eps*step) into an FMA in one scan body and
        # not the other, and 1+eps*step != 1 from step 1 on. A real
        # bwd pass would materialize the gradient pool the same way.
        return jax.lax.optimization_barrier(
            self.base_grads * (1.0 + 1e-3 * step.astype(jnp.float32)))

    def window_base(self):
        """The PR-9 scanned window: per-step tree-form engine step."""
        from jax.sharding import PartitionSpec as P

        from repro.parallel.collectives import compat_shard_map

        def step_body(params, opt, gfstate, step):
            return self.engine.run(self.plan, self._grads(step), params,
                                   opt, gfstate, 0.05)

        sm = compat_shard_map(
            step_body, mesh=self.mesh,
            in_specs=(P(None), P(None), P(None), P()),
            out_specs=(P(None), P(None), P(None)),
            axis_names={"data"}, check_vma=False)

        def win(carry, steps):
            def body(c, step):
                p2, o2, g2 = sm(*c, step)
                return (p2, o2, g2), jnp.sum(jnp.abs(o2.momentum[:64]))

            return jax.lax.scan(body, carry, steps)

        return jax.jit(win, donate_argnums=(0,))

    def window_pipe(self):
        """The pipelined window: segment-carry master (per-bucket
        slices in the scan carry — never a full-pool write per step),
        deferred tail in the lane, flushed at the window edge."""
        from jax.sharding import PartitionSpec as P

        from repro.core.engine import InflightLane
        from repro.parallel.collectives import compat_shard_map

        # Specs must mirror each carry pytree leaf-for-leaf (scalars
        # like lane.lr/ok need a rank-0 P()).
        n = len(self.plan.tasks)
        lane_specs = InflightLane(
            segs=(P(None),) * len(self.plan.tail_tasks), lr=P(), ok=P())
        m_specs = (P(None),) * n
        st_tmpl = jax.tree_util.tree_structure(self._fresh_opt())
        st_specs = tuple(
            jax.tree_util.tree_unflatten(
                st_tmpl, [P(None)] * st_tmpl.num_leaves)
            for _ in range(n))

        def step_body(m_segs, st_segs, lane, step):
            return self.engine.run_pipelined_segs(
                self.plan, self._grads(step), m_segs, st_segs, 0.05,
                lane)

        sm = compat_shard_map(
            step_body, mesh=self.mesh,
            in_specs=(m_specs, st_specs, lane_specs, P()),
            out_specs=(m_specs, st_specs, lane_specs),
            axis_names={"data"}, check_vma=False)

        def flush_body(m_segs, st_segs, lane):
            return self.engine.apply_inflight_segs(self.plan, m_segs,
                                                   st_segs, lane)

        sm_flush = compat_shard_map(
            flush_body, mesh=self.mesh,
            in_specs=(m_specs, st_specs, lane_specs),
            out_specs=(m_specs, st_specs),
            axis_names={"data"}, check_vma=False)

        def win(carry, steps):
            m_segs, st_segs = carry
            lane = self.engine.empty_inflight(self.plan)

            def body(c, step):
                m2, s2, lane2 = sm(*c, step)
                return (m2, s2, lane2), jnp.sum(
                    jnp.abs(s2[0].momentum[:64]))

            (m_segs, st_segs, lane), ms = jax.lax.scan(
                body, (m_segs, st_segs, lane), steps)
            m_segs, st_segs = sm_flush(m_segs, st_segs, lane)
            return (m_segs, st_segs), ms

        return jax.jit(win, donate_argnums=(0,))

    def drive(self, win, carry, num_steps):
        metrics = []
        for s in range(0, num_steps, PIPE_WINDOW):
            carry, ms = win(carry, jnp.arange(s, s + PIPE_WINDOW,
                                              dtype=jnp.int32))
            metrics.append(np.asarray(ms, np.float32))
        return carry, np.concatenate(metrics)


def pipeline_bench() -> Dict:
    """The cross-step pipeline's gated surfaces:

    * steps/sec — the K=32 pool-resident pipelined window vs the PR-9
      per-step-tree scanned window on the dispatch-dominated lane, same
      gradients, same bucket plan; the final states must also agree at
      the repo's scan tolerance (1e-6 — scan bodies of different shape
      FMA-contract the update chain differently);
    * bit-identity — a 4-rank subprocess drives per-step dispatch chains
      (unguarded AND guarded with a NaN fault tripping while two tail
      buckets are in flight): pipelined-then-flushed params/momentum/
      scale must equal the unpipelined run EXACTLY (max abs diff 0.0),
      and the two runs must trip on the same steps;
    * the analytic cross-step timeline — AlexNet on Cluster-V (pure
      cost-model python): the auto-selected tail must expose strictly
      less comm per steady-state step than the within-step staged
      schedule.
    """
    lane = _PipelineLane()

    def once(win, fresh):
        carry = fresh()
        t0 = time.perf_counter()
        carry, _ = lane.drive(win, carry, PIPE_MEASURE_STEPS)
        return carry, PIPE_MEASURE_STEPS / (time.perf_counter() - t0)

    # Interleaved best-of-N: the two windows alternate inside the same
    # seconds-long span, so slow drift (CPU frequency states, noisy
    # neighbours) hits both and the per-window best approximates the
    # uncontended step time. A single timed pass was observed swinging
    # the ratio by 2x run-to-run on an idle box.
    base_win = lane.window_base()
    pipe_win = lane.window_pipe()
    lane.drive(base_win, lane.fresh_tree_carry(), PIPE_MEASURE_STEPS)
    lane.drive(pipe_win, lane.fresh_seg_carry(), PIPE_MEASURE_STEPS)
    base_sps = pipe_sps = 0.0
    for _ in range(PIPE_TIMED_ROUNDS):
        base_carry, sps = once(base_win, lane.fresh_tree_carry)
        base_sps = max(base_sps, sps)
        pipe_carry, sps = once(pipe_win, lane.fresh_seg_carry)
        pipe_sps = max(pipe_sps, sps)
    base_master = np.asarray(lane.pool.pack(base_carry[0],
                                            dtype=jnp.float32)[0])
    pipe_master_j, pipe_opt = lane.engine.pool_join(lane.plan,
                                                    *pipe_carry)
    pipe_master = np.asarray(pipe_master_j)
    rel = lambda a, b: float(np.max(np.abs(a - b) /
                                    np.maximum(np.abs(b), 1e-6)))

    script = _PIPE_BITID_SCRIPT.format(devices=PIPE_BITID_DEVICES,
                                       src=_SRC)
    bitid = _harness.run_py_subprocess(script, label="pipeline bit-id")
    bitid["devices"] = PIPE_BITID_DEVICES

    return {
        "workload": f"alexnet/{PIPE_SCALE}",
        "pool_elems": lane.pool.size,
        "num_buckets": len(lane.plan.tasks),
        "pipeline_tail": lane.plan.pipeline_tail,
        "jax_version": jax.__version__,
        "speedup": {
            "window_steps": PIPE_WINDOW,
            "steps": PIPE_MEASURE_STEPS,
            "timed_rounds": PIPE_TIMED_ROUNDS,
            "steps_per_s_baseline": round(base_sps, 2),
            "steps_per_s_pipelined": round(pipe_sps, 2),
            "pipelined_vs_baseline": round(pipe_sps / base_sps, 3),
            "params_max_rel_err": rel(pipe_master, base_master),
            "momentum_max_rel_err": rel(
                np.asarray(pipe_opt.momentum),
                np.asarray(base_carry[1].momentum)),
        },
        "bit_identity": bitid,
        "analytic": _pipeline_analytic(),
    }


def _pipeline_analytic() -> Dict:
    """The AlexNet/Cluster-V cross-step timeline (the second table the
    dryrun ``--timeline`` prints), auto tail selection included — pure
    cost-model arithmetic, so CI drift-compares it verbatim."""
    from repro.configs.base import GradientFlowConfig
    from repro.core import engine
    from repro.core.gradientflow import GradientFlow
    from repro.parallel.topology import Topology

    topo = Topology.cluster_v()
    pool = GradientPool({f"t{i}": jnp.zeros(s, jnp.float32)
                         for i, s in enumerate(ALEXNET_GRAD_SHAPES)})
    gf = GradientFlow(
        GradientFlowConfig(mode="lazy", wire_dtype="float16",
                           warmup_steps=0, auto_bucket=True, topology=topo,
                           reduce_axes=("node", "gpu"),
                           collective_algo="auto", overlap="staged",
                           pipeline_tail_buckets=-1),
        pool, num_data_shards=topo.num_devices)
    plan = gf.plan()
    plan.validate()
    sim = engine.simulate_plan_pipelined(plan, topo)
    rnd = lambda x: round(float(x), 9)
    return {
        "workload": "alexnet",
        "devices": topo.num_devices,
        "num_buckets": len(plan.tasks),
        "tail": sim["tail"],
        "period_s": rnd(sim["period_s"]),
        "staged_finish_s": rnd(sim["staged_finish_s"]),
        "exposed_comm_s": rnd(sim["exposed_comm_s"]),
        "staged_exposed_comm_s": rnd(sim["staged_exposed_comm_s"]),
        "prologue_s": rnd(sim["prologue_s"]),
    }


def check_pipeline_regression(baseline_path: str) -> int:
    """CI gate: fail (exit 1) if the pipelined window loses its speedup
    over the PR-9 scanned baseline (< 1.15x), the pipelined chain stops
    being bit-identical to the unpipelined one (any nonzero diff on the
    per-step dispatch chains, unguarded or guarded-with-trip-in-flight,
    or a trip verdict moving between runs), the scanned twins diverge
    past the 1e-6 scan tolerance, the analytic cross-step timeline stops
    exposing strictly less comm than the staged schedule, or the
    machine-independent sections drift from the committed
    BENCH_pipeline.json without a refresh."""
    with open(baseline_path) as f:
        base = json.load(f)
    cur = pipeline_bench()
    failures = []
    sp = cur["speedup"]
    if sp["pipelined_vs_baseline"] < _PIPE_MIN_SPEEDUP:
        failures.append(
            f"pipelined window only {sp['pipelined_vs_baseline']:.2f}x "
            f"the PR-9 scanned baseline (< {_PIPE_MIN_SPEEDUP}x)")
    if sp["params_max_rel_err"] > 1e-6 or \
            sp["momentum_max_rel_err"] > 1e-6:
        failures.append(
            f"pipelined window diverged from the scanned baseline: "
            f"params rel err {sp['params_max_rel_err']:.2e}, momentum "
            f"rel err {sp['momentum_max_rel_err']:.2e} (> 1e-6)")
    bi = cur["bit_identity"]
    if bi["unguarded_max_abs_diff"] != 0.0:
        failures.append(
            f"unguarded pipelined chain no longer bit-identical: max "
            f"abs diff {bi['unguarded_max_abs_diff']:.2e}")
    if bi["guarded_max_abs_diff"] != 0.0:
        failures.append(
            f"guarded pipelined chain (trip in flight) no longer "
            f"bit-identical: max abs diff {bi['guarded_max_abs_diff']:.2e}")
    if bi["trips_baseline"] != bi["trips_pipelined"]:
        failures.append(
            f"guard verdicts moved: baseline trips {bi['trips_baseline']} "
            f"vs pipelined {bi['trips_pipelined']}")
    if not any(bi["trips_baseline"]):
        failures.append("guarded bit-identity chain never tripped — the "
                        "trip-while-in-flight case is no longer exercised")
    an = cur["analytic"]
    if not an["tail"] >= 1:
        failures.append(f"auto tail selection chose {an['tail']} on "
                        "AlexNet/Cluster-V (cross-step pipeline off)")
    if not an["exposed_comm_s"] < an["staged_exposed_comm_s"]:
        failures.append(
            f"cross-step exposed comm {an['exposed_comm_s']}s not "
            f"strictly below staged {an['staged_exposed_comm_s']}s")
    _harness.drift_check(failures, cur, base,
                         ("pool_elems", "num_buckets", "pipeline_tail"),
                         baseline="BENCH_pipeline.json")
    _harness.drift_check(
        failures, an, base.get("analytic", {}),
        ("workload", "devices", "num_buckets", "tail", "period_s",
         "staged_finish_s", "exposed_comm_s", "staged_exposed_comm_s",
         "prologue_s"),
        baseline="BENCH_pipeline.json", section="analytic")
    return _harness.report(
        "pipeline", failures,
        f"speedup={sp['pipelined_vs_baseline']}x bit_identity=0.0 "
        f"(trips {bi['trips_baseline']}) exposed "
        f"{an['exposed_comm_s']}s < staged "
        f"{an['staged_exposed_comm_s']}s")


# Peak VMEM the streaming kernels may claim per pallas_call — well under
# the ~16MiB/core budget so double buffering always has headroom.
_KERNEL_VMEM_BUDGET = 8 * 1024 * 1024


def check_kernel_regression(baseline_path: str) -> int:
    """CI gate: fail (exit 1) if the tiled kernels diverge from the ref
    oracles on the >4M pool, stop streaming (single tile), exceed the VMEM
    budget, or — when the environment's jax matches the baseline's — drift
    in tile count / copy-schedule size / VMEM bytes without the committed
    BENCH_kernels.json being refreshed alongside."""
    with open(baseline_path) as f:
        base = json.load(f)
    cur = kernel_bench(measure_time=False)
    failures = []
    for side, name in (("pack", "pool_pack"), ("unpack", "update_unpack")):
        if not cur[side]["kernel_dispatched"]:
            failures.append(
                f"ops.{name} did not dispatch to the streaming kernel on "
                "the >4M pool (ref fallback reintroduced?)")
    if not cur["pack"]["pool_exact"]:
        failures.append("tiled pool_pack no longer bit-exact vs ref")
    if cur["pack"]["norms_rel_err"] > 2e-5:
        failures.append(
            f"pack census rel err {cur['pack']['norms_rel_err']:.2e} > 2e-5")
    for k in ("mom_max_abs_err", "leaves_max_abs_err"):
        if cur["unpack"][k] > 1e-6:
            failures.append(
                f"unpack {k} {cur['unpack'][k]:.2e} > 1e-6")
    for side in ("pack", "unpack"):
        if cur[side]["num_tiles"] <= 1:
            failures.append(f"{side} kernel is not streaming "
                            f"(num_tiles={cur[side]['num_tiles']})")
        if cur[side]["vmem_bytes"] > _KERNEL_VMEM_BUDGET:
            failures.append(
                f"{side} peak VMEM {cur[side]['vmem_bytes']} bytes exceeds "
                f"budget {_KERNEL_VMEM_BUDGET}")
    # The tiling fields are pure-python schedule arithmetic — independent
    # of the installed jax/XLA — so the drift comparison applies
    # unconditionally (unlike the pool-bench HLO op counts).
    for side in ("pack", "unpack"):
        _harness.drift_check(
            failures, cur[side], base[side],
            ("tile_elems", "num_tiles", "num_copies", "vmem_bytes"),
            baseline="BENCH_kernels.json", section=side)
    # Ring gate: the owned collective must keep matching the psum it
    # replaces, execute exactly its planned 2(N-1) neighbor exchanges
    # with no hidden psum, and hold its static segmentation.
    ring = cur["ring"]
    # Tolerances: pure reduction-order rounding headroom (measured
    # 1.9e-6 / 0.125 on the ~10k-element pool summed over 8 ranks); a
    # structurally broken ring is off by O(1). The tight 1e-6 acceptance
    # bound lives in tests/test_ring_reduce.py on its smaller pools.
    if ring["max_abs_err_f32"] > 5e-6:
        failures.append(
            f"ring f32 max err {ring['max_abs_err_f32']:.2e} > 5e-6 vs "
            "flat psum")
    if ring["max_abs_err_bf16"] > 0.25:
        failures.append(
            f"ring bf16-wire max err {ring['max_abs_err_bf16']:.2e} > "
            "0.25 vs flat psum")
    if ring["ppermute_count"] != ring["exchange_steps"]:
        failures.append(
            f"ring executed {ring['ppermute_count']} neighbor exchanges, "
            f"planned 2(N-1) = {ring['exchange_steps']}")
    if ring["psum_count_in_ring"] != 0:
        failures.append(
            f"ring path contains {ring['psum_count_in_ring']} psum op(s) "
            "— no longer owns the collective")
    _harness.drift_check(
        failures, ring, base.get("ring", {}),
        ("devices", "pool_elems", "seg_elems", "exchange_steps",
         "wire_bytes_per_step", "wire_bytes_per_step_int8"),
        baseline="BENCH_kernels.json", section="ring")
    # Low-bit wire gates. The int8 grid is designed lossless in the ring
    # (rank_clip keeps partial sums on the int8 grid — wire.py): any
    # nonzero error means the in-flight requant cycle broke. fp8 tolerates
    # bounded per-hop rounding (half-ulp ~ 2^-4 relative, amortized over
    # the dequantized magnitudes; measured ~1e-2 on this pool).
    if "ring_max_err_int8" in ring and ring["ring_max_err_int8"] > 1e-6:
        failures.append(
            f"int8 ring no longer lossless: max err "
            f"{ring['ring_max_err_int8']:.2e} vs exact grid sum")
    if "ring_max_err_fp8_e4m3" in ring:
        # Per-hop fp8 rounding is half-ulp: <= 2^-4 of the value. Values
        # live under qmax*scale (the grid's headroom), so one envelope of
        # that bound covers the whole hop chain comfortably (measured
        # ~1.26 vs bound ~6.95 on this pool); a structurally broken
        # dequant cycle is off by the full magnitude, O(qmax*scale).
        bound = 448.0 * ring["ring_scale_max_fp8_e4m3"] * 2.0 ** -4
        if ring["ring_max_err_fp8_e4m3"] > bound:
            failures.append(
                f"fp8 ring max err {ring['ring_max_err_fp8_e4m3']:.2e} > "
                f"half-ulp envelope {bound:.2e} vs exact grid sum")
    wire = cur["wire"]
    # ISSUE acceptance: >=3.5x wire-bytes reduction for CSC-int8 vs the
    # bf16 dense baseline on the AlexNet/Cluster-V pool, with the tiny
    # train twin's final loss matching native to 1e-2 relative.
    if wire["reduction_csc_int8_vs_dense_bf16"] < 3.5:
        failures.append(
            f"CSC-int8 wire reduction "
            f"{wire['reduction_csc_int8_vs_dense_bf16']:.2f}x < 3.5x vs "
            "dense bf16")
    if wire["reduction_lazy_int8_vs_lazy_bf16"] < 1.9:
        failures.append(
            f"lazy int8 wire reduction "
            f"{wire['reduction_lazy_int8_vs_lazy_bf16']:.2f}x < 1.9x vs "
            "lazy bf16 (byte-width factor lost)")
    if wire["final_loss_rel_diff"] > 1e-2:
        failures.append(
            f"int8 train twin diverged: final loss rel diff "
            f"{wire['final_loss_rel_diff']:.2e} > 1e-2 (native "
            f"{wire['final_loss_native']} vs int8 {wire['final_loss_int8']})")
    _harness.drift_check(
        failures, wire, base.get("wire", {}),
        ("bytes_dense_bf16", "bytes_lazy_bf16", "bytes_lazy_int8",
         "bytes_csc_int8"),
        baseline="BENCH_kernels.json", section="wire")
    return _harness.report(
        "kernel", failures,
        f"pack={cur['pack']} unpack={cur['unpack']} ring={ring}")


def check_pool_regression(baseline_path: str, measure_time: bool = False
                          ) -> int:
    """CI gate: re-run the op-count benchmark and fail (exit 1) if the
    fused pack path issues any concatenate, loses its op-count advantage
    over the legacy chain measured in the SAME run, or — when the
    environment's jax matches the committed BENCH_pool.json's — regresses
    to more copy-class HLO ops than the baseline records. The absolute
    baseline comparison is skipped across jax/XLA versions (a different
    compiler may legitimately emit different op mixes for unchanged
    code); the same-run relative gates always apply."""
    with open(baseline_path) as f:
        base = json.load(f)
    cur = pool_pipeline(measure_time=measure_time)
    fused, base_fused = cur["fused"], base["fused"]
    failures = []
    if fused["concatenate"] > 0:
        failures.append(
            f"fused pack emits {fused['concatenate']} concatenate op(s)")
    if fused["total_ops"] >= cur["legacy"]["total_ops"]:
        failures.append(
            f"fused total ops {fused['total_ops']} not below legacy "
            f"{cur['legacy']['total_ops']}")
    copy_class = ("concatenate", "dynamic-slice", "copy")
    same_jax = base.get("jax_version") == jax.__version__
    if same_jax:
        cur_copies = sum(fused[k] for k in copy_class)
        base_copies = sum(base_fused[k] for k in copy_class)
        if cur_copies > base_copies:
            failures.append(
                f"fused pack copy-class ops regressed: {cur_copies} > "
                f"baseline {base_copies}")
    else:
        print(f"pool bench: baseline from jax "
              f"{base.get('jax_version', '<unrecorded>')}, running "
              f"{jax.__version__} — absolute copy-op comparison skipped "
              f"(relative gates still enforced)")
    return _harness.report(
        "pool", failures,
        f"fused={fused} vs legacy={cur['legacy']}")


# Every CI-gated benchmark, declared once: ``--<name>-json PATH``
# refreshes the committed BENCH_<name>.json baseline (wall time
# included), ``--<name>-check`` is the CI gate against it.
GATES = (
    _harness.Gate(
        "pool", "BENCH_pool.json",
        lambda: pool_pipeline(measure_time=True), check_pool_regression,
        json_help="run the pool pipeline benchmark (with wall time) and "
                  "write the baseline JSON",
        check_help="op-count mode: compare against the committed "
                   "BENCH_pool.json; exit 1 on regression"),
    _harness.Gate(
        "kernel", "BENCH_kernels.json",
        lambda: kernel_bench(measure_time=True), check_kernel_regression,
        json_help="run the streaming-kernel benchmark (with wall time) "
                  "and write the baseline JSON",
        check_help="kernel gate: re-validate tiled pack/unpack vs ref on "
                   "a >4M pool and compare tile count / peak VMEM bytes "
                   "against the committed BENCH_kernels.json; exit 1 on "
                   "regression"),
    _harness.Gate(
        "overlap", "BENCH_overlap.json",
        overlap_bench, check_overlap_regression,
        json_help="run the overlap-engine benchmark (jaxpr issue order + "
                  "AlexNet/Cluster-V timeline) and write the baseline "
                  "JSON",
        check_help="overlap gate: assert the staged pipeline's "
                   "interleaved issue order (reduce_i before update_{i-1} "
                   "completes) and compare the cost-model timeline "
                   "against the committed BENCH_overlap.json; exit 1 on "
                   "regression"),
    _harness.Gate(
        "soak", "BENCH_soak.json",
        soak_bench, check_soak_regression, print_key="final",
        json_help="run the simulated elastic soak (seeded fault schedule "
                  "+ StepPlan replan) and write the baseline trace JSON",
        check_help="soak gate: re-run the seeded soak and assert every "
                   "elastic event recompiled the StepPlan for the new "
                   "topology, all three event types fired, and the "
                   "deterministic trace matches the committed "
                   "BENCH_soak.json; exit 1 on regression"),
    _harness.Gate(
        "guard", "BENCH_guard.json",
        lambda: guard_bench(measure_time=True), check_guard_regression,
        json_help="run the numeric-guard benchmark (fault detection "
                  "truth table, clean-run false-trip scan, guarded-vs-"
                  "unguarded collective counts, census overhead) and "
                  "write the baseline JSON",
        check_help="guard gate: assert every injected fault class is "
                   "caught with a bit-identical skip, a clean 100-step "
                   "run never trips, the guarded step adds ZERO "
                   "collectives (jaxpr-counted), and the truth table "
                   "matches the committed BENCH_guard.json; exit 1 on "
                   "regression"),
    _harness.Gate(
        "loop", "BENCH_loop.json",
        loop_bench, check_loop_regression,
        json_help="run the compile-once loop benchmark (scanned K-step "
                  "windows vs per-step dispatch: steps/sec at K in "
                  "{1,8,32}, trace/executable counts, host-sync counts, "
                  "per-step equivalence) and write the baseline JSON",
        check_help="loop gate: assert the K=32 scanned window beats "
                   "per-step dispatch by >= 1.5x, every (stage, K) "
                   "window compiles exactly once (zero retraces in the "
                   "timed pass), the host syncs once per window, and the "
                   "scanned schedule matches the per-step loop at 1e-6; "
                   "compare the schedule shape against the committed "
                   "BENCH_loop.json; exit 1 on regression"),
    _harness.Gate(
        "pipeline", "BENCH_pipeline.json",
        pipeline_bench, check_pipeline_regression,
        json_help="run the cross-step pipeline benchmark (pool-resident "
                  "pipelined window vs the PR-9 scanned baseline, 4-rank "
                  "bit-identity chains, AlexNet/Cluster-V cross-step "
                  "timeline) and write the baseline JSON",
        check_help="pipeline gate: assert the K=32 pipelined window "
                   "beats the non-pipelined scanned window by >= 1.15x, "
                   "pipelined-vs-unpipelined training is bit-identical "
                   "(including a guarded fault tripping while tail "
                   "buckets are in flight), and the cross-step timeline "
                   "exposes strictly less comm than the staged schedule, "
                   "vs the committed BENCH_pipeline.json; exit 1 on "
                   "regression"),
)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    _harness.add_cli(ap, GATES)
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = _harness.dispatch(args, GATES, root)
    if code is not None:
        return code
    for r in run():
        print(f"{r['name']},{r['us']:.1f},{r['derived']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
