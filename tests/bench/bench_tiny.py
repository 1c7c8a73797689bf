"""A tiny cell of the benchmark for CPU tests: smollm-135m's registered
configuration cut to a few narrow layers, one or four workers, a window
of 4 steps of 4 rows of 32 tokens. Written as files into a fresh root,
so the tests drive the harness exactly as a cell found by name."""
from __future__ import annotations

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

MODEL = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
         "d_ff": 128, "vocab_size": 256}


# Limits of the tiny cell, set between its sound readings (loss 3.6e-4,
# update 1.7e-3, momentum 3.6e-3 in bf16) and its control's (2.0e-3,
# 0.041, 0.040).
LIMITS = {"loss": 1e-3, "update": 0.01, "momentum": 0.02, "hg": 0.01,
          "selection": 0.05, "replicas": 1e-3}
SEED = 2 ** 31 + 4099




def write(root, *parts, obj):
    path = os.path.join(root, *parts)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
    return path


def make_root(root: str, *, mode: str = "lazy", chips: int = 1,
              limits: dict = None, compute_dtype: str = "bfloat16",
              model: dict = None) -> str:
    """A benchmark root holding one tiny cell named ``tiny``; ``model``
    sets further keys of its ModelConfig."""
    extra = model or {}
    model = {**MODEL, "family": "dense", "norm": "rmsnorm",
             "rope_theta": 10000.0, "activation": "swiglu",
             "tie_embeddings": True, "compute_dtype": compute_dtype, **extra}
    reduced = sorted(set(MODEL) | {"compute_dtype"} | set(extra))
    write(root, "bench", "configs", "tiny.json", obj={
        "name": "tiny", "arch": "smollm-135m", "model": model,
        "reduced": reduced,
        "reference": {"module": "dense_lm", "norm_eps": 1e-6,
                      "rows_per_block": 2 if chips == 1 else 1}})
    write(root, "bench", "traffic", "tiny.json", obj={
        "seq_len": 32, "global_batch": 4, "mesh": f"{chips}x1",
        "window_steps": 4, "attn_chunk": 0,
        "gradientflow": {"mode": mode, "wire_format": "native",
                         "collective_algo": "auto", "use_kernels": True,
                         "chunk_elems": 1024, "sparsity": 0.85,
                         "csc_warmup": 0},
        "optimizer": {"name": "momentum_sgd", "lr": 0.5, "momentum": 0.9,
                      "weight_decay": 0.0001, "schedule_steps": 20},
        "tokens": {"kind": "markov", "branching": 4}})
    write(root, "bench", "limits", "tiny.json",
          obj={"limits": limits or {}})
    write(root, "BENCHMARK.json", obj={
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json",
                     "reduced": reduced}],
        "workloads": [{"name": "tiny", "config": "tiny", "traffic": "tiny",
                       "chips": chips}],
        "end_to_end": [{"name": "tokens_per_s_per_chip"},
                       {"name": "setup_s"}],
        "per_layer": []})
    return root


@pytest.fixture
def cache_restored():
    """The harness turns JAX's persistent cache on; put it back as it
    was for the tests that share this process."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", prev[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev[1])
    compilation_cache.reset_cache()


def run_tiny(root, capsys, **kw) -> dict:
    """A whole run of the tiny cell (no chip), and its result line."""
    import json
    from bench import harness
    make_root(str(root), limits=LIMITS, **kw)
    rc = harness.main(["--workload", "tiny", "--seed", str(SEED),
                       "--seconds", "0.5", "--trace", "0"],
                      root=str(root), require_chip=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
