"""FLOP and byte counters, and the table of peaks, against hand counts."""
from __future__ import annotations

import json
import os

import pytest

import bench_tiny  # noqa: F401  (puts the repo root on sys.path)
from bench import counts, devices, reference
from bench.spec import ROOT


def _count(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    shapes = reference.model_module(cfg).param_shapes(
        reference.reference_model(cfg))
    return cfg, counts.param_count(shapes)


@pytest.mark.parametrize("name,params,gflop", [
    # 49152*576 + 30*(4*576*576 - 2*576*384 ... ) by hand: embedding
    # 28,311,552; per layer attention 884,736, MLP 2,654,208, two norms
    # 1,152; final norm 576.
    ("smollm-135m", 28_311_552 + 30 * (884_736 + 2_654_208 + 1_152) + 576,
     1.231763328),
    # embedding 50304*2048 = 103,022,592; per layer 4*2048^2 +
    # 3*2048*8192 = 67,108,864; no norm parameters.
    ("olmo-1b-4L", 103_022_592 + 4 * 67_108_864, 2.43007488),
    # embedding 151936*1024 = 155,582,464; per layer q and o 2 * 1024 *
    # 2048, k and v 2 * 1024 * 1024, MLP 3 * 1024 * 3072, QK-norm 2 *
    # 128, two norms 2 * 1024; final norm 1024. Attention at 16 heads of
    # 128: 12 * 4 * 2048 * 2048.
    ("qwen3-0.6b-4L", 155_582_464 + 4 * (6_291_456 + 9_437_184 + 256
                                         + 2_048) + 1_024, 1.512370176),
])
def test_params_and_model_flops(name, params, gflop):
    cfg, n = _count(name)
    assert n == params
    assert n == {"smollm-135m": 134_515_008,
                 "olmo-1b-4L": 371_458_048,
                 "qwen3-0.6b-4L": 218_507_264}[name]
    # 6N + 12 * layers * seq * heads * head_dim at seq 2048
    m = cfg["model"]
    fpt = counts.flops_per_token(m, 2048, n)
    assert fpt == pytest.approx(gflop * 1e9, rel=1e-12)
    assert fpt == 6 * n + 12 * m["num_layers"] * 2048 * m["num_heads"] \
        * (m["head_dim"] or m["d_model"] // m["num_heads"])


def test_csc_pool_layout():
    _, n = _count("olmo-1b-4L")
    lay = counts.pool_layout(n, {"mode": "csc", "chunk_elems": 32768,
                                 "sparsity": 0.85})
    assert lay == {"n": 371_458_048, "size": 371_458_048,
                   "chunks": 11_336, "selected": 1_700}


def test_pool_kernel_bytes_by_hand():
    n = 134_515_008
    lazy = counts.pool_kernel_bytes(n, {"mode": "lazy",
                                        "wire_dtype": "bfloat16"})
    # pack: read f32 grads, write bf16 pool; read f32 params, write f32
    # master. update: read master f32, grads bf16, momentum f32; write
    # momentum f32 and params f32.
    assert lazy == {"pool_pack": n * (4 + 2) + n * 8,
                    "pool_unpack_update": n * 18}
    csc = counts.pool_kernel_bytes(371_458_048, {
        "mode": "csc", "wire_dtype": "bfloat16", "chunk_elems": 32768,
        "sparsity": 0.85})
    m = 371_458_048
    assert csc == {"pool_pack": m * 16, "pool_unpack_update": m * 20,
                   "csc_compact": 1_700 * 32768 * 8,
                   "chunk_l1norm": m * 4 + 11_336 * 4}


def test_peaks_table():
    p = devices.peaks_for("tpu", "TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_per_s, p.hbm_bytes) == \
        (197e12, 819e9, 16e9)


@pytest.mark.parametrize("platform,kind", [("tpu", "TPU v99"),
                                           ("cpu", "cpu"),
                                           ("gpu", "TPU v5 lite")])
def test_unknown_and_cpu_devices_refused(platform, kind):
    with pytest.raises(devices.UnknownDevice):
        devices.peaks_for(platform, kind)
