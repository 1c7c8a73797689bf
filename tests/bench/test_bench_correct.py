"""The comparison that decides ``correct``, driven through a whole run of
a tiny cell on the CPU (the harness's look for a chip skipped): a sound
run is correct, and the control (the reference in the next precision
down, fp8, put in the program's place) is not. The limits are the tiny
cell's own, set in bench_tiny between its sound and control readings."""
from __future__ import annotations

import pytest

import bench_tiny
from bench_tiny import LIMITS, SEED, cache_restored, run_tiny  # noqa: F401


@pytest.mark.parametrize("mode,dtype", [("lazy", "bfloat16"),
                                        ("lazy", "float32"),
                                        ("csc", "float32")])
def test_sound_run_is_correct(tmp_path, capsys, cache_restored, mode,
                              dtype):
    """bf16 as the cells run; f32 where CSC's selection, which flips on
    near-ties of bf16 chunk norms at this size, must agree chunk for
    chunk, and the program then matches the reference to the bf16 wire's
    rounding."""
    out = run_tiny(tmp_path, capsys, mode=mode, compute_dtype=dtype)
    assert out["correct"], out["checks"]
    assert list(out["checks"])[-1:] and list(out)[-1] == "checks"
    if dtype == "float32":
        assert all(c["value"] < 2e-3 for c in out["checks"].values()), \
            out["checks"]


def test_sound_run_with_qk_norm_is_correct(tmp_path, capsys,
                                           cache_restored):
    """Qwen3's blocks: QK-norm, and heads wider than d_model / heads."""
    out = run_tiny(tmp_path, capsys, model={"qk_norm": True, "head_dim": 32,
                                            "rope_theta": 1000000.0})
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("mode", ["lazy", "csc"])
def test_control_is_not_correct(tmp_path, mode):
    """The reference computed with fp8 products, in the program's place,
    fails the limits the program passes."""
    from bench import check, reference
    from bench.spec import Spec
    bench_tiny.make_root(str(tmp_path), limits=LIMITS, mode=mode)
    spec = Spec(str(tmp_path))
    config, traffic = spec.config("tiny"), spec.traffic("tiny")
    ref = reference.Reference(config, traffic).run(SEED, 4)
    ctl = reference.Reference(config, traffic, precision="fp8").run(SEED, 4)
    ok, rows = check.judge(check.numbers(ctl, ref), LIMITS)
    assert not ok, rows


