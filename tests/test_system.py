"""End-to-end behaviour tests: convergence, CSC parity with dense training,
the momentum-correction ablation, and serve/train agreement."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.configs.base import (GradientFlowConfig, OptimizerConfig,
                                TrainConfig)
from repro.data.synthetic import SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.launch.trainer import Trainer
from repro.parallel.collectives import compat_set_mesh


def run_training(gf_mode, steps=40, sparsity=0.75, momentum=0.9,
                 correction=True, seed=0, lr=0.3):
    """Train reduced smollm on the Markov stream; returns loss history."""
    model_cfg, rules = get_smoke("smollm-135m")
    gf = GradientFlowConfig(
        mode=gf_mode, bucket_elems=4096, chunk_elems=512,
        sparsity=sparsity, momentum=momentum if correction else 0.0,
        warmup_steps=0, wire_dtype="float32")
    cfg = TrainConfig(
        model=model_cfg, gradientflow=gf,
        optimizer=OptimizerConfig(name="momentum_sgd", learning_rate=lr,
                                  momentum=momentum, weight_decay=0.0,
                                  warmup_steps=2, total_steps=steps,
                                  schedule="constant"),
        seq_len=64, global_batch=4, attn_chunk=0, seed=seed)
    mesh = make_host_mesh()
    trainer = Trainer(cfg, mesh, rules)
    data = SyntheticLM(model_cfg.vocab_size, seed=seed)
    losses = []
    with compat_set_mesh(mesh):
        state = trainer.init_state(jax.random.PRNGKey(seed))
        step = trainer.build_train_step()
        for t in range(steps):
            batch = jax.device_put(data.batch(t, 4, 64))
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
    return np.asarray(losses)


@pytest.fixture(scope="module")
def dense_losses():
    return run_training("dense")


def test_loss_decreases(dense_losses):
    assert np.isfinite(dense_losses).all()
    assert dense_losses[-5:].mean() < dense_losses[:5].mean() - 0.1


def test_lazy_equals_dense(dense_losses):
    """Lazy allreduce is a pure communication-scheduling change: identical
    numerics to the per-tensor dense baseline."""
    lazy = run_training("lazy")
    np.testing.assert_allclose(lazy, dense_losses, rtol=1e-5)


def test_csc_converges_close_to_dense(dense_losses):
    """Paper Table 3: sparse communication trains to (near) parity."""
    csc = run_training("csc", sparsity=0.75)
    assert np.isfinite(csc).all()
    # end-of-run loss within a modest margin of dense.
    assert csc[-5:].mean() < dense_losses[-5:].mean() + 0.15


def test_momentum_correction_matters():
    """Ablating Algorithm 1 (momentum=0 in the correction, i.e. historical
    gradients are dropped rather than re-injected) must hurt — this is the
    paper's justification for the correction."""
    with_corr = run_training("csc", sparsity=0.9, correction=True, steps=30)
    without = run_training("csc", sparsity=0.9, correction=False, steps=30)
    # dropping 90% of gradients without correction learns strictly less
    assert with_corr[-5:].mean() <= without[-5:].mean() + 1e-6


def test_deterministic_replay():
    a = run_training("csc", steps=10)
    b = run_training("csc", steps=10)
    np.testing.assert_array_equal(a, b)


def test_checkpoint_resume_bitexact(tmp_path):
    """Train 10 steps; checkpoint at 5; resume from 5 and verify identical
    trajectory — the fault-tolerance contract."""
    from repro.checkpoint.manager import CheckpointManager
    model_cfg, rules = get_smoke("olmo-1b")
    cfg = TrainConfig(
        model=model_cfg,
        gradientflow=GradientFlowConfig(mode="csc", chunk_elems=512,
                                        sparsity=0.5, warmup_steps=0,
                                        wire_dtype="float32"),
        optimizer=OptimizerConfig(name="momentum_sgd", learning_rate=0.2,
                                  warmup_steps=1, total_steps=10,
                                  schedule="constant"),
        seq_len=32, global_batch=2, attn_chunk=0)
    mesh = make_host_mesh()
    trainer = Trainer(cfg, mesh, rules)
    data = SyntheticLM(model_cfg.vocab_size, seed=0)
    mgr = CheckpointManager(str(tmp_path), keep=2)
    with compat_set_mesh(mesh):
        state = trainer.init_state(jax.random.PRNGKey(0))
        step = trainer.build_train_step(donate=False)
        losses = []
        for t in range(10):
            if t == 5:
                mgr.save(5, state, blocking=True)
            state, m = step(state, jax.device_put(data.batch(t, 2, 32)))
            losses.append(float(m["loss"]))
        # resume
        _, restored = mgr.restore(state)
        relosses = []
        for t in range(5, 10):
            restored, m = step(restored,
                               jax.device_put(data.batch(t, 2, 32)))
            relosses.append(float(m["loss"]))
    np.testing.assert_array_equal(np.asarray(losses[5:]),
                                  np.asarray(relosses))
