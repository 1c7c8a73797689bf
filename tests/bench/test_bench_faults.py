"""Runs of the tiny cell with the timed path broken underneath are not
correct: a step that returns its state unchanged, half of the batch left
out, a loss altered where it is produced, and, on four virtual chips, the
exchange between chips left out."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench_tiny import LIMITS, SEED, cache_restored, run_tiny  # noqa: F401


def _frozen_state(monkeypatch):
    from repro.launch.trainer import Trainer
    orig = Trainer._build_step_fn

    def broken(self, *a, **k):
        step = orig(self, *a, **k)

        def frozen(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return frozen
    monkeypatch.setattr(Trainer, "_build_step_fn", broken)


def _half_batch(monkeypatch):
    from repro.models.transformer import TransformerLM
    orig = TransformerLM.loss_fn

    def half(self, params, batch, **kw):
        n = batch["tokens"].shape[0] // 2
        return orig(self, params, {k: v[:n] for k, v in batch.items()},
                    **kw)
    monkeypatch.setattr(TransformerLM, "loss_fn", half)


def _altered_loss(monkeypatch):
    from repro.models.transformer import TransformerLM
    orig = TransformerLM.loss_fn

    def altered(self, params, batch, **kw):
        loss, metrics = orig(self, params, batch, **kw)
        return loss, {**metrics, "loss": metrics["loss"] * 1.01}
    monkeypatch.setattr(TransformerLM, "loss_fn", altered)


FAULTS = {"state_unchanged": (_frozen_state, "update"),
          "half_batch": (_half_batch, None),
          "loss_altered": (_altered_loss, "loss")}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(tmp_path, capsys, cache_restored,
                              monkeypatch, fault):
    plant, caught_by = FAULTS[fault]
    plant(monkeypatch)
    out = run_tiny(tmp_path, capsys)
    assert not out["correct"], out["checks"]
    if caught_by:
        c = out["checks"][caught_by]
        assert c["value"] > c["limit"], out["checks"]


def test_exchange_left_out_is_not_correct(tmp_path):
    """On four (virtual) chips: sound, then with each bucket's collective
    skipped, so every replica updates with its own gradients."""
    script = textwrap.dedent(f"""
        import json, os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {os.path.dirname(__file__)!r})
        import bench_tiny
        from bench import harness
        root = bench_tiny.make_root({str(tmp_path)!r}, chips=4,
                                    limits={LIMITS!r})
        argv = ["--workload", "tiny", "--seed", "{SEED}", "--seconds",
                "0.5", "--trace", "0"]
        harness.main(argv, root=root, require_chip=False)
        import jax, repro.core.lazy_allreduce as la
        def local(pool, start, end, axes, wire_dtype, *, algo=None,
                  accum_dtype=jax.numpy.float32):
            seg = jax.lax.slice_in_dim(pool, start, end)
            if wire_dtype is not None:
                seg = seg.astype(wire_dtype)
            return seg.astype(accum_dtype)
        la.reduce_bucket = local
        harness.main(argv, root=root, require_chip=False)
    """)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-4000:]
    sound, broken = [json.loads(line) for line in proc.stdout.splitlines()
                     if line.startswith("{")]
    assert sound["correct"], sound["checks"]
    assert sound["checks"]["replicas"]["value"] == 0.0
    assert not broken["correct"], broken["checks"]
    assert broken["checks"]["replicas"]["value"] > LIMITS["replicas"]
