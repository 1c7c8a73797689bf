"""Multi-device correctness (8 placeholder CPU devices via subprocess —
the main pytest process must keep seeing the single real device).

The model_size>1 trainer tests run the reduce+update in a SIBLING fully
manual (data+model) shard_map (see launch/trainer.py) with the layer scan
on: TP sharding + the pool-space update must be numerically transparent
across meshes.
"""
import pytest

from conftest import run_multi_device


@pytest.mark.slow
def test_lazy_allreduce_sums_across_shards():
    run_multi_device("""
        from repro.core import GradientPool, GradientFlow, GFState
        from repro.configs.base import GradientFlowConfig
        mesh = compat_make_mesh((8,), ("data",))
        params = {"a": jnp.zeros((100, 8)), "b": jnp.zeros((64,))}
        pool = GradientPool(params, pad_to=64)
        cfg = GradientFlowConfig(mode="lazy", bucket_elems=256,
                                 wire_dtype="float32",
                                 reduce_axes=("data",))
        gf = GradientFlow(cfg, pool, num_data_shards=8)
        def step(shard_val):
            # each shard contributes shard_index+1
            g = jnp.full((pool.size,), shard_val[0])
            red, mask, _ = gf.reduce(g, gf.init_state())
            return red
        sm = compat_shard_map(step, mesh=mesh, in_specs=P("data"),
                           out_specs=P(None), axis_names={"data"})
        vals = jnp.arange(1.0, 9.0)
        with compat_set_mesh(mesh):
            red = jax.jit(sm)(vals)
        # mean of 1..8 = 4.5
        np.testing.assert_allclose(np.asarray(red), 4.5, rtol=1e-6)
        print("OK")
    """)


@pytest.mark.slow
def test_csc_cross_shard_selection_agrees_and_reduces():
    run_multi_device("""
        from repro.core import csc
        from repro.configs.base import GradientFlowConfig
        mesh = compat_make_mesh((8,), ("data",))
        CHUNK, NCHUNK = 64, 8
        POOL = CHUNK * NCHUNK
        cfg = GradientFlowConfig(mode="csc", chunk_elems=CHUNK,
                                 bucket_elems=10**9, sparsity=0.5,
                                 momentum=0.9, wire_dtype="float32",
                                 reduce_axes=("data",))
        def step(shard_val):
            # shard i's gradient = (i+1) everywhere
            g = jnp.full((POOL,), shard_val[0])
            state = csc.CSCState(hg=jnp.zeros((POOL,)),
                                 chunk_norms=jnp.arange(NCHUNK, 0, -1.0))
            res = csc.csc_reduce(g, state, cfg, num_selected=4,
                                 bucket_boundaries=((0, 4 * CHUNK),),
                                 num_data_shards=8)
            return res.grads, res.elem_mask, res.state.chunk_norms
        sm = compat_shard_map(step, mesh=mesh, in_specs=P("data"),
                           out_specs=(P(None), P(None), P(None)),
                           axis_names={"data"})
        with compat_set_mesh(mesh):
            grads, mask, norms = jax.jit(sm)(jnp.arange(1.0, 9.0))
        m = np.asarray(mask)
        # transmitted chunks: mean over shards of (i+1) = 4.5
        np.testing.assert_allclose(np.asarray(grads)[m], 4.5, rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(grads)[~m], 0.0)
        # norm census: psum over shards
        assert np.asarray(norms).shape == (NCHUNK,)
        print("OK")
    """)


@pytest.mark.slow
def test_trainer_2x2_mesh_modes_match_single_device():
    """Dense/lazy/CSC on a 2x2 (data x model) mesh must reproduce the
    1-device trajectory: TP sharding and the sibling-region update are
    numerically transparent."""
    out = run_multi_device("""
        from repro.configs import get_smoke
        from repro.configs.base import (GradientFlowConfig, OptimizerConfig,
                                        TrainConfig)
        from repro.data.synthetic import SyntheticLM
        from repro.launch.mesh import make_mesh
        from repro.launch.trainer import Trainer

        def run(mesh_shape, mode):
            model_cfg, rules = get_smoke("qwen3-32b")
            gf = GradientFlowConfig(mode=mode, bucket_elems=4096,
                                    chunk_elems=512, sparsity=0.5,
                                    warmup_steps=0, wire_dtype="float32")
            cfg = TrainConfig(model=model_cfg, gradientflow=gf,
                              optimizer=OptimizerConfig(
                                  name="momentum_sgd", learning_rate=0.2,
                                  warmup_steps=1, total_steps=20,
                                  schedule="constant"),
                              seq_len=32, global_batch=4, attn_chunk=0,
                              scan_layers=True)
            mesh = make_mesh(mesh_shape, ("data", "model"))
            trainer = Trainer(cfg, mesh, rules)
            data = SyntheticLM(model_cfg.vocab_size, seed=0)
            losses = []
            with compat_set_mesh(mesh):
                state = trainer.init_state(jax.random.PRNGKey(0))
                step = trainer.build_train_step(donate=False)
                for t in range(6):
                    state, m = step(state, jax.device_put(
                        data.batch(t, 4, 32)))
                    losses.append(float(m["loss"]))
            return losses

        for mode in ["dense", "lazy", "csc"]:
            single = run((1, 1), mode)
            multi = run((2, 2), mode)
            # bf16 compute: sharded matmuls reduce in different orders;
            # trajectories drift at bf16 resolution, not structurally.
            np.testing.assert_allclose(single, multi, rtol=6e-3,
                                       err_msg=mode)
            print(mode, "OK", single[-1], multi[-1])
    """, timeout=1800)
    assert out.count("OK") == 3


@pytest.mark.slow
def test_hierarchical_psum_matches_flat():
    run_multi_device("""
        from repro.parallel.collectives import hierarchical_psum
        mesh = compat_make_mesh((2, 4), ("pod", "data"))
        def f(x):
            flat = jax.lax.psum(x, ("pod", "data"))
            hier = hierarchical_psum(x, "data", ("pod",))
            return flat, hier
        sm = compat_shard_map(f, mesh=mesh, in_specs=P(("pod", "data")),
                           out_specs=(P(None), P(None)),
                           axis_names={"pod", "data"})
        with compat_set_mesh(mesh):
            # 13 elements: exercises the padding path
            x = jnp.arange(8 * 13.0)
            flat, hier = jax.jit(sm)(x)
        np.testing.assert_allclose(np.asarray(flat), np.asarray(hier),
                                   rtol=1e-6)
        print("OK")
    """)


@pytest.mark.slow
def test_elastic_reshard_resume():
    """Train on (2,2), checkpoint, restore onto (4,2) and (1,2) — loss
    trajectory must continue identically. Elastic events change the DATA
    degree only (TP is an architecture property; see runtime/elastic.py),
    so the pool-space optimizer state shapes are preserved."""
    out = run_multi_device("""
        import tempfile
        from repro.checkpoint.manager import CheckpointManager
        from repro.configs import get_smoke
        from repro.configs.base import (GradientFlowConfig, OptimizerConfig,
                                        TrainConfig)
        from repro.data.synthetic import SyntheticLM
        from repro.launch.mesh import make_mesh
        from repro.launch.trainer import Trainer

        model_cfg, rules = get_smoke("olmo-1b")
        def make(mesh_shape, gb=4):
            gf = GradientFlowConfig(mode="lazy", bucket_elems=4096,
                                    wire_dtype="float32", warmup_steps=0)
            cfg = TrainConfig(model=model_cfg, gradientflow=gf,
                              optimizer=OptimizerConfig(
                                  name="momentum_sgd", learning_rate=0.2,
                                  warmup_steps=1, total_steps=20,
                                  schedule="constant"),
                              seq_len=32, global_batch=gb, attn_chunk=0,
                              scan_layers=True)
            mesh = make_mesh(mesh_shape, ("data", "model"))
            return Trainer(cfg, mesh, rules), mesh

        data = SyntheticLM(model_cfg.vocab_size, seed=0)
        tmp = tempfile.mkdtemp()
        mgr = CheckpointManager(tmp, keep=1)

        trainer, mesh = make((2, 2))
        with compat_set_mesh(mesh):
            state = trainer.init_state(jax.random.PRNGKey(0))
            step = trainer.build_train_step(donate=False)
            for t in range(3):
                state, m = step(state, jax.device_put(data.batch(t, 4, 32)))
            mgr.save(3, state, blocking=True)
            ref = []
            for t in range(3, 6):
                state, m = step(state, jax.device_put(data.batch(t, 4, 32)))
                ref.append(float(m["loss"]))

        for new_shape in [(4, 2), (1, 2)]:
            tr2, mesh2 = make(new_shape)
            with compat_set_mesh(mesh2):
                s2 = tr2.init_state(jax.random.PRNGKey(1))
                _, restored = mgr.restore(s2)
                restored = jax.tree_util.tree_map(
                    lambda x, like: jax.device_put(jnp.asarray(x),
                                                   like.sharding),
                    restored, tr2.abstract_state())
                step2 = tr2.build_train_step(donate=False)
                got = []
                for t in range(3, 6):
                    restored, m = step2(restored, jax.device_put(
                        data.batch(t, 4, 32)))
                    got.append(float(m["loss"]))
            np.testing.assert_allclose(got, ref, rtol=2e-4,
                                       err_msg=str(new_shape))
            print("reshard", new_shape, "OK")
    """, timeout=1800)
    assert out.count("OK") == 2
