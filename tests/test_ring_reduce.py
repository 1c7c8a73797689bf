"""Ring allreduce (`pallas_ring`) test matrix.

Equivalence of the owned ring against the flat psum over {2, 4, 8}
devices x {f32 pool, bf16 wire} x {aligned, ragged, smaller-than-N}
sizes — standalone (ref twin inside a compat_shard_map region) and
through the registry (`get_algorithm("pallas_ring").reduce`,
`bucketed_reduce` with ragged buckets, CSC's compacted wire buffer, and
a trainer end to end) — plus the step-count contract: exactly 2(N-1)
neighbor exchanges and no hidden psum on the full-ring path.

Multi-device tests run in subprocesses with placeholder CPU devices
(the main pytest process must keep seeing the single real device), the
same harness as test_topology.py / test_distributed.py.
"""
import pytest

from conftest import run_multi_device
from repro.kernels import ring_reduce
from repro.parallel import cost_model


# -- static schedule (no devices) --------------------------------------------


def test_ring_segment_bounds_static():
    # aligned
    assert ring_reduce.ring_segment_bounds(8, 4) == (
        (0, 2), (2, 4), (4, 6), (6, 8))
    # ragged final segment
    assert ring_reduce.ring_segment_bounds(10, 4) == (
        (0, 3), (3, 6), (6, 9), (9, 10))
    # smaller than N: unit segments then empty ones
    assert ring_reduce.ring_segment_bounds(3, 5) == (
        (0, 1), (1, 2), (2, 3), (3, 3), (3, 3))
    # degenerate ring
    assert ring_reduce.ring_segment_bounds(7, 1) == ((0, 7),)


def test_ring_plan_matches_cost_model_steps_and_wire_bytes():
    p = ring_reduce.plan(10_000, 8, "bfloat16")
    assert p["exchange_steps"] == cost_model.ring_exchange_steps(8) == 14
    seg = -(-10_000 // 8)
    assert p["seg_elems"] == seg
    assert p["wire_bytes_per_step"] == seg * 2
    assert p["total_wire_bytes"] == 14 * seg * 2
    # the model-level mirror prices the same padded segment
    assert cost_model.ring_step_wire_bytes(10_000 * 2, 8) == \
        pytest.approx(float(-(-(10_000 * 2) // 8)))
    # tile divides the segment exactly (the kernel's sub-tile loop rule)
    assert p["seg_elems"] % p["tile_elems"] == 0
    assert p["vmem_bytes"] <= 8 * 1024 * 1024


def test_ring_plan_sub_n_pool():
    p = ring_reduce.plan(5, 8, "float32")
    assert p["seg_elems"] == 1 and p["padded_elems"] == 8
    assert p["segment_bounds"][-1] == (5, 5)  # empty trailing segments
    assert p["exchange_steps"] == 14


# -- multi-device equivalence (subprocess) -----------------------------------

_EQUIV_BODY = """
    from repro.kernels import ref
    from repro.parallel.topology import get_algorithm
    mesh = compat_make_mesh((N,), ("data",))
    algo = get_algorithm("pallas_ring")
    rng = np.random.default_rng(0)
    # aligned, ragged, and smaller-than-N per-shard pool sizes
    for size in (N * 37, N * 5 + 3, max(N - 3, 1)):
        for wire in ("float32", "bfloat16"):
            wire = jnp.dtype(wire)
            # check_vma=False pins the full 2(N-1) ring (a checked
            # region would reject the varying-tagged ppermute chain and
            # reroute to the vma twin)
            def f(x):
                xw = x.astype(wire)
                ring = ref.ring_allreduce(xw, "data")        # standalone
                inv = ref.ring_allreduce_invariant(xw, "data")
                reg = algo.reduce(xw, ("data",))             # registry
                flat = jax.lax.psum(xw, "data")
                return ring.astype(jnp.float32), \\
                    inv.astype(jnp.float32), \\
                    reg.astype(jnp.float32), flat.astype(jnp.float32)
            sm = compat_shard_map(f, mesh=mesh, in_specs=P("data"),
                                  out_specs=(P(None),) * 4,
                                  axis_names={"data"}, check_vma=False)
            x = jnp.asarray(rng.normal(size=N * size), jnp.float32)
            with compat_set_mesh(mesh):
                ring, inv, reg, flat = jax.jit(sm)(x)
            tol = 1e-6 if wire == jnp.float32 else 0.06
            np.testing.assert_allclose(np.asarray(ring), np.asarray(flat),
                                       atol=tol, err_msg=f"{size} {wire}")
            # the vma-safe twin (RS ring + place-and-psum gather) agrees
            np.testing.assert_allclose(np.asarray(inv), np.asarray(flat),
                                       atol=tol, err_msg=f"inv {size}")
            np.testing.assert_array_equal(np.asarray(ring),
                                          np.asarray(reg))
            print("OK", size, wire.name)
"""


@pytest.mark.slow
@pytest.mark.parametrize("devices", [2, 4, 8])
def test_ring_matches_psum(devices):
    """ISSUE acceptance: ring == psum to <=1e-6 (f32) / bf16-wire
    tolerance, for aligned, ragged, and smaller-than-N pools, both as a
    direct ref-twin call and through get_algorithm('pallas_ring')."""
    out = run_multi_device(_EQUIV_BODY, devices=devices)
    assert out.count("OK") == 6


@pytest.mark.slow
def test_ring_step_count_exactly_2n_minus_1_exchanges():
    """The full-ring path issues exactly 2(N-1) ppermute neighbor
    exchanges and bottoms out in NO psum — it genuinely owns the
    collective (check_vma=False pins the full-ring twin on every jax
    version; the vma-safe variant for checked regions trades the gather
    phase for one psum and is asserted separately)."""
    run_multi_device("""
        from repro.kernels import ref
        mesh = compat_make_mesh((4,), ("data",))
        def f(x):
            return ref.ring_allreduce(x, "data")
        sm = compat_shard_map(f, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data"), axis_names={"data"},
                              check_vma=False)
        x = jnp.arange(4 * 13.0)
        jaxpr = str(jax.make_jaxpr(sm)(x))
        n_pp = jaxpr.count("ppermute")
        assert n_pp == 2 * (4 - 1), jaxpr
        assert "psum" not in jaxpr, jaxpr
        print("OK", n_pp)
    """, devices=4)


@pytest.mark.slow
def test_ring_inside_bucketed_reduce_ragged_buckets():
    """pallas_ring as the per-bucket algorithm of the lazy allreduce:
    ragged tensor-aligned buckets, each independently re-segmented by the
    ring, against the flat-psum bucketed reduce."""
    run_multi_device("""
        from repro.core.lazy_allreduce import bucketed_reduce
        from repro.core.pool import GradientPool
        from repro.parallel.topology import get_algorithm
        mesh = compat_make_mesh((8,), ("data",))
        params = {"a": jnp.zeros((100, 7)), "b": jnp.zeros((61,)),
                  "c": jnp.zeros((3,))}
        pool = GradientPool(params, pad_to=1)
        bounds = tuple(pool.bucket_boundaries(64))
        assert len(bounds) > 1 and len({e - s for s, e in bounds}) > 1, \\
            "want multiple ragged buckets"
        ring = get_algorithm("pallas_ring")
        def f(g):
            r = bucketed_reduce(g, bounds, ("data",), "bfloat16",
                                algo=ring)
            p = bucketed_reduce(g, bounds, ("data",), "bfloat16")
            return r, p
        sm = compat_shard_map(f, mesh=mesh, in_specs=P("data"),
                              out_specs=(P(None), P(None)),
                              axis_names={"data"})
        rng = np.random.default_rng(3)
        g = jnp.asarray(rng.normal(size=8 * pool.size), jnp.float32)
        with compat_set_mesh(mesh):
            r, p = jax.jit(sm)(g)
        np.testing.assert_allclose(np.asarray(r), np.asarray(p), atol=0.1)
        print("OK")
    """)


@pytest.mark.slow
def test_ring_reduces_csc_compacted_wire_buffer():
    """CSC + pallas_ring: the ring reduces the compacted k*chunk wire
    buffer; selection, means, and the flat norm census must match the
    psum-backed run exactly (f32 wire keeps it tight)."""
    run_multi_device("""
        from repro.core import csc
        from repro.configs.base import GradientFlowConfig
        from repro.parallel.topology import get_algorithm
        mesh = compat_make_mesh((8,), ("data",))
        CHUNK, NCHUNK = 64, 8
        POOL = CHUNK * NCHUNK
        def run(algo):
            cfg = GradientFlowConfig(mode="csc", chunk_elems=CHUNK,
                                     bucket_elems=3 * CHUNK, sparsity=0.5,
                                     momentum=0.9, wire_dtype="float32",
                                     reduce_axes=("data",))
            k = 4
            bounds = csc.wire_bucket_boundaries(k, CHUNK, cfg.bucket_elems)
            def step(shard_val):
                g = jnp.full((POOL,), shard_val[0])
                state = csc.CSCState(hg=jnp.zeros((POOL,)),
                                     chunk_norms=jnp.arange(NCHUNK, 0, -1.0))
                res = csc.csc_reduce(g, state, cfg, num_selected=k,
                                     bucket_boundaries=bounds,
                                     num_data_shards=8, algo=algo)
                return res.grads, res.elem_mask, res.state.chunk_norms
            sm = compat_shard_map(step, mesh=mesh, in_specs=P("data"),
                                  out_specs=(P(None),) * 3,
                                  axis_names={"data"})
            with compat_set_mesh(mesh):
                return jax.jit(sm)(jnp.arange(1.0, 9.0))
        ring = run(get_algorithm("pallas_ring"))
        flat = run(None)
        for a, b in zip(ring, flat):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
        np.testing.assert_allclose(np.asarray(ring[0])[np.asarray(ring[1])],
                                   4.5, rtol=1e-5)
        print("OK")
    """)


@pytest.mark.slow
def test_trainer_end_to_end_pallas_ring_matches_flat():
    """collective_algo='pallas_ring' threads from the config through
    GradientFlow into the train step: a 2-device data mesh trains to the
    same loss trajectory as the flat-psum run (f32 wire)."""
    out = run_multi_device("""
        from repro.configs import get_smoke
        from repro.configs.base import (GradientFlowConfig, OptimizerConfig,
                                        TrainConfig)
        from repro.data.synthetic import SyntheticLM
        from repro.launch.mesh import make_mesh
        from repro.launch.trainer import Trainer

        def run(algo):
            model_cfg, rules = get_smoke("smollm-135m")
            gf = GradientFlowConfig(mode="lazy", bucket_elems=4096,
                                    wire_dtype="float32", warmup_steps=0,
                                    collective_algo=algo)
            cfg = TrainConfig(model=model_cfg, gradientflow=gf,
                              optimizer=OptimizerConfig(
                                  name="momentum_sgd", learning_rate=0.2,
                                  warmup_steps=1, total_steps=20,
                                  schedule="constant"),
                              seq_len=32, global_batch=4, attn_chunk=0)
            mesh = make_mesh((2, 1), ("data", "model"))
            trainer = Trainer(cfg, mesh, rules)
            data = SyntheticLM(model_cfg.vocab_size, seed=0)
            losses = []
            with compat_set_mesh(mesh):
                state = trainer.init_state(jax.random.PRNGKey(0))
                step = trainer.build_train_step(donate=False)
                for t in range(4):
                    state, m = step(state, jax.device_put(
                        data.batch(t, 4, 32)))
                    losses.append(float(m["loss"]))
            return losses

        ring = run("pallas_ring")
        flat = run("flat")
        np.testing.assert_allclose(ring, flat, rtol=1e-5)
        print("OK", ring[-1], flat[-1])
    """, devices=2, timeout=1800)
    assert out.count("OK") == 1
