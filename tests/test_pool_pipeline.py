"""Single-pass gradient-pool pipeline tests: pack/unpack round-trips vs the
legacy ravel/unravel semantics (bit-for-bit), fused norms vs the census
oracle, kernel-vs-ref twins, and the donation/aliasing contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import OptimizerConfig
from repro.core.pool import GradientPool
from repro.kernels import ops, ref
from repro.kernels.pool_pack import pool_pack as k_pack
from repro.kernels.pool_unpack import pool_unpack_update as k_unpack
from repro.optim import sgd

CHUNK = 256
SIZES = [(7,), (33, 5), (2, 3, 4), (129,), (64, 2)]
# Forced-tile tests: a kernel tile is a whole number of 1-D granules
# (tiling.GRANULE = 1024 elements), so these trees span several granules
# and keep unaligned segment boundaries (6,627 elements; 6,656 padded).
BIG_SIZES = [(7,), (33, 50), (2, 3, 400), (1290,), (64, 20)]


def make_tree(dtype=jnp.float32, seed=0, sizes=SIZES):
    ks = jax.random.split(jax.random.PRNGKey(seed), len(sizes))
    return {f"t{i}": jax.random.normal(k, s, jnp.float32).astype(dtype)
            for i, (k, s) in enumerate(zip(ks, sizes))}


def concat_oracle(pool: GradientPool, tree, dtype):
    """The pre-pipeline ravel, kept as an independent oracle."""
    flat = [leaf.reshape((-1,)).astype(dtype)
            for leaf in reversed(jax.tree_util.tree_leaves(tree))]
    if pool.padding:
        flat.append(jnp.zeros((pool.padding,), dtype))
    return jnp.concatenate(flat)


@pytest.mark.parametrize("wire", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("pad_to", [1, CHUNK])
def test_pack_matches_concat_oracle_bitexact(wire, pad_to):
    tree = make_tree()
    pool = GradientPool(tree, pad_to=pad_to)
    got, _ = pool.pack(tree, dtype=wire)
    want = concat_oracle(pool, tree, wire)
    assert got.dtype == jnp.dtype(wire)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # ravel is a thin wrapper over the same path
    np.testing.assert_array_equal(np.asarray(pool.ravel(tree, dtype=wire)),
                                  np.asarray(want))


def test_pack_unravel_roundtrip_bitexact():
    tree = make_tree()
    pool = GradientPool(tree, pad_to=CHUNK)
    packed, _ = pool.pack(tree)
    back = pool.unravel(packed)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("wire", [jnp.float32, jnp.bfloat16])
def test_fused_norms_equal_census_of_ravel(wire):
    tree = make_tree()
    pool = GradientPool(tree, pad_to=CHUNK)
    _, norms = pool.pack(tree, dtype=wire, norms_chunk=CHUNK)
    want = ref.chunk_l1norm(pool.ravel(tree, dtype=wire), CHUNK)
    assert norms.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(norms), np.asarray(want))


def test_pack_into_threads_staging_across_steps():
    """Steady-state shape: the staging buffer from step t is the input of
    step t+1 and each step's pool matches a fresh pack exactly."""
    pool = GradientPool(make_tree(), pad_to=CHUNK)
    staging = jnp.zeros((pool.size,), jnp.float32)
    for seed in (1, 2, 3):
        tree = make_tree(seed=seed)
        p, norms, staging = pool.pack_into(staging, tree,
                                           dtype=jnp.bfloat16,
                                           norms_chunk=CHUNK)
        fresh, fresh_norms = pool.pack(tree, dtype=jnp.bfloat16,
                                       norms_chunk=CHUNK)
        np.testing.assert_array_equal(np.asarray(p), np.asarray(fresh))
        np.testing.assert_array_equal(np.asarray(norms),
                                      np.asarray(fresh_norms))


@pytest.mark.parametrize("wire", [jnp.float32, jnp.bfloat16])
def test_pool_pack_kernel_matches_ref(wire):
    tree = make_tree()
    pool = GradientPool(tree, pad_to=CHUNK)
    leaves = pool.flat_leaves(tree)
    got_p, got_n = k_pack(tuple(leaves), pool.offsets, pool.sizes,
                          pool.size, CHUNK, jnp.dtype(wire).name,
                          interpret=True)
    want_p, want_n, _ = ref.pool_pack(leaves, pool.offsets, pool.size,
                                      CHUNK, wire)
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
    np.testing.assert_allclose(np.asarray(got_n), np.asarray(want_n),
                               rtol=2e-5)


def test_pool_pack_ops_dispatch_matches_ref():
    tree = make_tree()
    pool = GradientPool(tree, pad_to=CHUNK)
    leaves = pool.flat_leaves(tree)
    got_p, got_n, _ = ops.pool_pack(leaves, pool.offsets, pool.sizes,
                                    pool.size, CHUNK, jnp.bfloat16)
    want_p, want_n, _ = ref.pool_pack(leaves, pool.offsets, pool.size,
                                      CHUNK, jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
    np.testing.assert_allclose(np.asarray(got_n), np.asarray(want_n),
                               rtol=2e-5)


@pytest.mark.parametrize("has_scale", [False, True])
@pytest.mark.parametrize("mask_frac", [0.0, 0.4, 1.0])
def test_pool_unpack_update_kernel_matches_ref(has_scale, mask_frac):
    pool = GradientPool(make_tree(), pad_to=CHUNK)
    n = pool.size
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    master = jax.random.normal(ks[0], (n,))
    grads = jax.random.normal(ks[1], (n,))
    mom = jax.random.normal(ks[2], (n,))
    mask = jax.random.bernoulli(ks[3], mask_frac, (n,))
    scale = jnp.abs(jax.random.normal(ks[4], (n,))) if has_scale else None
    got_leaves, got_mom = k_unpack(
        master, grads, mom, mask, pool.offsets, pool.sizes, lr=0.05,
        momentum=0.9, weight_decay=1e-4, scale=scale, interpret=True)
    want_leaves, want_mom = ref.pool_unpack_update(
        master, grads, mom, mask, pool.offsets, pool.sizes, lr=0.05,
        momentum=0.9, weight_decay=1e-4, scale=scale)
    np.testing.assert_allclose(np.asarray(got_mom), np.asarray(want_mom),
                               rtol=1e-6, atol=1e-6)
    for g, w in zip(got_leaves, want_leaves):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-6)


def test_update_unpack_equals_update_pool_plus_unravel():
    """The fused update side is bit-compatible with the two-pass legacy
    (update_pool then unravel) it replaces."""
    tree = make_tree()
    pool = GradientPool(tree, pad_to=CHUNK)
    n = pool.size
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    master = pool.ravel(tree)
    grads = jax.random.normal(ks[0], (n,))
    state = sgd.SGDState(momentum=jax.random.normal(ks[1], (n,)))
    mask = jax.random.bernoulli(ks[2], 0.5, (n,))
    cfg = OptimizerConfig(momentum=0.9, weight_decay=1e-4)
    params_fused, st_fused = sgd.update_unpack(
        pool, master, grads, state, mask, cfg, 0.1)
    new_master, st_legacy = sgd.update_pool(master, grads, state, mask,
                                            cfg, 0.1)
    params_legacy = pool.unravel(new_master)
    np.testing.assert_array_equal(np.asarray(st_fused.momentum),
                                  np.asarray(st_legacy.momentum))
    for a, b in zip(jax.tree_util.tree_leaves(params_fused),
                    jax.tree_util.tree_leaves(params_legacy)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pool_and_momentum_buffers_are_donated():
    """The jitted pipeline step must alias its pool-form buffers: the
    staging pool and the momentum pool come back at the same address, so
    steady-state steps allocate no new pool-sized buffers."""
    tree = make_tree()
    pool = GradientPool(tree, pad_to=CHUNK)
    cfg = OptimizerConfig(momentum=0.9, weight_decay=1e-4)

    def step(staging, mom_state, grads_tree, master):
        gpool, _, staging = pool.pack_into(staging, grads_tree)
        mask = jnp.ones((pool.size,), bool)
        params, mom_state = sgd.update_unpack(pool, master, gpool,
                                              mom_state, mask, cfg, 0.1)
        return staging, mom_state, params

    jstep = jax.jit(step, donate_argnums=(0, 1))
    staging = jnp.zeros((pool.size,), jnp.float32)
    mom = sgd.SGDState(momentum=jnp.zeros((pool.size,), jnp.float32))
    master = pool.ravel(tree)
    staging_ptr = staging.unsafe_buffer_pointer()
    mom_ptr = mom.momentum.unsafe_buffer_pointer()
    staging2, mom2, _ = jstep(staging, mom, make_tree(seed=3), master)
    assert staging.is_deleted() and mom.momentum.is_deleted()
    assert staging2.unsafe_buffer_pointer() == staging_ptr
    assert mom2.momentum.unsafe_buffer_pointer() == mom_ptr


def test_kernel_pack_into_wire_staging_aliases_and_threads():
    """ROADMAP 'pack staging donation', closed: the streaming pack kernel
    accepts a donated WIRE-dtype staging buffer via input_output_aliases.
    The compiled step must alias the POOL output itself to the staging
    parameter (output {0} <- param 0: the pool IS the next step's
    staging, unlike the ref path, which aliases only its source-dtype
    staging output), consume the donated input, dispatch to the kernel,
    and match a fresh pack exactly while threading across steps."""
    import re

    pool = GradientPool(make_tree(), pad_to=CHUNK)

    def step(staging, grads_tree):
        p, norms, _ = pool.pack_into(staging, grads_tree,
                                     dtype=jnp.bfloat16, norms_chunk=CHUNK,
                                     use_kernels=True)
        return p, norms  # p is the staging for the next step

    jstep = jax.jit(step, donate_argnums=(0,))

    # (1) the aliasing contract, read off the compiled executable: the
    # wire staging parameter feeds the pool output buffer. (Pointer
    # equality at run time is best-effort on the CPU allocator and not
    # asserted; the alias entry is the compile-level guarantee.)
    txt = jstep.lower(jnp.zeros((pool.size,), jnp.bfloat16),
                      make_tree()).compile().as_text()
    m = re.search(r"input_output_alias=\{ \{0\}: \(0, \{\}", txt)
    assert m, "pool output is not aliased to the staging parameter"

    # (2) donation consumes the input buffer
    before = dict(ops.dispatch_counts)
    staging = jnp.zeros((pool.size,), jnp.bfloat16)
    first = staging
    staging, _ = jstep(staging, make_tree(seed=9))
    assert first.is_deleted()

    # (3) threading: each step's pool (== next staging) matches a fresh
    # pack bit-for-bit, and the kernel — not the ref twin — ran
    for seed in (1, 2, 3):
        staging, norms = jstep(staging, make_tree(seed=seed))
        fresh, fresh_norms = pool.pack(make_tree(seed=seed),
                                       dtype=jnp.bfloat16,
                                       norms_chunk=CHUNK, use_kernels=True)
        np.testing.assert_array_equal(np.asarray(staging),
                                      np.asarray(fresh))
        np.testing.assert_allclose(np.asarray(norms),
                                   np.asarray(fresh_norms), rtol=2e-5)
    assert ops.dispatch_counts.get("pool_pack.kernel", 0) > \
        before.get("pool_pack.kernel", 0)
    assert ops.dispatch_counts.get("pool_pack.ref", 0) == \
        before.get("pool_pack.ref", 0)


def test_kernel_pack_into_source_dtype_staging_still_routes_to_ref():
    """The legacy contract is unchanged: a source-dtype staging buffer
    (staging != wire dtype) keeps the ref twin's stage-then-cast path even
    when kernels are requested."""
    pool = GradientPool(make_tree(), pad_to=CHUNK)
    staging = jnp.zeros((pool.size,), jnp.float32)
    before = dict(ops.dispatch_counts)
    tree = make_tree(seed=5)
    p, _, staging2 = pool.pack_into(staging, tree, dtype=jnp.bfloat16,
                                    norms_chunk=CHUNK, use_kernels=True)
    assert staging2.dtype == jnp.float32 and p.dtype == jnp.bfloat16
    fresh, _ = pool.pack(tree, dtype=jnp.bfloat16, norms_chunk=CHUNK)
    np.testing.assert_array_equal(np.asarray(p), np.asarray(fresh))
    assert ops.dispatch_counts.get("pool_pack.ref", 0) > \
        before.get("pool_pack.ref", 0)


def _mixed_tree(seed=0):
    tree = make_tree(seed=seed, sizes=BIG_SIZES)
    tree["t1"] = tree["t1"].astype(jnp.bfloat16)
    return tree


# No-census packs: (tree, wire dtype, pad_to, donated wire staging).
NO_CENSUS_CASES = {
    "f32_to_bf16_donated": (make_tree, jnp.bfloat16, CHUNK, True),
    "f32_to_f32_no_staging": (make_tree, jnp.float32, CHUNK, False),
    "mixed_dtype_tree": (_mixed_tree, jnp.bfloat16, CHUNK, False),
    "sizes_not_1024_multiples": (
        lambda seed=0: make_tree(seed=seed, sizes=BIG_SIZES),
        jnp.bfloat16, 1, True),
    "pad_to_tail": (lambda seed=0: make_tree(seed=seed, sizes=BIG_SIZES),
                    jnp.float32, 1000, True),
}


@pytest.mark.parametrize("entry", ["ops", "pool"])
@pytest.mark.parametrize("case", sorted(NO_CENSUS_CASES))
def test_no_census_pack_takes_xla_pass_bit_equal(case, entry):
    """A pack that asks for no census is one XLA pass
    (``pool_pack.xla``), never the Mosaic kernel, and its pool is bit
    for bit the ref twin's and the kernel's (interpret mode). A donated
    wire-dtype staging buffer is consumed and the pool comes back as the
    next step's staging."""
    make, wire, pad_to, staged = NO_CENSUS_CASES[case]
    tree = make(seed=4)
    pool = GradientPool(tree, pad_to=pad_to)
    leaves = pool.flat_leaves(tree)
    want, _, _ = ref.pool_pack(leaves, pool.offsets, pool.size, 0, wire)
    kern, _ = k_pack(tuple(leaves), pool.offsets, pool.sizes, pool.size, 0,
                     jnp.dtype(wire).name, interpret=True)
    staging = jnp.zeros((pool.size,), wire) if staged else None
    before = dict(ops.dispatch_counts)
    if entry == "ops":
        got, norms, staging_out = ops.pool_pack(
            leaves, pool.offsets, pool.sizes, pool.size, 0, wire,
            out=staging)
    elif staged:
        got, norms, staging_out = pool.pack_into(
            staging, tree, dtype=wire, use_kernels=True)
    else:
        got, norms = pool.pack(tree, dtype=wire, use_kernels=True)
        staging_out = None
    assert ops.dispatch_counts.get("pool_pack.xla", 0) == \
        before.get("pool_pack.xla", 0) + 1
    assert ops.dispatch_counts.get("pool_pack.kernel", 0) == \
        before.get("pool_pack.kernel", 0)
    assert norms is None and got.dtype == jnp.dtype(wire)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(kern))
    if staged:
        assert staging.is_deleted()
        assert staging_out is got


@pytest.mark.parametrize("entry", ["ops", "pool"])
def test_census_pack_still_takes_kernel(entry):
    tree = make_tree()
    pool = GradientPool(tree, pad_to=CHUNK)
    before = dict(ops.dispatch_counts)
    if entry == "ops":
        ops.pool_pack(pool.flat_leaves(tree), pool.offsets, pool.sizes,
                      pool.size, CHUNK, jnp.bfloat16)
    else:
        pool.pack(tree, dtype=jnp.bfloat16, norms_chunk=CHUNK,
                  use_kernels=True)
    assert ops.dispatch_counts.get("pool_pack.kernel", 0) == \
        before.get("pool_pack.kernel", 0) + 1
    assert ops.dispatch_counts.get("pool_pack.xla", 0) == \
        before.get("pool_pack.xla", 0)


@pytest.mark.parametrize("chunk", [0, CHUNK], ids=["no_census", "census"])
def test_pack_lowered_for_tpu_holds_kernel_only_with_census(chunk,
                                                            monkeypatch):
    """Lowered for a TPU (ops told it runs compiled), an f32 -> bf16 pack
    holds a Mosaic call (``tpu_custom_call``) only where a census is
    asked, and the no-census pass makes no f32 buffer of the pool's
    size (the ref twin's stage-in-f32-then-cast would)."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    pool = GradientPool(make_tree(sizes=BIG_SIZES), pad_to=CHUNK)
    leaves = [jax.ShapeDtypeStruct((n,), jnp.float32) for n in pool.sizes]

    def pack(*ls):
        return ops.pool_pack(list(ls), pool.offsets, pool.sizes, pool.size,
                             chunk, jnp.bfloat16)[0]

    txt = jax.jit(pack).trace(*leaves).lower(
        lowering_platforms=("tpu",)).as_text()
    assert ("tpu_custom_call" in txt) == (chunk > 0)
    if not chunk:
        assert f"tensor<{pool.size}xf32>" not in txt
        assert f"tensor<{pool.size}xbf16>" in txt


def test_pack_mixed_dtype_tree_promotes_like_concatenate():
    """Regression: a pytree with mixed leaf dtypes must pack (per-leaf
    promotion to the staging dtype), as the old concatenate-ravel did."""
    tree = {"a": jnp.ones((8,), jnp.bfloat16), "b": jnp.ones((8,))}
    pool = GradientPool(tree)
    p, _ = pool.pack(tree)
    assert p.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(p), 1.0)
    p16, _ = pool.pack(tree, dtype=jnp.bfloat16)
    assert p16.dtype == jnp.bfloat16


def test_update_unpack_restores_param_dtype():
    """Regression: the fused unpack must hand back leaves in the declared
    param dtype (as unravel does), not the f32 master dtype."""
    tree = {"w": jnp.ones((16,), jnp.bfloat16)}
    pool = GradientPool(tree)
    cfg = OptimizerConfig(momentum=0.9, weight_decay=0.0)
    master = pool.ravel(tree, dtype=jnp.float32)
    params, _ = sgd.update_unpack(
        pool, master, jnp.zeros((pool.size,)),
        sgd.SGDState(momentum=jnp.zeros((pool.size,))),
        jnp.ones((pool.size,), bool), cfg, 0.1)
    assert params["w"].dtype == jnp.bfloat16


# -- streaming tiled kernels: tile-boundary coverage ------------------------


def test_tile_schedule_covers_pool_exactly_once():
    """Every pool element is covered by exactly one copy/fill, including
    segments straddling tile edges and the padding tail."""
    from repro.kernels import tiling
    pool = GradientPool(make_tree(), pad_to=CHUNK)
    for tile in (100, CHUNK, 3 * CHUNK, pool.size + 5):
        sched = tiling.tile_schedule(pool.offsets, pool.sizes, pool.size,
                                     tile)
        hits = np.zeros((pool.size,), np.int32)
        for c in sched.copies + sched.fills:
            lo = c.tile * tile + c.dst_lo
            hits[lo:lo + c.elems] += 1
            if c.leaf >= 0:  # src range stays inside its leaf
                assert 0 <= c.src_lo and \
                    c.src_lo + c.elems <= pool.sizes[c.leaf]
        np.testing.assert_array_equal(hits, 1)
        assert sched.num_tiles == -(-pool.size // tile)


@pytest.mark.parametrize("wire", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tile_chunks", [4, 8])
def test_pack_kernel_straddling_tile_boundaries(wire, tile_chunks):
    """Small forced tiles (one and two granules): every segment larger
    than a tile straddles at least one boundary; output must still match
    the ref twin exactly."""
    tree = make_tree(sizes=BIG_SIZES)
    pool = GradientPool(tree, pad_to=CHUNK)
    leaves = pool.flat_leaves(tree)
    got_p, got_n = k_pack(tuple(leaves), pool.offsets, pool.sizes,
                          pool.size, CHUNK, jnp.dtype(wire).name,
                          tile_elems=tile_chunks * CHUNK, interpret=True)
    want_p, want_n, _ = ref.pool_pack(leaves, pool.offsets, pool.size,
                                      CHUNK, wire)
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
    np.testing.assert_allclose(np.asarray(got_n), np.asarray(want_n),
                               rtol=2e-5)


@pytest.mark.parametrize("tile", [1024, 2048, 3072])
def test_pack_kernel_ragged_final_tile(tile):
    """Pools whose (padded) size is NOT a multiple of the tile: the final
    grid step is a ragged edge block (no census: pad_to=1 keeps the pool
    size odd too)."""
    tree = make_tree(sizes=BIG_SIZES)
    pool = GradientPool(tree, pad_to=1)
    assert pool.size % tile != 0
    leaves = pool.flat_leaves(tree)
    got_p, got_n = k_pack(tuple(leaves), pool.offsets, pool.sizes,
                          pool.size, 0, "float32", tile_elems=tile,
                          interpret=True)
    want_p, _, _ = ref.pool_pack(leaves, pool.offsets, pool.size, 0,
                                 jnp.float32)
    assert got_n is None
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))


@pytest.mark.parametrize("tile", [1024, 2048])
def test_unpack_kernel_straddling_and_ragged_tiles(tile):
    pool = GradientPool(make_tree(sizes=BIG_SIZES), pad_to=CHUNK)
    n = pool.size
    ks = jax.random.split(jax.random.PRNGKey(21), 4)
    master = jax.random.normal(ks[0], (n,))
    grads = jax.random.normal(ks[1], (n,))
    mom = jax.random.normal(ks[2], (n,))
    mask = jax.random.bernoulli(ks[3], 0.5, (n,))
    got_l, got_m = k_unpack(master, grads, mom, mask, pool.offsets,
                            pool.sizes, lr=0.05, momentum=0.9,
                            weight_decay=1e-4, tile_elems=tile,
                            interpret=True)
    want_l, want_m = ref.pool_unpack_update(
        master, grads, mom, mask, pool.offsets, pool.sizes, lr=0.05,
        momentum=0.9, weight_decay=1e-4)
    np.testing.assert_allclose(np.asarray(got_m), np.asarray(want_m),
                               rtol=1e-6, atol=1e-6)
    for g, w in zip(got_l, want_l):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-6)


def test_unpack_kernel_lars_ratios_match_expanded_scale():
    """The in-kernel per-tile ratio expansion must equal the ref path fed
    the pool-sized expanded scale (the buffer the kernel never builds)."""
    pool = GradientPool(make_tree(sizes=BIG_SIZES), pad_to=CHUNK)
    n = pool.size
    ks = jax.random.split(jax.random.PRNGKey(23), 4)
    master = jax.random.normal(ks[0], (n,))
    grads = jax.random.normal(ks[1], (n,))
    mom = jax.random.normal(ks[2], (n,))
    mask = jax.random.bernoulli(ks[3], 0.5, (n,))
    ratios = jnp.abs(jax.random.normal(jax.random.PRNGKey(5),
                                       (pool.num_tensors,))) + 0.1
    expanded = ref.expand_ratios(ratios, pool.sizes, n)
    got_l, got_m = k_unpack(master, grads, mom, mask, pool.offsets,
                            pool.sizes, lr=0.05, momentum=0.9,
                            weight_decay=1e-4, ratios=ratios,
                            tile_elems=1024, interpret=True)
    want_l, want_m = ref.pool_unpack_update(
        master, grads, mom, mask, pool.offsets, pool.sizes, lr=0.05,
        momentum=0.9, weight_decay=1e-4, scale=expanded)
    np.testing.assert_allclose(np.asarray(got_m), np.asarray(want_m),
                               rtol=1e-6, atol=1e-6)
    for g, w in zip(got_l, want_l):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-6)


def test_sgd_update_unpack_ratios_kernel_vs_scale_ref():
    """optim-level: the kernel path fed per-tensor ratios agrees with the
    non-kernel path fed the expanded scale (what the trainer switches
    between)."""
    from repro.optim.lars import LARSScaler
    tree = make_tree()
    pool = GradientPool(tree, pad_to=CHUNK)
    lars = LARSScaler(pool)
    cfg = OptimizerConfig(momentum=0.9, weight_decay=1e-4, name="lars")
    master = pool.ravel(tree)
    ks = jax.random.split(jax.random.PRNGKey(31), 2)
    grads = jax.random.normal(ks[0], (pool.size,))
    mask = jax.random.bernoulli(ks[1], 0.5, (pool.size,))
    state = sgd.SGDState(momentum=jnp.zeros((pool.size,)))
    r = lars.ratios(master, grads, cfg, mask)
    p_kern, st_kern = sgd.update_unpack(pool, master, grads, state, mask,
                                        cfg, 0.1, ratios=r,
                                        use_kernels=True)
    p_ref, st_ref = sgd.update_unpack(pool, master, grads, state, mask,
                                      cfg, 0.1, scale=lars.expand(r),
                                      use_kernels=False)
    np.testing.assert_allclose(np.asarray(st_kern.momentum),
                               np.asarray(st_ref.momentum),
                               rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(p_kern),
                    jax.tree_util.tree_leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_streaming_kernels_match_ref_above_retired_4m_bound():
    """>4M-element pool: the old whole-pool variants deferred to the refs
    here; the streaming kernels must now run — and agree — at this size
    (dispatch goes through ops, which no longer has a size fallback)."""
    from repro.kernels import ops as kops
    assert not hasattr(kops, "_POOL_KERNEL_MAX_ELEMS")
    big = {"a": jnp.ones((2_100_000,)), "b": jnp.ones((2_100_000,)),
           "c": jnp.ones((999,))}
    pool = GradientPool(big, pad_to=32768)
    assert pool.size > 4 * 1024 * 1024
    leaves = pool.flat_leaves(big)
    got_p, got_n, _ = kops.pool_pack(leaves, pool.offsets, pool.sizes,
                                     pool.size, 32768, jnp.bfloat16)
    want_p, want_n, _ = ref.pool_pack(leaves, pool.offsets, pool.size,
                                      32768, jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
    np.testing.assert_allclose(np.asarray(got_n), np.asarray(want_n),
                               rtol=2e-5)
    n = pool.size
    master = pool.ravel(big)
    mom = jnp.zeros((n,))
    mask = jnp.ones((n,), bool)
    grads = got_p.astype(jnp.float32)
    got_l, got_m = kops.update_unpack(master, grads, mom, mask,
                                      pool.offsets, pool.sizes, lr=0.05,
                                      momentum=0.9, weight_decay=1e-4)
    want_l, want_m = ref.pool_unpack_update(
        master, grads, mom, mask, pool.offsets, pool.sizes, lr=0.05,
        momentum=0.9, weight_decay=1e-4)
    np.testing.assert_allclose(np.asarray(got_m), np.asarray(want_m),
                               rtol=1e-6, atol=1e-6)
    for g, w in zip(got_l, want_l):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-6)


def test_num_chunks_requires_padded_multiple():
    """Regression: the old assertion accepted `pad_to % chunk == 0` even
    when the pool size itself was not chunk-aligned."""
    tree = {"a": jnp.zeros((100,))}
    pool = GradientPool(tree, pad_to=64)  # size 128
    assert pool.num_chunks(64) == 2
    bad = GradientPool(tree, pad_to=1)  # size 100, NOT chunk-aligned
    with pytest.raises(AssertionError):
        bad.num_chunks(64)
