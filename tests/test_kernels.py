"""Per-kernel validation: shape/dtype sweeps, allclose vs the ref.py
pure-jnp oracles (kernels run in interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.chunk_l1norm import chunk_l1norm as k_l1
from repro.kernels.csc_compact import csc_compact as k_compact
from repro.kernels.fused_update import fused_update as k_update


@pytest.mark.parametrize("chunk", [128, 1024, 32768])
@pytest.mark.parametrize("nchunks", [4, 32])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chunk_l1norm_sweep(chunk, nchunks, dtype):
    if chunk * nchunks > 2 ** 21:
        pytest.skip("interpret-mode too slow for this size")
    pool = jax.random.normal(jax.random.PRNGKey(0), (nchunks * chunk,),
                             jnp.float32).astype(dtype)
    got = k_l1(pool, chunk, interpret=True)
    want = ref.chunk_l1norm(pool, chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5)
    assert got.dtype == jnp.float32  # f32 accumulate regardless of input


@pytest.mark.parametrize("chunk", [128, 2048])
@pytest.mark.parametrize("nchunks,k", [(8, 3), (64, 16), (16, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_csc_compact_sweep(chunk, nchunks, k, dtype):
    key = jax.random.PRNGKey(1)
    pool = jax.random.normal(key, (nchunks * chunk,),
                             jnp.float32).astype(dtype)
    idx = jnp.sort(jax.random.permutation(key, nchunks)[:k]).astype(jnp.int32)
    got = k_compact(pool, idx, chunk, interpret=True)
    want = ref.csc_compact(pool, idx, chunk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [1024, 128 * 1024, 128 * 1024 + 512])
@pytest.mark.parametrize("has_scale", [False, True])
@pytest.mark.parametrize("mask_frac", [0.0, 0.3, 1.0])
def test_fused_update_sweep(n, has_scale, mask_frac):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    master = jax.random.normal(ks[0], (n,))
    grads = jax.random.normal(ks[1], (n,))
    mom = jax.random.normal(ks[2], (n,))
    mask = jax.random.bernoulli(ks[3], mask_frac, (n,))
    scale = jnp.abs(jax.random.normal(ks[4], (n,))) if has_scale else None
    got = k_update(master, grads, mom, mask, lr=0.05, momentum=0.9,
                   weight_decay=1e-4, scale=scale, interpret=True)
    want = ref.fused_update(master, grads, mom, mask, lr=0.05, momentum=0.9,
                            weight_decay=1e-4, scale=scale)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-6)


def test_fused_update_mask_semantics():
    """Masked-off elements keep master AND momentum untouched (Alg 1)."""
    n = 4096
    master = jnp.ones((n,))
    grads = jnp.full((n,), 3.0)
    mom = jnp.full((n,), 7.0)
    mask = jnp.zeros((n,), bool).at[: n // 2].set(True)
    new_master, new_mom = k_update(master, grads, mom, mask, lr=0.1,
                                   momentum=0.9, weight_decay=0.0,
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(new_master[n // 2:]), 1.0)
    np.testing.assert_array_equal(np.asarray(new_mom[n // 2:]), 7.0)
    expected_u = 0.9 * 7.0 + 0.1 * 3.0
    np.testing.assert_allclose(np.asarray(new_mom[: n // 2]), expected_u,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new_master[: n // 2]),
                               1.0 - expected_u, rtol=1e-6)


@pytest.mark.parametrize("tile", [0, 3072])
def test_update_unpack_variant_streams_and_matches_fused_update(tile):
    """fused_update's tiled ``update_unpack`` variant: same Algorithm-1
    math as ``fused_update`` (shared ``update_math``), leaves DMA'd out
    per tile instead of a new master pool — including with a ragged
    forced tile (3072: the smallest granule multiple that leaves 4096
    ragged)."""
    from repro.kernels.fused_update import update_unpack as k_uu
    offsets, sizes = (0, 1000, 3500), (1000, 2500, 300)
    n = 4096  # 296 elements of padding
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    master = jax.random.normal(ks[0], (n,))
    grads = jax.random.normal(ks[1], (n,))
    mom = jax.random.normal(ks[2], (n,))
    mask = jax.random.bernoulli(ks[3], 0.5, (n,))
    leaves, new_mom = k_uu(master, grads, mom, mask, offsets, sizes,
                           lr=0.05, momentum=0.9, weight_decay=1e-4,
                           tile_elems=tile, interpret=True)
    want_master, want_mom = k_update(master, grads, mom, mask, lr=0.05,
                                     momentum=0.9, weight_decay=1e-4,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(new_mom), np.asarray(want_mom),
                               rtol=1e-6, atol=1e-6)
    for (off, sz), leaf in zip(zip(offsets, sizes), leaves):
        np.testing.assert_allclose(np.asarray(leaf),
                                   np.asarray(want_master[off:off + sz]),
                                   rtol=1e-6, atol=1e-6)


def test_ops_dispatch_matches_ref():
    """Public ops wrappers agree with refs outside shard_map."""
    chunk, nchunks = 256, 12
    pool = jax.random.normal(jax.random.PRNGKey(3), (nchunks * chunk,))
    np.testing.assert_allclose(np.asarray(ops.chunk_l1norm(pool, chunk)),
                               np.asarray(ref.chunk_l1norm(pool, chunk)),
                               rtol=1e-6)
    idx = jnp.array([0, 5, 11], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(ops.csc_compact(pool, idx, chunk)),
        np.asarray(ref.csc_compact(pool, idx, chunk)))
