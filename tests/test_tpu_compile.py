"""The six Pallas kernels compile for a TPU v5e at real sizes.

Each test lowers a kernel with ``interpret=False`` for a *described*
``v5e:2x2`` topology (the TPU compiler is installed; no chip is
attached) at the smollm-135m gradient-pool size — the ring on a 4-way
``data`` mesh and along ``data`` of a 2x2 data x model mesh — and
checks that the compiled HLO holds the Mosaic kernel
(``tpu_custom_call``). Mosaic refuses here exactly what it would
refuse on the chip: unaligned slices and DMAs, illegal block shapes,
device ids it cannot address.

The topology, and everything built from it, is built inside the module
fixtures below, never while a module is imported: only the worker that
runs this file loads the TPU library. Where no topology can be
described, every test skips. The persistent compilation cache is off
around these compiles (a described-topology compile cannot be read back
without a chip).
"""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core.pool import GradientPool
from repro.kernels import chunk_l1norm as cl
from repro.kernels import csc_compact as cc
from repro.kernels import fused_update as fu
from repro.kernels import pool_pack as pp
from repro.kernels import pool_unpack as pu
from repro.kernels import ring_reduce as rr
from repro.models.registry import build_model
from repro.parallel import sharding as sh

CHUNK = 32768      # the paper's CSC chunk (GradientFlowConfig default)
SPARSITY = 0.85    # CSC default: k = 15% of the chunks


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / plugin in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", params=[(4, 1), (2, 2)],
                ids=["4x1", "2x2"])
def data_mesh(request, topo):
    """A data x model mesh of the four described chips: the trainer's
    4-way data mesh, and a 2x2 one where each model column runs its own
    2-rank ring along data."""
    return Mesh(np.array(topo.devices).reshape(request.param),
                ("data", "model"))


@pytest.fixture(scope="module")
def pool():
    """smollm-135m's gradient pool (one chip: nothing model-sharded),
    padded to whole CSC chunks as the trainer pads it."""
    cfg, rules = get_arch("smollm-135m")
    specs = sh.localize_specs(build_model(cfg).param_specs(), rules, 1)
    return GradientPool(sh.abstract_params(specs), pad_to=CHUNK)


def _compile(fn, *args):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo, "no Mosaic kernel in the compiled HLO"
    return compiled, time.perf_counter() - t0


def _vec(sharding, n, dtype=jnp.float32):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def test_pool_pack_compiles_at_smollm_pool(one_chip, pool, capsys):
    leaves = [_vec(one_chip, s) for s in pool.sizes]
    _, secs = _compile(
        lambda *l: pp.pool_pack(l, pool.offsets, pool.sizes, pool.size, 0,
                                "bfloat16", interpret=False), *leaves)
    p = pp.plan(pool.offsets, pool.sizes, pool.size, 0, jnp.float32,
                jnp.bfloat16)
    with capsys.disabled():
        print(f"\npool_pack: {pool.size} elems, {len(pool.sizes)} leaves, "
              f"{p['num_tiles']} tiles, {p['num_windows']} DMA windows: "
              f"lower+compile {secs:.1f} s")


def test_pool_pack_census_compiles_at_smollm_pool(one_chip, pool):
    leaves = [_vec(one_chip, s) for s in pool.sizes]
    staging = _vec(one_chip, pool.size)
    _compile(lambda st, *l: pp.pool_pack(
        l, pool.offsets, pool.sizes, pool.size, CHUNK, "float32",
        staging=st, interpret=False), staging, *leaves)


def test_pool_unpack_update_compiles_at_smollm_pool(one_chip, pool,
                                                     capsys):
    n = pool.size
    ratios = _vec(one_chip, len(pool.sizes) + 1)
    _, secs = _compile(
        lambda m, g, mo, mk, r: pu.pool_unpack_update(
            m, g, mo, mk, pool.offsets, pool.sizes, lr=0.1, momentum=0.9,
            weight_decay=1e-4, ratios=r, interpret=False),
        _vec(one_chip, n), _vec(one_chip, n), _vec(one_chip, n),
        _vec(one_chip, n, jnp.bool_), ratios)
    p = pu.plan(pool.offsets, pool.sizes, n, jnp.float32, has_ratios=True)
    with capsys.disabled():
        print(f"\npool_unpack_update: {n} elems, {p['num_tiles']} tiles, "
              f"{p['num_windows']} DMA windows: lower+compile {secs:.1f} s")


def test_fused_update_compiles_at_smollm_pool(one_chip, pool):
    n = pool.size
    _compile(lambda m, g, mo, mk: fu.fused_update(
        m, g, mo, mk, lr=0.1, momentum=0.9, weight_decay=1e-4,
        interpret=False),
        _vec(one_chip, n), _vec(one_chip, n), _vec(one_chip, n),
        _vec(one_chip, n, jnp.bool_))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chunk_l1norm_compiles_at_smollm_pool(one_chip, pool, dtype):
    _compile(lambda x: cl.chunk_l1norm(x, CHUNK, interpret=False),
             _vec(one_chip, pool.size, dtype))


def test_csc_compact_compiles_at_smollm_pool(one_chip, pool):
    k = int(round((1 - SPARSITY) * pool.size // CHUNK))
    _compile(lambda x, i: cc.csc_compact(x, i, CHUNK, interpret=False),
             _vec(one_chip, pool.size),
             jax.ShapeDtypeStruct((k,), jnp.int32, sharding=one_chip))


@pytest.mark.parametrize("wire", [jnp.bfloat16, jnp.int8])
def test_ring_allreduce_compiles_on_data_mesh(data_mesh, pool, wire):
    """One lazy bucket of the smollm pool (the launcher's 4M-element
    θ), reduced over ``data`` inside a shard_map, as GradientFlow's
    ``pallas_ring`` issues it; peers addressed by mesh coordinates with
    ``model`` left at the device's own."""
    n = pool.bucket_boundaries(1 << 22)[0][1]
    ranks = data_mesh.shape["data"]

    def reduce(x):
        return rr.ring_allreduce(x[0, 0], "data", ranks,
                                 collective_id=3)[None, None]

    spec = P("data", "model")
    x = jax.ShapeDtypeStruct((ranks, data_mesh.shape["model"], n), wire,
                             sharding=NamedSharding(data_mesh, spec))
    _compile(jax.shard_map(reduce, mesh=data_mesh, in_specs=spec,
                           out_specs=spec, check_vma=False), x)
