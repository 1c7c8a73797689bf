"""Shared fixtures + the multi-device subprocess harness. NOTE: no
XLA_FLAGS here — smoke tests and benches must see the single real CPU
device; only tests that need a multi-device mesh spawn a subprocess."""
import os
import subprocess
import sys
import textwrap

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_multi_device(body: str, devices: int = 8, timeout: int = 900):
    """Execute ``body`` with N placeholder CPU devices in a subprocess
    (the main pytest process must keep seeing the single real device).

    The prelude provides jax/jnp/np, PartitionSpec ``P``, NamedSharding,
    the collectives helpers, ``N`` (= devices), and the ``smap``
    shorthand over ``compat_shard_map``. Shared by test_distributed /
    test_topology / test_ring_reduce — keep harness fixes here, in ONE
    place (benchmarks/micro.py carries its own inline variant because it
    must run without the test tree installed).
    """
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import sys
        sys.path.insert(0, {SRC!r})
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.parallel.collectives import (compat_make_mesh,
            compat_set_mesh, compat_shard_map)
        N = {devices}

        def smap(f, mesh, in_specs, out_specs, axes):
            return compat_shard_map(f, mesh=mesh, in_specs=in_specs,
                                    out_specs=out_specs, axis_names=axes)
    """) + textwrap.dedent(body)
    # Pinned to the CPU: on a host with a chip the child never competes
    # with this process for the device.
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=timeout,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}")
    return proc.stdout


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
