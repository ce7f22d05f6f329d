"""Ahead-of-time compiles of the streaming path's kernel for a TPU v5e.

The TPU compiler is installed with JAX, so ``window_reduce_fwd`` is
compiled here for a described (not attached) v5e chip at the batch sizes
the pipeline launches: the benchmark's 200,000 events x 4,096 segments,
a replay batch, a cold-range scan and a tiny late drain.  A compile that
passes shows the kernel's blocks and memory fit the chip; it runs
nothing.  This is the only test file that describes the chip: the
topology is described inside a fixture, after a test has started, so
every test worker collects the same tests and only the one that runs
them loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.window_reduce import window_reduce_fwd


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.mark.parametrize("n_events,n_segments", [
    (200_000, 4096),     # benchmarks/bench_alerts.py kernel size
    (20_000, 256),       # a replay batch
    (300, 50),           # a cold-range scan
    (5, 3),              # a small late-event drain
])
def test_window_reduce_compiles_natively_for_v5e(one_chip,
                                                 no_persistent_cache,
                                                 n_events, n_segments):
    vals = jax.ShapeDtypeStruct((n_events,), jnp.float32, sharding=one_chip)
    segs = jax.ShapeDtypeStruct((n_events,), jnp.int32, sharding=one_chip)
    compiled = window_reduce_fwd.lower(
        vals, segs, num_segments=n_segments, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
