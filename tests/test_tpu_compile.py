"""Compile the store's device programs for a described TPU v5e (no chip needed).

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests catch what interpret mode cannot:
lowerings Mosaic refuses and programs that do not fit the device.  The
topology is described inside a fixture, never at import, and the persistent
compile cache is off around the compiles (an entry written for a described
chip cannot be read back without one).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.merge_runs import ops
from repro.kernels.merge_runs.kernel import merge_runs_pallas

V5E_HBM_BYTES = 16 * 1024**3
YCSB_KEY_WORDS = 8  # 24-byte keys: 6 words, bucketed to 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("newer_rows,older_rows", [
    (4096, 131072),          # L0 flush into L1
    (131072, 1 << 20),       # L1 into a 1M-row L2
])
def test_store_merge_compiles_for_v5e(one_chip, no_persistent_cache, newer_rows, older_rows):
    def column(rows):
        return jax.ShapeDtypeStruct((YCSB_KEY_WORDS + 2, rows), jnp.uint32, sharding=one_chip)

    flag = jax.ShapeDtypeStruct((), jnp.bool_, sharding=one_chip)
    compiled = ops._merge_order.lower(
        column(newer_rows), column(older_rows), flag,
        out_rows=ops.bucket(newer_rows + older_rows),
    ).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES // 4


@pytest.mark.parametrize("g,t", [(8, 128), (16, 1024)])
def test_pallas_merge_compiles_for_v5e(one_chip, no_persistent_cache, g, t):
    keys = jax.ShapeDtypeStruct((g, t), jnp.int32, sharding=one_chip)
    compiled = merge_runs_pallas.lower(keys, keys, keys, keys).compile()
    assert "tpu_custom_call" in compiled.as_text()
