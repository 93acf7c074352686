"""Compiles for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX, and it compiles for a chip that is
described rather than present.  These tests compile the engine's main-path
kernels at real widths and check what the chip's compiler alone can refuse:
block shapes and DMA slices off the tiling, loads from HBM refs, and a
program that does not fit the device's memory.  They say nothing about
results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the tests of this file
all run in the process that loaded it.  The persistent compilation cache is
off around them (a described-chip compile can be written to it but not read
back without a chip).
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.ampc.solvers import _build_mis_solver
from repro.core import dht, msf
from repro.kernels.dht_gather.kernel import (LANES, dht_gather_pallas,
                                             line_layout)

SCALE20_N = 1 << 20          # Graph500 scale-20 vertex count


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("width", [1, 4])
def test_dht_gather_kernel_compiles(one_chip, width):
    # a scale-20 table of `width`-value rows, one 2^16-key lookup wave
    row_width, _, _ = line_layout(width)
    lines = _spec(one_chip, (SCALE20_N * row_width // LANES, LANES),
                  jnp.int32)
    keys = _spec(one_chip, (1 << 16,), jnp.int32)
    compiled = jax.jit(lambda t, k: dht_gather_pallas(
        t, k, row_width=width, n_rows=SCALE20_N, block_q=1024,
        interpret=False)).lower(lines, keys).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    print(f"dht_gather width={width}: {mem}")
    # the output is one 128-lane line per key, and nothing else is staged
    assert mem.output_size_in_bytes < 2 * (1 << 16) * LANES * 4
    assert mem.temp_size_in_bytes < 1 << 20


def test_fused_local_lookup_compiles(one_chip):
    # the local path's one-dispatch lookup on a scale-20 value table
    values = _spec(one_chip, (SCALE20_N,), jnp.int32)
    keys = _spec(one_chip, (1 << 12,), jnp.int32)
    compiled = dht._fused_local_lookup.lower(values, keys, 4, True).compile()
    mem = compiled.memory_analysis()
    print(f"_fused_local_lookup: {mem}")
    assert mem.temp_size_in_bytes < 1 << 24


def test_truncated_prim_keeps_adjacency_dense(one_chip):
    # a row gather from an (n, 3) adjacency would make the compiler copy it
    # row-major with the 3 columns tiled out to 128 lanes (~43x), and all
    # nt searches at once would hold ~1.5 GB of frontiers here; the
    # column-wise gather and the scanned chunks of _PRIM_STATE_BYTES of
    # search state (~6 chunks at this size) keep it to a few chunks' worth
    nt = 1 << 20
    budget = int(np.ceil(nt ** 0.25))
    lane_bytes = 4 * (2 * budget + 3 * 3 * budget + 8)
    assert nt * lane_bytes > 4 * msf._PRIM_STATE_BYTES, "must chunk"
    adj = [_spec(one_chip, (nt, 3), dt)
           for dt in (jnp.int32, jnp.float32, jnp.int32)]
    rank = _spec(one_chip, (nt,), jnp.float32)
    compiled = msf.truncated_prim.lower(*adj, rank, budget,
                                        nt * 3 // 2).compile()
    mem = compiled.memory_analysis()
    print(f"truncated_prim nt={nt}: {mem}")
    # 0.61 GB chunked; 3.8 GB with every search live at once
    assert mem.temp_size_in_bytes < 4 * msf._PRIM_STATE_BYTES


def test_mis_bucket_compiles_without_scatter(one_chip):
    # a solve_many bucket of four graphs (n <= 4,096, m <= 65,536): the
    # sort by sender compiles for the chip, and the chip's compiler puts
    # no scatter back into the waves
    b, nb, e = 4, 1 << 12, 1 << 17
    ids = _spec(one_chip, (b, e), jnp.int32)
    compiled = _build_mis_solver(nb).lower(
        ids, ids, _spec(one_chip, (b, nb), jnp.float32),
        _spec(one_chip, (b, e), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    print(f"mis bucket: {mem}")
    assert not re.search(r"\sscatter\(", compiled.as_text())
    assert mem.temp_size_in_bytes < 1 << 26
