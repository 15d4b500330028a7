"""Compile the main path for a described TPU v5e chip, with no chip attached.

Each test lowers one program of the serving or update path at the CONFIG
deployment size and compiles it with the TPU compiler, which refuses what
a chip would refuse: a Pallas block off the lane tiling, a kernel past its
fast-memory budget, a program past HBM.  Nothing runs, so these say
nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.dspc import CONFIG
from repro.core import query as Q
from repro.core.construct import _hub_batch_round
from repro.core.graph import Graph
from repro.core.hybrid import hyb_spc_batch
from repro.core.labels import SPCIndex
from repro.kernels.spc_query.kernel import _spc_query_jit

N = CONFIG.n
CAP_E = 1 << 21           # from_edges' capacity for CONFIG's 2 m slots
HBM_BYTES = 16 << 30      # one v5e chip


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)


def _graph(chip):
    return Graph(src=chip((CAP_E,), jnp.int32), dst=chip((CAP_E,), jnp.int32),
                 m2=chip((), jnp.int32), n=N)


def _index(chip, l_cap):
    rows = (N + 1, l_cap)
    return SPCIndex(hub=chip(rows, jnp.int32), dist=chip(rows, jnp.int32),
                    cnt=chip(rows, jnp.int64), size=chip((N + 1,), jnp.int32),
                    cnt_sum=chip((N + 1,), jnp.int64),
                    overflow=chip((), jnp.int32), n=N)


@pytest.mark.parametrize("l_cap", [64, 128, 256, 512, 1024])
def test_spc_query_kernel_compiles(chip, l_cap):
    """Up to 1024, the label capacity the CONFIG build reaches."""
    i32 = chip((1024, l_cap), jnp.int32)
    f32 = chip((1024, l_cap), jnp.float32)
    compiled = _spc_query_jit.lower(i32, i32, f32, i32, i32, f32,
                                    block_b=128, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_merge_route_compiles(chip):
    pairs = chip((1024,), jnp.int32)
    compiled = Q.batched_query_jit.lower(_index(chip, CONFIG.l_cap), pairs,
                                         pairs).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES


def test_update_chunk_compiles(chip):
    events = chip((CONFIG.update_batch, 3), jnp.int32)
    compiled = hyb_spc_batch.lower(_graph(chip), _index(chip, CONFIG.l_cap),
                                   events).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("l_cap", [1024])
def test_build_round_fits_one_chip(chip, l_cap):
    """One batched-build round at the label capacity the CONFIG build
    reaches (1024 by its 512th hub on a v5e chip) fits HBM: the round's
    temporaries no longer grow with l_cap (2.1 GiB from 256 to 1024)."""
    compiled = _hub_batch_round.lower(
        _graph(chip), _index(chip, l_cap), chip((), jnp.int32),
        CONFIG.construct_batch, None).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
