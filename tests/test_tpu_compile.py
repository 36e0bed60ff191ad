"""The main path's Pallas kernels compile for a described TPU v5e.

Nothing runs: each case lowers a kernel with ``interpret=False`` for one
chip of a ``v5e:2x2`` topology that is described, not attached, and lets
the TPU compiler accept or refuse it (tiling, VMEM, unsupported ops).  The
topology is described inside a fixture, so a worker that is not given
this file never loads the TPU compiler, and the tests skip where the
topology cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ee_gate.ee_gate import ee_gate_pallas
from repro.kernels.minplus.minplus import (banded_minplus_chain_kbest_pallas,
                                           banded_minplus_chain_pallas,
                                           banded_minplus_pallas,
                                           minplus_argmin_pallas)

# the population's shapes: paper_scenario(n_extra_edge=2) has 5 nodes,
# gamma=10 gives 11 depth cells, and the deepest paper profiles (h1-h4,
# 5 blocks) have 4 relax layers
B, N, GP1, L = 64, 5, 11, 4
#: qwen3-4b's padded vocab (151,936 rounded up to a multiple of 2,048)
QWEN3_VOCAB_PAD = 153_600


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _banded(N):
    return (((B, N, GP1), jnp.float32), ((B, L, N, N), jnp.float32),
            ((B, L, N, N), jnp.int32))


BANDED = _banded(N)
#: the largest network the repo relaxes: paper_scenario(n_extra_edge=12)
#: has 15 nodes; E/st enter the kernels as [..., N, N, 1] columns, whose
#: VMEM blocks grow with N squared
N_LARGEST = 15


@pytest.mark.parametrize("lo", [None, 4])
def test_banded_chain_compiles(one_chip, lo):
    _compile(lambda d, e, s: banded_minplus_chain_pallas(
        d, e, s, lo=lo, interpret=False), one_chip, *BANDED)


@pytest.mark.parametrize("K", [1, 3])
def test_banded_chain_kbest_compiles(one_chip, K):
    _compile(lambda d, e, s: banded_minplus_chain_kbest_pallas(
        d, e, s, K, interpret=False), one_chip, *BANDED)


def test_banded_chains_compile_on_largest_network(one_chip):
    _compile(lambda d, e, s: banded_minplus_chain_pallas(
        d, e, s, interpret=False), one_chip, *_banded(N_LARGEST))
    _compile(lambda d, e, s: banded_minplus_chain_kbest_pallas(
        d, e, s, 3, interpret=False), one_chip, *_banded(N_LARGEST))


def test_banded_layer_compiles_at_bench_size(one_chip):
    # the largest single-layer case of benchmarks/bench_kernels.py
    n, g = 64, 48
    _compile(lambda d, e, s: banded_minplus_pallas(d, e, s, interpret=False),
             one_chip, ((n, g + 1), jnp.float32), ((n, n), jnp.float32),
             ((n, n), jnp.int32))


def test_minplus_argmin_compiles(one_chip):
    S = N * GP1
    _compile(lambda d, w: minplus_argmin_pallas(d, w, interpret=False),
             one_chip, ((8, S), jnp.float32), ((S, S), jnp.float32))


@pytest.mark.parametrize("batch", [8, 16])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_ee_gate_compiles(one_chip, batch, dtype):
    _compile(lambda x: ee_gate_pallas(x, interpret=False), one_chip,
             ((batch, QWEN3_VOCAB_PAD), dtype))
