"""The main path's Pallas kernels compile for a described TPU v5e.

Nothing runs: each case lowers a kernel with ``interpret=False`` for one
chip of a ``v5e:2x2`` topology that is described, not attached, and lets
the TPU compiler accept or refuse it (tiling, VMEM, unsupported ops).  The
topology is described inside a fixture, so a worker that is not given
this file never loads the TPU compiler, and the tests skip where the
topology cannot be described.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get
from repro.kernels.decode_attn import decode_attn as decode_attn_kernel
from repro.kernels.ee_gate.ee_gate import ee_gate_pallas
from repro.kernels.minplus.minplus import (banded_minplus_chain_kbest_pallas,
                                           banded_minplus_chain_pallas,
                                           banded_minplus_pallas,
                                           minplus_argmin_pallas)
from repro.models import transformer as T
from repro.runtime.serve_engine import gate_heads

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
    # a 64-node, 48-step single layer: the largest banded layer shape
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


@pytest.mark.parametrize("rows", [8, 1])
def test_gate_heads_compiles(one_chip, rows):
    """The serving gate's one program over three deployed heads holds the
    kernel three times, each a custom call named ``ee_gate`` (the name the
    benchmark's trace reduction finds the kernel by)."""
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    heads = tuple(arg((rows, QWEN3_VOCAB_PAD), jnp.float32)
                  for _ in range(3))
    text = gate_heads.lower(heads, arg((2,), jnp.float32),
                            interpret=False).compile().as_text()
    calls = re.findall(r"^\s*(?:ROOT )?%?([\w.-]+) = .*custom-call\(", text,
                       re.M)
    assert [re.sub(r"(\.\d+)+$", "", c) for c in calls] == ["ee_gate"] * 3


def test_decode_step_reads_the_cache_in_place(one_chip, monkeypatch):
    """qwen3-4b's decode step at its published widths, batch 8, cache
    4096 (two layers: the scan body is the same) runs attention as the
    ``decode_attn`` kernel on the carried stack: a custom call of that name
    (the name the benchmark's trace finds it by), and no dynamic-slice that
    copies one layer's K/V slab out of the stack."""
    # the kernel resolves interpret mode from the default backend (this
    # process's CPU); the described chip compiles it
    monkeypatch.setattr(decode_attn_kernel, "interpret_mode",
                        lambda interpret=None: False)
    cfg = dataclasses.replace(get("qwen3-4b"), n_layers=2, exit_layers=())
    on_chip = functools.partial(jax.tree.map, lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip))
    params = on_chip(jax.eval_shape(
        lambda: T.init_model(jax.random.PRNGKey(0), cfg)))
    caches = on_chip(T.cache_shape_dtypes(cfg, 8, 4096))
    toks, pos = on_chip((jax.ShapeDtypeStruct((8, 1), jnp.int32),
                         jax.ShapeDtypeStruct((8,), jnp.int32)))
    text = jax.jit(lambda p, c, t, q: T.decode_step(p, cfg, t, c, q),
                   donate_argnums=(1,)).lower(
        params, caches, toks, pos).compile().as_text()
    calls = re.findall(r"^\s*(?:ROOT )?%?([\w.-]+) = .*custom-call\(", text,
                       re.M)
    assert "decode_attn" in [re.sub(r"(\.\d+)+$", "", c) for c in calls]
    slab = [line for line in text.splitlines()
            if "dynamic-slice" in line and "bf16[1,8,4096,8,128]" in line]
    assert not slab, slab[:2]
