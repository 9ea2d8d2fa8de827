"""Compile the routing kernels of the main path for a described TPU v5e.

No chip is attached: the TPU compiler is installed, so each test lowers a
kernel at Caps-MN1 widths (L=1152, H=10, C=16) for the first device of a
described ``v5e:2x2`` topology and compiles it — Mosaic refuses here what
it would refuse on the chip (shape casts it cannot lay out, blocks that
break the tiling, more scoped VMEM than the limit).  The ops are called
with ``interpret=False``; nothing runs.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.caps_benchmarks import CAPS_BENCHMARKS
from repro.kernels.routing import kernel as rt_kernel
from repro.kernels.routing import ops as rt_ops

MN1 = CAPS_BENCHMARKS["Caps-MN1"]
L, H, C = MN1.num_l_caps, MN1.num_h_caps, MN1.h_caps_dim
BATCHES = (8, MN1.batch_size)     # serving microbatch, Table-1 batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip cannot read back a persistent-cache entry: keep
    # these compiles out of the cache while the module runs
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *structs):
    text = jax.jit(fn).lower(*structs).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _votes(sharding, B, dtype=jnp.float32):
    return jax.ShapeDtypeStruct((B, L, H, C), dtype, sharding=sharding)


@pytest.mark.parametrize("B", BATCHES)
@pytest.mark.parametrize("stream_dtype", ["fp32", "bf16", "int8"])
def test_procedure_forward_compiles(one_chip, B, stream_dtype):
    _compile(functools.partial(rt_ops.dynamic_routing_procedure_fused,
                               iterations=3, stream_dtype=stream_dtype,
                               interpret=False),
             _votes(one_chip, B))


@pytest.mark.parametrize("B", BATCHES)
def test_procedure_early_exit_compiles(one_chip, B):
    _compile(functools.partial(rt_ops.dynamic_routing_procedure_stats,
                               iterations=3, early_exit_eps=1e-3,
                               interpret=False),
             _votes(one_chip, B))


@pytest.mark.parametrize("B", BATCHES)
def test_procedure_backward_compiles(one_chip, B):
    """The recompute-b backward megakernel — the train path's kernel."""
    def loss(u):
        return rt_ops.dynamic_routing_procedure_train(
            u, iterations=3, interpret=False).sum()

    text = _compile(jax.grad(loss), _votes(one_chip, B))
    assert "routing_procedure_bwd" in text


@pytest.mark.parametrize("B", BATCHES)
def test_stage_split_compiles(one_chip, B):
    """The sharded path's stage kernels: STAGE 1 votes and the folded
    STAGE 2 (through the ops loop), and the unfolded STAGE 2."""
    _compile(functools.partial(rt_ops.dynamic_routing_fused_sharded,
                               axes={}, iterations=3, interpret=False),
             _votes(one_chip, B))
    _compile(functools.partial(rt_kernel.routing_stage_update,
                               l_tile=rt_ops.auto_l_tile(B, L, H, C, "fp32"),
                               interpret=False),
             _votes(one_chip, B),
             jax.ShapeDtypeStruct((B, H, C), jnp.float32, sharding=one_chip))


@pytest.mark.parametrize("B", BATCHES)
def test_em_stages_compile(one_chip, B):
    a_in = jax.ShapeDtypeStruct((B, L), jnp.float32, sharding=one_chip)
    _compile(functools.partial(rt_ops.em_routing_fused, axes={},
                               iterations=3, interpret=False),
             _votes(one_chip, B), a_in)
