"""Compile the routing kernels of the main path for a described TPU v5e.

No chip is attached: the TPU compiler is installed, so each test lowers a
kernel at Caps-MN1 widths (L=1152, H=10, C=16) — the megakernel also at the
Table-1 shapes that leave it its smallest tiles, and the EM stage kernels
at the matrix-capsule cell's shapes — for the first device of a described
``v5e:2x2`` topology and compiles it — Mosaic refuses here what
it would refuse on the chip (shape casts it cannot lay out, blocks that
break the tiling, more scoped VMEM than the limit).  The ops are called
with ``interpret=False``; nothing runs.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.  The Caps-EN3 serving wave is compiled whole, to check
how its votes reach the routing kernel.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import compat
from repro.configs.caps_benchmarks import CAPS_BENCHMARKS
from repro.core import router as rt_router
from repro.core.em_routing import ConvGeometry
from repro.kernels.routing import kernel as rt_kernel
from repro.kernels.routing import ops as rt_ops
from repro.models import capsnet
from repro.runtime.caps_serve import make_wave_fn
from repro.runtime.wave_serve import ServeConfig

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


# The shapes at which the megakernel's VMEM model leaves it the smallest
# tiles among the Table-1 networks: the widest row (Caps-EN3, H·C = 992)
# at B = 256, and the largest batch at the narrowest and the longest L.
EDGE_SHAPES = {"Caps-EN3": 256, "Caps-MN1": 1000, "Caps-CF3": 1000}


@pytest.mark.parametrize("network", sorted(EDGE_SHAPES))
def test_procedure_forward_compiles_at_edge_shapes(one_chip, network):
    c = CAPS_BENCHMARKS[network]
    shape = (EDGE_SHAPES[network], c.num_l_caps, c.num_h_caps, c.h_caps_dim)
    text = _compile(functools.partial(rt_ops.dynamic_routing_procedure_fused,
                                      iterations=c.routing_iters,
                                      interpret=False),
                    jax.ShapeDtypeStruct(shape, jnp.float32,
                                         sharding=one_chip))
    assert "routing_procedure_fused" in text


@pytest.mark.parametrize("B", BATCHES)
def test_procedure_early_exit_compiles(one_chip, B):
    _compile(functools.partial(rt_ops.dynamic_routing_procedure_stats,
                               iterations=3, early_exit_eps=1e-3,
                               interpret=False),
             _votes(one_chip, B))


@pytest.mark.parametrize("B", BATCHES)
def test_procedure_backward_compiles(one_chip, B):
    """The backward megakernel — the train path's kernel."""
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


# The emnorb-serve-rate cell (bench/configs/emcaps-norb.json): ConvCaps1
# and ConvCaps2 as patch batches of a microbatch of 8 (B' = 8·36 and 8·16
# windows of L = 3·3·32 children, H = 32, C = 16) with the cross-window
# fold between the stage kernels, and the class layer (L = 4·4·32, H = 5).
EM_NORB = {"convcaps1": (8 * 36, 288, 32, (3, 2, 14, 14)),
           "convcaps2": (8 * 16, 288, 32, (3, 1, 6, 6)),
           "class": (8, 512, 5, None)}


@pytest.mark.parametrize("layer", sorted(EM_NORB))
def test_em_norb_layers_compile(one_chip, layer):
    rows, L, H, geom = EM_NORB[layer]
    conv = ConvGeometry(*geom) if geom else None
    structs = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
               for s in ((rows, L, H, C), (rows, L), (H,), (H,))]

    def layer_fn(votes, a_in, beta_a, beta_u):
        return rt_ops.em_routing_fused(
            votes, a_in, axes={}, iterations=3, beta_a=beta_a,
            beta_u=beta_u, conv=conv, logits=conv is None,
            interpret=False)

    text = _compile(layer_fn, *structs)
    assert "em_stage_stats" in text and "em_stage_estep" in text


def test_em_l_sharded_compiles(topo):
    """EM with L sharded over the four chips: the statistics pass, the
    Table-2 psum of its sums, then the E-step pass."""
    mesh = Mesh(np.array(topo.devices), ("x",))
    spec = P(None, "x")
    structs = [jax.ShapeDtypeStruct(s, jnp.float32,
                                    sharding=NamedSharding(mesh, spec))
               for s in ((8, 512, 32, C), (8, 512))]

    def routed(votes, a_in):
        return rt_ops.em_routing_fused(votes, a_in, axes={"L": "x"},
                                       iterations=3, interpret=False)

    text = _compile(compat.shard_map(routed, mesh, in_specs=(spec, spec),
                                     out_specs=(P(), P())), *structs)
    assert "em_stage_stats" in text and "em_stage_estep" in text


def _relayout_passes(text: str, lead: str) -> int:
    """Copies and loop fusions, outside fused computations, that write an
    f32 array whose leading dims are ``lead``: each is one pass over it
    that computes nothing the kernels need."""
    fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
    count, body = 0, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) ", line)
        if head:
            body = head.group(1)
        elif body not in fused and re.match(
                rf"\s*(?:ROOT )?%\S+ = f32\[{lead},[\d,]+\]\S* "
                r"(copy\(|fusion\(.*kind=kLoop)", line):
            count += 1
    return count


def test_serving_wave_hands_votes_lane_packed(one_chip, monkeypatch):
    """The en3-serve-rate wave (Caps-EN3, microbatch 8, software pipeline):
    the votes û reach each routing call after at most one relayout pass,
    and no array of them has H = 62 on the lanes (padded to 128)."""
    import repro.kernels
    monkeypatch.setattr(repro.kernels, "pallas_interpret_mode",
                        lambda: False)
    jax.clear_caches()           # no kernel traced in interpret mode
    cfg = CAPS_BENCHMARKS["Caps-EN3"]
    mb, n_micro = 8, 2
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: capsnet.init_capsnet(jax.random.key(0),
                                                    cfg)))
    micro = {"images": jax.ShapeDtypeStruct((n_micro, mb, 28, 28, 1),
                                            jnp.float32, sharding=one_chip),
             "mask": jax.ShapeDtypeStruct((n_micro, mb), jnp.float32,
                                          sharding=one_chip)}
    spec = rt_router.RouterSpec(backend="pallas",
                                iterations=cfg.routing_iters)
    scfg = ServeConfig(microbatch=mb, n_micro=n_micro, pipeline="software")
    text = _compile(lambda p, m: make_wave_fn(p, cfg, spec, scfg)(m),
                    params, micro)
    H, C = cfg.num_h_caps, cfg.h_caps_dim
    assert f"[{mb},{cfg.num_l_caps},{H},{C}]" not in text
    calls = len(re.findall(r"= f32\[\d+,\d+\]\S* custom-call\(.*"
                           r"routing_procedure_fused", text))
    assert calls == n_micro
    assert _relayout_passes(text, f"{mb},{cfg.num_l_caps}") <= calls
