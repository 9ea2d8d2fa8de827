"""Multi-device (sharded) behaviour tests.

jax locks the host-device count at first init and the main pytest process
must keep the single real CPU device (task spec), so every test here runs a
small script in a subprocess with XLA_FLAGS=--xla_force_host_platform_
device_count=8 and asserts on its output.
"""
import os
import subprocess
import sys

import pytest

ENV = {**os.environ,
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src")}


def run_ok(script: str, timeout=420) -> str:
    r = subprocess.run([sys.executable, "-c", script], env=ENV,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_routing_all_dims():
    """Paper §5.1: B/L/H-sharded routing == unsharded, and the inserted
    collective matches the dimension (Table 2 aggregation structure) —
    through the unified Router API."""
    run_ok("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.router import ExecutionPlan, RouterSpec, build_router
from repro.runtime.mesh_utils import make_mesh
mesh = make_mesh((8,), ('x',))
key = jax.random.PRNGKey(0)
u_hat = jax.random.normal(key, (8, 64, 8, 16))
spec = RouterSpec(algorithm='dynamic', iterations=3)
want = build_router(spec)(u_hat)
for dim in ('B', 'L', 'H'):
    routed = build_router(spec, ExecutionPlan(mesh=mesh, axes=((dim, 'x'),)))
    got = jax.jit(routed)(u_hat)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5), dim
    # collective presence check in the lowered HLO
    txt = jax.jit(routed).lower(u_hat).compile().as_text()
    assert 'all-reduce' in txt or 'reduce-scatter' in txt, dim
# plan='auto' resolves to a feasible dim and matches the unsharded result
auto = build_router(spec, ExecutionPlan(mesh=mesh, auto=True))
got = jax.jit(auto)(u_hat)
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=2e-4, atol=2e-5)
assert auto.resolve(u_hat), 'auto plan should shard a dim on 8 devices'
print('sharded routing OK')
""")


def test_sharded_xent_and_flash_decode():
    run_ok("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.models import layers as L
from repro.runtime.mesh_utils import make_mesh
mesh = make_mesh((2, 4), ('data', 'model'))
key = jax.random.PRNGKey(0)
# vocab-sharded xent == dense
logits = jax.random.normal(key, (4, 8, 64))
labels = jax.random.randint(key, (4, 8), 0, 64)
got = L.sharded_softmax_xent(logits, labels, mesh, 'model',
                             batch_spec=P('data'))
lse = jax.nn.logsumexp(logits, -1)
want = lse - jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)
# gradient flows and matches dense
f_sh = lambda lg: L.sharded_softmax_xent(lg, labels, mesh, 'model',
                                         batch_spec=P('data')).sum()
f_dn = lambda lg: (jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
    lg, labels[..., None], -1)[..., 0]).sum()
g_sh = jax.grad(f_sh)(logits)
g_dn = jax.grad(f_dn)(logits)
np.testing.assert_allclose(np.asarray(g_sh), np.asarray(g_dn),
                           rtol=1e-4, atol=1e-5)
# flash-decode sharded == local
rules = L.AxisRules(rules={'batch': 'data', 'cache_seq': 'model'},
                    mesh=mesh, enabled=True)
B, S, H, KV, D = 2, 64, 8, 4, 16
p = L.init_attention(key, 32, H, KV, D, jnp.float32)
x = jax.random.normal(key, (B, 1, 32), jnp.float32)
ck = jax.random.normal(key, (B, S, KV, D), jnp.float32)
cv = jax.random.normal(key, (B, S, KV, D), jnp.float32)
pos = jnp.array([37, 37])
o1, k1, v1 = jax.jit(lambda *a: L.attention_decode(
    *a, n_heads=H, n_kv=KV, d_head=D, rope_theta=1e4, kv_chunk=16,
    rules=rules))(p, x, ck, cv, pos)
o0, k0, v0 = L.attention_decode(p, x, ck, cv, pos, n_heads=H, n_kv=KV,
                                d_head=D, rope_theta=1e4, kv_chunk=16)
np.testing.assert_allclose(np.asarray(o1), np.asarray(o0), rtol=1e-5,
                           atol=1e-6)
print('sharded xent + flash decode OK')
""")


def test_sharded_moe_dispatch():
    run_ok("""
import jax, jax.numpy as jnp, numpy as np
from repro.models import layers as L, moe as moe_lib
from repro.runtime.mesh_utils import make_mesh
mesh = make_mesh((2, 4), ('data', 'model'))
rules = L.AxisRules(rules={'batch': 'data', 'experts': 'model'},
                    mesh=mesh, enabled=True)
key = jax.random.PRNGKey(0)
cfg = moe_lib.MoEConfig(d_model=32, d_ff=16, n_experts=8, top_k=2,
                        capacity_factor=100.0)
params = moe_lib.init_moe(key, cfg, jnp.float32)
x = jax.random.normal(key, (4, 8, 32))
got, aux = jax.jit(lambda p, x: moe_lib.moe_forward(p, x, cfg,
                                                    rules=rules))(params, x)
want, _ = moe_lib.moe_forward_dense_oracle(params, x, cfg)
np.testing.assert_allclose(np.asarray(got), np.asarray(want, np.float32),
                           rtol=1e-4, atol=1e-4)
# sub-expert (EP x TP) sharded path: 4 experts x 2 subs over 4 shards
cfg2 = moe_lib.MoEConfig(d_model=32, d_ff=16, n_experts=4, top_k=2,
                         capacity_factor=100.0, sub_experts=2)
p2 = moe_lib.init_moe(key, cfg2, jnp.float32)
got2, _ = jax.jit(lambda p, x: moe_lib.moe_forward(p, x, cfg2,
                                                   rules=rules))(p2, x)
want2, _ = moe_lib.moe_forward_dense_oracle(p2, x, cfg2)
np.testing.assert_allclose(np.asarray(got2), np.asarray(want2, np.float32),
                           rtol=1e-4, atol=1e-4)
print('sharded moe OK')
""")


def test_sharded_em_routing():
    """Paper generality claim: the §5.1 distribution applies to EM routing
    — L-sharded (M-step psums) and B-sharded (no collectives) both match
    the unsharded result."""
    run_ok("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import em_routing
from repro.core.router import ExecutionPlan, RouterSpec, build_router
from repro.runtime.mesh_utils import make_mesh
mesh = make_mesh((8,), ('x',))
key = jax.random.PRNGKey(0)
votes = jax.random.normal(key, (8, 64, 4, 8))
a_in = jax.nn.sigmoid(jax.random.normal(key, (8, 64)))
pose_ref, act_ref = em_routing.em_routing(votes, a_in)
for dim in ('B', 'L'):
    routed = build_router(RouterSpec(algorithm='em'),
                          ExecutionPlan(mesh=mesh, axes=((dim, 'x'),)))
    pose, act = jax.jit(routed)(votes, a_in)
    np.testing.assert_allclose(np.asarray(pose), np.asarray(pose_ref),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(act), np.asarray(act_ref),
                               rtol=2e-4, atol=2e-5)
    txt = jax.jit(routed).lower(votes, a_in).compile().as_text()
    has_ar = 'all-reduce' in txt
    assert has_ar == (dim == 'L'), (dim, has_ar)  # B-sharding: collective-free
print('sharded EM routing OK')
""")


def test_elastic_resume_across_mesh_sizes(tmp_path):
    """Fault-tolerance path end-to-end: train 2 steps on a (2,2) mesh,
    checkpoint, resume on a (2,4) mesh, keep training — loss continues."""
    tmp_path = str(tmp_path)
    run_ok(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
import repro.configs as C
from repro.runtime.mesh_utils import make_mesh
from repro import checkpoint as ck
from repro.models import lm
from repro.optim import adamw_init
from repro.runtime import elastic, sharding as sh, train_loop

def run_steps(mesh, start, n, ckpt_dir):
    cfg = C.get_smoke_config('granite-3-2b')
    key = jax.random.PRNGKey(0)
    params, opt, step0, rules = elastic.resume_or_init(cfg, mesh, ckpt_dir,
                                                       key)
    assert step0 == start, (step0, start)
    fn = jax.jit(train_loop.make_train_step(cfg, rules))
    toks = jax.random.randint(key, (8, 16), 0, cfg.vocab)
    batch = {{'tokens': toks, 'labels': toks}}
    loss = None
    for i in range(n):
        params, opt, m = fn(params, opt, batch)
        loss = float(m['loss'])
    ck.save_checkpoint(ckpt_dir, start + n, params)
    return loss

mesh_a = make_mesh((2, 2), ('data', 'model'))
mesh_b = make_mesh((2, 4), ('data', 'model'))
l1 = run_steps(mesh_a, 0, 2, {tmp_path!r})
l2 = run_steps(mesh_b, 2, 2, {tmp_path!r})   # resumed on a BIGGER mesh
assert l2 < l1 + 0.5, (l1, l2)               # training continues sanely
print('elastic resume OK', l1, l2)
""", timeout=560)


def test_two_stage_pipeline():
    run_ok("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import pipeline
from repro.runtime.mesh_utils import make_mesh
mesh = make_mesh((2, 4), ('pipe', 'x'))
stage_a = lambda x: x * 2.0 + 1.0
stage_b = lambda h: h ** 2
micro = jnp.arange(24, dtype=jnp.float32).reshape(6, 4)
runner = pipeline.two_stage_pipeline(
    stage_a, stage_b, mesh, 'pipe',
    jax.ShapeDtypeStruct((4,), jnp.float32))
got = runner(micro)
want = jnp.stack([stage_b(stage_a(m)) for m in micro])
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
print('pipeline OK')
""")


def test_smoke_dryrun_machinery():
    """The dry-run machinery itself (reduced mesh + reduced configs):
    one arch per family x one shape each, single- and multi-pod."""
    run_ok("""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
from repro.launch import dryrun
cells = [('granite-3-2b', 'train_4k'), ('qwen3-moe-30b-a3b', 'prefill_32k'),
         ('falcon-mamba-7b', 'decode_32k'), ('zamba2-7b', 'long_500k'),
         ('seamless-m4t-large-v2', 'train_4k')]
for arch, shape in cells:
    for mp in (False, True):
        rec = dryrun.lower_cell(arch, shape, mp, smoke=True)
        assert rec['status'] == 'ok', (arch, shape, mp, rec.get('error'))
        assert rec['memory']['peak_bytes_per_device'] > 0
        assert rec['hlo']['flops'] > 0
print('smoke dryrun OK')
""", timeout=560)


def test_elastic_reshard_roundtrip(tmp_path):
    """Checkpoint on a 4-shard mesh, restore on an 8-shard mesh (elastic)."""
    tmp_path = str(tmp_path)
    run_ok(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import checkpoint as ck
from repro.runtime.mesh_utils import make_mesh
mesh4 = make_mesh((2, 2), ('data', 'model'))
mesh8 = make_mesh((2, 4), ('data', 'model'))
tree = {{'w': jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
        'b': jnp.ones((8,), jnp.float32)}}
sh4 = {{'w': NamedSharding(mesh4, P('data', 'model')),
       'b': NamedSharding(mesh4, P(None))}}
tree4 = jax.tree.map(jax.device_put, tree, sh4)
ck.save_checkpoint({tmp_path!r}, 3, tree4)
assert ck.latest_step({tmp_path!r}) == 3
sh8 = {{'w': NamedSharding(mesh8, P('data', 'model')),
       'b': NamedSharding(mesh8, P(None))}}
restored = ck.load_checkpoint({tmp_path!r}, 3, tree, sh8)
np.testing.assert_array_equal(np.asarray(restored['w']), np.asarray(tree['w']))
assert restored['w'].sharding.mesh.shape['model'] == 4
print('elastic reshard OK')
""")
