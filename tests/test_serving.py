"""CapsNet serving subsystem (runtime.caps_serve, DESIGN.md §Serving):
padding invariance, pipelined == unpipelined equivalence, queue drain under
ragged arrivals, async admission (concurrent submitters over serve_forever,
back-pressure shed/reject accounting), atomic submit, JSON-safe metrics,
EM serving waves, the serve_caps CLI smoke, and the pipeline x sharded-plan
composition on a multi-device mesh (subprocess, like tests/test_sharded.py).
"""
import dataclasses
import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.caps_benchmarks import CapsConfig
from repro.core.router import RouterSpec
from repro.data.synthetic import SyntheticCapsDataset
from repro.models import capsnet
from repro.runtime.caps_serve import (CapsServer, QueueFullError,
                                      ServeConfig, ServeMetrics,
                                      make_wave_fn)

ENV = {**os.environ,
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src")}


def tiny_caps() -> CapsConfig:
    """Smaller than smoke_caps — serving tests run many waves."""
    return CapsConfig("Caps-tiny", "synthetic", 8, 72, 10, 2,
                      caps_channels=2, conv_channels=16)


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_caps()
    params = capsnet.init_capsnet(jax.random.PRNGKey(0), cfg)
    # non-zero conv biases: a zero-image pad lane now produces non-zero
    # votes, so padding invariance genuinely depends on the lane mask
    # (routing's b is batch-shared — paper Table 2's B aggregation).
    params["primary"]["conv1"]["b"] = (
        params["primary"]["conv1"]["b"] + 0.1)
    params["primary"]["caps_conv"]["b"] = (
        params["primary"]["caps_conv"]["b"] + 0.05)
    ds = SyntheticCapsDataset(cfg.image_hw, cfg.image_channels,
                              cfg.num_h_caps)
    return cfg, params, ds


def _micro(cfg, images, mask, n_micro, microbatch):
    return {"images": jnp.asarray(images, jnp.float32).reshape(
                (n_micro, microbatch, cfg.image_hw, cfg.image_hw,
                 cfg.image_channels)),
            "mask": jnp.asarray(mask, jnp.float32).reshape(
                (n_micro, microbatch))}


def _assert_pad_content_invariant(wave, cfg, padded, mask, got):
    """The real lanes of a one-microbatch wave are bit-identical whatever
    the padded lanes hold: the mask zeroes their capsules before Eq.1."""
    n = int(mask.sum())
    rng = np.random.default_rng(7)
    for fill in (rng.uniform(0.0, 1.0, padded.shape),
                 rng.normal(0.0, 50.0, padded.shape)):
        other = np.where(mask[:, None, None, None] > 0, padded,
                         fill.astype(np.float32))
        again = wave(_micro(cfg, other, mask, 1, len(mask)))[0, :n]
        np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


def test_padding_invariance(setup):
    """Padded lanes never change real outputs — even though routing couples
    batch lanes through the shared b logits and the (biased) encoder maps
    zero images to non-zero votes."""
    cfg, params, ds = setup
    n_micro, microbatch = 1, 8
    real = ds.batch(0, 3)["images"]

    # the mask is load-bearing: an unmasked zero image has non-zero votes
    zero_votes = capsnet.encode_votes(
        params, jnp.zeros((1, cfg.image_hw, cfg.image_hw,
                           cfg.image_channels)), cfg)
    assert float(jnp.abs(zero_votes).max()) > 1e-3

    wave = make_wave_fn(params, cfg, None,
                        ServeConfig(microbatch=microbatch, n_micro=n_micro,
                                    pipeline="software"))
    padded = np.zeros((microbatch, cfg.image_hw, cfg.image_hw,
                       cfg.image_channels), np.float32)
    padded[:3] = real
    mask = np.zeros((microbatch,), np.float32)
    mask[:3] = 1.0
    got = wave(_micro(cfg, padded, mask, n_micro, microbatch))[0, :3]
    _assert_pad_content_invariant(wave, cfg, padded, mask, got)

    ref_wave = make_wave_fn(params, cfg, None,
                            ServeConfig(microbatch=3, n_micro=1,
                                        pipeline="software"))
    want = ref_wave(_micro(cfg, real, np.ones(3), 1, 3))[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_pipelined_matches_unpipelined(setup):
    """The §4 pipeline transform is exact (<= 1e-5) for the serving wave."""
    cfg, params, ds = setup
    n_micro, microbatch = 3, 4
    images = ds.batch(1, n_micro * microbatch)["images"]
    mask = np.ones((n_micro * microbatch,), np.float32)
    mask[-2:] = 0.0            # include padded lanes in the comparison
    micro = _micro(cfg, images, mask, n_micro, microbatch)
    probs = {}
    for arm, pipeline in (("piped", "software"), ("plain", None)):
        wave = make_wave_fn(params, cfg, None,
                            ServeConfig(microbatch=microbatch,
                                        n_micro=n_micro,
                                        pipeline=pipeline))
        probs[arm] = np.asarray(wave(micro))
    assert np.max(np.abs(probs["piped"] - probs["plain"])) <= 1e-5


def test_queue_drains_ragged_arrivals(setup):
    """Ragged arrival pattern fully drains; every request completes exactly
    once with sane latency/padding accounting (fake clock)."""
    cfg, params, ds = setup
    ticks = iter(range(1000))
    server = CapsServer(params, cfg,
                        cfg=ServeConfig(microbatch=4, n_micro=2,
                                        pipeline="software"),
                        clock=lambda: float(next(ticks)))
    arrivals = [3, 0, 9, 1, 0, 0, 5, 2]
    submitted = []
    done = []
    for tick, count in enumerate(arrivals):
        if count:
            submitted += server.submit(ds.batch(tick, count)["images"])
        done += server.step()
    done += server.drain()

    assert server.pending() == 0
    assert sorted(c.rid for c in done) == sorted(submitted)
    s = server.metrics.summary()
    assert s["completed"] == s["submitted"] == sum(arrivals)
    assert s["waves"] * server.cfg.wave_lanes \
        == s["completed"] + s["padded_lanes"]
    assert all(c.latency_s >= 0 for c in done)
    assert s["p90_latency_s"] >= s["p50_latency_s"] >= 0
    # FIFO: completion order == submission order under a single queue
    assert [c.rid for c in done] == submitted


def test_wave_fn_compiles_once(setup):
    """Continuous batching keeps a constant wave shape: ragged arrivals all
    reuse one executable (compile-once per (spec, plan))."""
    cfg, params, ds = setup
    server = CapsServer(params, cfg,
                        cfg=ServeConfig(microbatch=4, n_micro=2,
                                        pipeline="software"))
    calls = []
    inner = server._wave_fn
    server._wave_fn = lambda m: (calls.append(
        jax.tree.map(jnp.shape, m)), inner(m))[1]
    for tick, count in enumerate([1, 7, 3]):
        server.submit(ds.batch(tick, count)["images"])
        server.step()
    server.drain()
    assert len(set(map(str, calls))) == 1      # one shape -> one executable


def test_default_config_fresh_and_frozen(setup):
    """cfg=None builds a fresh ServeConfig per server (no shared default
    instance), and ServeConfig is frozen so plan-affecting fields cannot
    drift after make_wave_fn compiled."""
    cfg, params, ds = setup
    s1 = CapsServer(params, cfg)
    s2 = CapsServer(params, cfg)
    with pytest.raises(dataclasses.FrozenInstanceError):
        s1.cfg.microbatch = 99
    s1.submit(ds.batch(0, 1)["images"])
    assert (s1.metrics.submitted, s2.metrics.submitted) == (1, 0)
    assert (s1.pending(), s2.pending()) == (1, 0)
    with pytest.raises(ValueError, match="overflow"):
        ServeConfig(overflow="panic")
    with pytest.raises(ValueError, match="max_queue"):
        ServeConfig(max_queue=0)


def test_submit_is_atomic(setup):
    """A mid-batch invalid image admits nothing: everything validates
    before anything enqueues, mis-shaped and ragged arrivals get the
    friendly error, and an empty-queue step() is a no-op."""
    cfg, params, ds = setup
    server = CapsServer(params, cfg,
                        cfg=ServeConfig(microbatch=2, n_micro=1))
    good = np.asarray(ds.batch(0, 2)["images"], np.float32)

    with pytest.raises(ValueError, match="image shape"):
        server.submit(np.zeros((2, 3, 3, 1), np.float32))
    with pytest.raises(ValueError, match="ragged arrival"):
        server.submit([good[0], np.zeros((5,), np.float32)])
    assert server.pending() == 0
    assert server.metrics.submitted == 0
    assert server.metrics.t_first_submit is None

    assert server.step() == []                 # empty-queue step: no-op
    assert server.metrics.waves == 0
    assert server.submit([]) == []

    rids = server.submit(good)                 # valid arrivals still admit
    assert rids == [0, 1] and server.pending() == 2


def test_summary_is_strict_json_safe():
    """summary() never emits NaN/Infinity (strict JSON round-trip) and
    uses nearest-rank percentiles."""
    def boom(name):
        raise AssertionError(f"non-finite constant {name} in summary")

    empty = ServeMetrics().summary()
    assert empty["p50_latency_s"] is None
    assert empty["p90_latency_s"] is None
    assert empty["throughput_rps"] is None     # span 0 != "completed rps"
    assert json.loads(json.dumps(empty), parse_constant=boom) == empty

    m = ServeMetrics(submitted=4, completed=4,
                     latencies_s=[3.0, 1.0, 2.0, 4.0],
                     t_first_submit=0.0, t_last_done=2.0)
    s = m.summary()
    # nearest-rank over [1,2,3,4]: p50 -> ceil(2)=2nd -> 2.0, p90 -> 4th
    assert s["p50_latency_s"] == 2.0
    assert s["p90_latency_s"] == 4.0
    assert s["throughput_rps"] == 2.0
    assert json.loads(json.dumps(s), parse_constant=boom) == s


def test_async_admission_concurrent_submitters(setup):
    """serve_forever on a background thread sustains concurrent submitter
    threads: no lost or double-counted requests, clean stop drains the
    queue, and submitted == completed + shed + pending holds."""
    cfg, params, ds = setup
    server = CapsServer(params, cfg,
                        cfg=ServeConfig(microbatch=4, n_micro=2,
                                        pipeline="software"))
    stop = threading.Event()
    done = []
    driver = threading.Thread(
        target=lambda: done.extend(server.serve_forever(stop, poll_s=0.005)))
    driver.start()

    rids, lock = [], threading.Lock()

    def client(worker):
        got = []
        for tick, count in enumerate([3, 1, 5, 2]):
            got += server.submit(ds.batch(worker * 10 + tick,
                                          count)["images"])
            time.sleep(0.002)
        with lock:
            rids.extend(got)

    clients = [threading.Thread(target=client, args=(w,)) for w in range(3)]
    for c in clients:
        c.start()
    for c in clients:
        c.join()
    stop.set()
    driver.join(timeout=300)
    assert not driver.is_alive()

    m = server.metrics
    assert sorted(c.rid for c in done) == sorted(rids)
    assert len({c.rid for c in done}) == len(done)          # no duplicates
    assert server.pending() == 0 and m.shed == 0
    assert m.submitted == m.completed + m.shed + server.pending() == 33


def test_backpressure_shed_and_reject(setup):
    """Bounded queue: "shed" admits up to the bound and tail-drops the
    rest (counted); "reject" raises atomically, admitting nothing."""
    cfg, params, ds = setup
    server = CapsServer(params, cfg,
                        cfg=ServeConfig(microbatch=2, n_micro=2,
                                        max_queue=3, overflow="shed"))
    rids = server.submit(ds.batch(0, 5)["images"])
    assert len(rids) == 3
    m = server.metrics
    assert (m.submitted, m.shed, server.pending()) == (5, 2, 3)
    assert len(server.drain()) == 3
    assert m.submitted == m.completed + m.shed + server.pending()
    assert m.summary()["shed"] == 2

    server = CapsServer(params, cfg,
                        cfg=ServeConfig(microbatch=2, n_micro=2,
                                        max_queue=2, overflow="reject"))
    server.submit(ds.batch(1, 1)["images"])
    with pytest.raises(QueueFullError):
        server.submit(ds.batch(2, 4)["images"])
    assert server.pending() == 1                # atomic: nothing admitted
    assert server.metrics.submitted == 1
    assert server.metrics.rejected == 4
    assert server.metrics.shed == 0


def test_em_wave_pipelined_matches_unpipelined(setup):
    """EM serving waves (the multi-input (votes, a_in) stage hand-off):
    pipelined == unpipelined <= 1e-5, and the server completes over it."""
    cfg, params, ds = setup
    spec = RouterSpec(algorithm="em", iterations=2)
    n_micro, microbatch = 2, 4
    images = ds.batch(3, n_micro * microbatch)["images"]
    mask = np.ones((n_micro * microbatch,), np.float32)
    mask[-1] = 0.0
    micro = _micro(cfg, images, mask, n_micro, microbatch)
    scores = {}
    for arm, pipeline in (("piped", "software"), ("plain", None)):
        wave = make_wave_fn(params, cfg, spec,
                            ServeConfig(microbatch=microbatch,
                                        n_micro=n_micro,
                                        pipeline=pipeline))
        scores[arm] = np.asarray(wave(micro))
    assert scores["piped"].shape == (n_micro, microbatch, cfg.num_h_caps)
    assert np.max(np.abs(scores["piped"] - scores["plain"])) <= 1e-5

    server = CapsServer(params, cfg, spec=spec,
                        cfg=ServeConfig(microbatch=microbatch,
                                        n_micro=n_micro,
                                        pipeline="software"))
    server.submit(ds.batch(4, 6)["images"])
    assert len(server.drain()) == 6


def test_em_padding_invariance(setup):
    """Padded lanes never change real EM outputs: the lane mask zeroes a
    padded lane's a_in and votes, so its (biased-encoder, non-zero) votes
    never weight any Gaussian — checked against an unpadded reference
    wave, not just the other pipeline arm (which shares the masking)."""
    cfg, params, ds = setup
    spec = RouterSpec(algorithm="em", iterations=2)
    microbatch = 8
    real = ds.batch(5, 3)["images"]
    padded = np.zeros((microbatch, cfg.image_hw, cfg.image_hw,
                       cfg.image_channels), np.float32)
    padded[:3] = real
    mask = np.zeros((microbatch,), np.float32)
    mask[:3] = 1.0
    wave = make_wave_fn(params, cfg, spec,
                        ServeConfig(microbatch=microbatch, n_micro=1,
                                    pipeline="software"))
    got = wave(_micro(cfg, padded, mask, 1, microbatch))[0, :3]
    _assert_pad_content_invariant(wave, cfg, padded, mask, got)
    ref_wave = make_wave_fn(params, cfg, spec,
                            ServeConfig(microbatch=3, n_micro=1,
                                        pipeline="software"))
    want = ref_wave(_micro(cfg, real, np.ones(3), 1, 3))[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("extra", [(), ("--async",)],
                         ids=["sync", "async"])
def test_serve_caps_cli_smoke(extra):
    """python -m repro.launch.serve_caps --smoke [--async] completes and
    reports."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve_caps", "--smoke",
         *extra],
        env=ENV, capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "latency p50" in r.stdout and "throughput" in r.stdout
    if extra:
        assert "async" in r.stdout


def test_serving_wave_over_two_stage_mesh():
    """The full serving composition on an 8-device mesh: CapsNet wave
    (images+mask pytree) through two_stage pipe x {unsharded, auto, B-, L-
    sharded} routing stage matches the unpipelined arm to <= 1e-5, and
    CapsServer drains over it."""
    script = """
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.configs.caps_benchmarks import CapsConfig
from repro.data.synthetic import SyntheticCapsDataset
from repro.models import capsnet
from repro.runtime.caps_serve import CapsServer, ServeConfig, make_wave_fn
cfg = CapsConfig('t', 'synthetic', 8, 72, 8, 2, caps_channels=2,
                 conv_channels=16)
params = capsnet.init_capsnet(jax.random.PRNGKey(0), cfg)
ds = SyntheticCapsDataset(cfg.image_hw, cfg.image_channels, cfg.num_h_caps)
n_micro, mb = 2, 8
imgs = jnp.asarray(ds.batch(0, n_micro * mb)['images']).reshape(
    (n_micro, mb, cfg.image_hw, cfg.image_hw, cfg.image_channels))
micro = {'images': imgs, 'mask': jnp.ones((n_micro, mb))}
mesh = compat.make_mesh((2, 4), ('pipe', 'vault'))
plain = make_wave_fn(params, cfg, None,
                     ServeConfig(microbatch=mb, n_micro=n_micro,
                                 pipeline=None))(micro)
for rp in [None, 'auto', (('B', 'vault'),), (('L', 'vault'),)]:
    sc = ServeConfig(microbatch=mb, n_micro=n_micro, pipeline='two_stage',
                     mesh=mesh, routing_plan=rp)
    got = make_wave_fn(params, cfg, None, sc)(micro)
    assert float(jnp.max(jnp.abs(got - plain))) <= 1e-5, rp
server = CapsServer(params, cfg,
                    cfg=ServeConfig(microbatch=mb, n_micro=n_micro,
                                    pipeline='two_stage', mesh=mesh,
                                    routing_plan='auto'))
server.submit(ds.batch(1, 11)['images'])
assert len(server.drain()) == 11 and server.pending() == 0
print('serving over two_stage mesh OK')
"""
    r = subprocess.run([sys.executable, "-c", script], env=ENV,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"


def test_multi_dim_and_em_two_stage_pipeline():
    """Tentpole composition on an 8-device mesh (2 pipe x 2 data x 2
    model): the routing stage shards over BOTH vault axes inside the §4
    two_stage pipe (multi-dim sharded pipeline stages), and EM routing runs
    as pipeline stages — the (votes, a_in) hand-off crossing the ppermute —
    unsharded, L-sharded, and B+L-sharded, all <= 1e-5 vs unpipelined."""
    script = """
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.core.router import ExecutionPlan, RouterSpec, build_router
key = jax.random.PRNGKey(0)
micro = jax.random.normal(key, (3, 4, 16, 8, 6))
W = jax.random.normal(jax.random.fold_in(key, 1), (6, 6)) * 0.3
stage_a = lambda x: jnp.tanh(x @ W)
mesh = compat.make_mesh((2, 2, 2), ('pipe', 'data', 'model'))

spec = RouterSpec(iterations=3)
want = jnp.stack([build_router(spec)(stage_a(m)) for m in micro])
plan = ExecutionPlan(mesh=mesh, pipeline='two_stage', stage_a=stage_a,
                     axes=(('B', 'data'), ('L', 'model')))
got = build_router(spec, plan)(micro)
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=1e-5, atol=1e-6)
print('dynamic B x L two_stage OK')

a_stage = lambda x: (jnp.tanh(x @ W), jax.nn.sigmoid(x[..., 0, 0]))
espec = RouterSpec(algorithm='em', iterations=2)
ecore = build_router(espec)
refs = [ecore(*a_stage(m)) for m in micro]
want_pose = jnp.stack([r[0] for r in refs])
want_act = jnp.stack([r[1] for r in refs])
for axes in [(), (('L', 'model'),), (('B', 'data'), ('L', 'model'))]:
    plan = ExecutionPlan(mesh=mesh, pipeline='two_stage', stage_a=a_stage,
                         axes=axes)
    pose, act = build_router(espec, plan)(micro)
    assert float(jnp.max(jnp.abs(pose - want_pose))) <= 1e-5, axes
    assert float(jnp.max(jnp.abs(act - want_act))) <= 1e-5, axes
    print('em two_stage OK axes=', axes)
"""
    r = subprocess.run([sys.executable, "-c", script], env=ENV,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"


def test_two_stage_sharded_pipeline_composition():
    """Paper §4 x §5.1 composed: two_stage pipeline over 'pipe' with the
    routing stage sharded (explicitly and via plan='auto') over 'vault' —
    outputs match the plain unpipelined router to <= 1e-5."""
    script = """
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.core.router import ExecutionPlan, RouterSpec, build_router
key = jax.random.PRNGKey(0)
micro = jax.random.normal(key, (5, 4, 16, 8, 6))
W = jax.random.normal(jax.random.fold_in(key, 1), (6, 6)) * 0.3
stage_a = lambda x: jnp.tanh(x @ W)
spec = RouterSpec(algorithm='dynamic', iterations=3)
want = jnp.stack([build_router(spec)(stage_a(m)) for m in micro])
mesh = compat.make_mesh((2, 4), ('pipe', 'vault'))
for plan in [ExecutionPlan(mesh=mesh, pipeline='two_stage',
                           stage_a=stage_a, axes=(('L', 'vault'),)),
             ExecutionPlan(mesh=mesh, pipeline='two_stage',
                           stage_a=stage_a, axes=(('B', 'vault'),)),
             ExecutionPlan(mesh=mesh, pipeline='two_stage',
                           stage_a=stage_a, auto=True)]:
    router = build_router(spec, plan)
    got = jax.jit(router)(micro)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    if plan.auto:
        axes = router.resolve(micro)
        assert axes and axes[0][1] == 'vault', axes
print('two-stage sharded pipeline OK')
"""
    r = subprocess.run([sys.executable, "-c", script], env=ENV,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
