"""End-to-end CapsNet (the paper's model): forward shapes, margin loss,
training convergence on the synthetic dataset, Table-5 accuracy-delta
reproduction (exact vs approximated routing), pipeline equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.caps_benchmarks import CAPS_BENCHMARKS, smoke_caps
from repro.core import capsule_layers as CL
from repro.core.approx import exact_squash
from repro.core import pipeline, routing
from repro.core.router import RouterSpec, build_router
from repro.data.synthetic import SyntheticCapsDataset
from repro.models import capsnet


@pytest.fixture(scope="module")
def trained():
    """Train the smoke CapsNet (Adam, ~150 steps -> ~100% on the synthetic
    class-conditional blobs) once per module."""
    from repro.optim import AdamWConfig, adamw_init, adamw_update
    cfg = smoke_caps()
    key = jax.random.PRNGKey(0)
    params = capsnet.init_capsnet(key, cfg)
    opt = adamw_init(params)
    ocfg = AdamWConfig(lr=1e-3, weight_decay=0.0)
    ds = SyntheticCapsDataset(cfg.image_hw, cfg.image_channels,
                              cfg.num_h_caps)

    @jax.jit
    def step(params, opt, images, labels):
        (loss, metrics), grads = jax.value_and_grad(
            capsnet.loss_fn, has_aux=True)(params, images, labels, cfg)
        params, opt = adamw_update(grads, opt, params, ocfg)
        return params, opt, loss, metrics

    for i in range(150):
        b = ds.batch(i, cfg.batch_size)
        params, opt, loss, metrics = step(params, opt,
                                          jnp.asarray(b["images"]),
                                          jnp.asarray(b["labels"]))
    return cfg, params, ds, float(metrics["accuracy"])


def test_forward_shapes(key):
    cfg = smoke_caps()
    params = capsnet.init_capsnet(key, cfg)
    ds = SyntheticCapsDataset(cfg.image_hw, cfg.image_channels,
                              cfg.num_h_caps)
    b = ds.batch(0, 4)
    out = capsnet.forward(params, jnp.asarray(b["images"]), cfg)
    assert out["v"].shape == (4, cfg.num_h_caps, cfg.h_caps_dim)
    assert out["class_probs"].shape == (4, cfg.num_h_caps)
    assert out["reconstruction"].shape == (
        4, cfg.image_hw * cfg.image_hw * cfg.image_channels)
    assert bool(jnp.isfinite(out["v"]).all())


def test_training_converges(trained):
    _, _, _, acc = trained
    assert acc > 0.9, f"smoke CapsNet accuracy {acc} after 150 steps"


def test_table5_accuracy_delta(trained):
    """Paper Table 5: approximation w/ recovery costs ~0 accuracy."""
    cfg, params, ds, _ = trained
    accs = {}
    for name, rc in [
        ("exact", routing.RoutingConfig(iterations=cfg.routing_iters)),
        ("approx", routing.RoutingConfig(iterations=cfg.routing_iters,
                                         use_approx=True)),
        ("fused", RouterSpec(backend="pallas",
                             iterations=cfg.routing_iters)),
    ]:
        hits = n = 0
        for i in range(200, 204):
            b = ds.batch(i, 64)
            out = capsnet.forward(params, jnp.asarray(b["images"]), cfg, rc)
            pred = jnp.argmax(out["class_probs"], -1)
            hits += int((pred == jnp.asarray(b["labels"])).sum())
            n += 64
        accs[name] = hits / n
    assert accs["approx"] >= accs["exact"] - 0.01, accs   # ~0.04% in paper
    assert accs["fused"] == pytest.approx(accs["exact"], abs=1e-6), accs


def test_margin_loss_zero_when_perfect():
    v = jnp.zeros((2, 3, 4)).at[0, 1].set(jnp.array([1, 0, 0, 0.0]))
    v = v.at[1, 2].set(jnp.array([0, 1, 0, 0.0]))
    labels = jnp.array([1, 2])
    # perfect: correct capsule norm 1 >= .9, others 0 <= .1
    assert float(CL.margin_loss(v, labels, 3)) == pytest.approx(0.0, abs=1e-6)


def test_margin_loss_penalizes_wrong():
    v = jnp.zeros((1, 3, 4)).at[0, 0].set(jnp.array([1, 0, 0, 0.0]))
    labels = jnp.array([1])
    assert float(CL.margin_loss(v, labels, 3)) > 0.5


def test_decoder_masks_by_label(key):
    cfg = smoke_caps()
    params = capsnet.init_capsnet(key, cfg)
    v = jax.random.normal(key, (2, cfg.num_h_caps, cfg.h_caps_dim))
    r1 = CL.decoder_forward(params["decoder"], v, jnp.array([0, 0]))
    r2 = CL.decoder_forward(params["decoder"], v, jnp.array([1, 1]))
    assert float(jnp.abs(r1 - r2).max()) > 1e-6


def test_caps_table1_configs_complete():
    assert len(CAPS_BENCHMARKS) == 12
    mn1 = CAPS_BENCHMARKS["Caps-MN1"]
    assert (mn1.batch_size, mn1.num_l_caps, mn1.num_h_caps,
            mn1.routing_iters) == (100, 1152, 10, 3)
    sv3 = CAPS_BENCHMARKS["Caps-SV3"]
    assert sv3.routing_iters == 9 and sv3.num_l_caps == 576


@pytest.mark.parametrize("network", ["Caps-MN1", "Caps-EN3"])
def test_predict_votes_packed_matches_eq1(key, network):
    """Eq.1 through W's lane-packed view (L, C_L, H·C_H) == the plain
    (L, H, C_L, C_H) contraction, on squashed primary capsules."""
    c = CAPS_BENCHMARKS[network]
    ku, kw = jax.random.split(key)
    u = exact_squash(jax.random.normal(ku, (4, c.num_l_caps, c.l_caps_dim)))
    params = CL.init_caps_layer(kw, c.num_l_caps, c.num_h_caps,
                                c.l_caps_dim, c.h_caps_dim)
    with jax.default_matmul_precision("highest"):
        got = CL.predict_votes(params, u)
        want = jnp.einsum("blc,lhcd->blhd", u, params["W"])
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-6)


def _scan_carries(fn, *args):
    """Carry shapes of each top-level scan in fn's jaxpr."""
    eqns = jax.make_jaxpr(fn)(*args).jaxpr.eqns
    return [tuple(v.aval.shape for v in e.invars[
                e.params["num_consts"]:
                e.params["num_consts"] + e.params["num_carry"]])
            for e in eqns if e.primitive.name == "scan"]


@pytest.mark.parametrize("hand_off", ["votes", "em"])
def test_software_pipeline_matches_sequential(key, hand_off):
    """paper §4 pipeline: overlapped schedule == sequential composition,
    for a bare-votes hand-off (dynamic routing) and EM's (votes, a_in)
    tuple; the scan carries the votes lane-packed, (B, L, H·C), and a_in
    as it is."""
    cfg = smoke_caps()
    params = capsnet.init_capsnet(key, cfg)
    ds = SyntheticCapsDataset(cfg.image_hw, cfg.image_channels,
                              cfg.num_h_caps)
    B = 4
    micro = jnp.stack([jnp.asarray(ds.batch(i, B)["images"])
                       for i in range(3)])
    packed = (B, cfg.num_l_caps, cfg.num_h_caps * cfg.h_caps_dim)

    def stage_a(images):  # "host": conv + primary caps + votes
        u = capsnet.primary_caps(params, images, cfg)
        votes = CL.predict_votes(params["digit"], u)
        if hand_off == "votes":
            return votes
        return votes, jax.nn.sigmoid(jnp.sum(u, axis=-1))

    if hand_off == "votes":
        rc = routing.RoutingConfig(iterations=cfg.routing_iters)
        stage_b = lambda u_hat: routing.dynamic_routing(u_hat, rc)  # noqa
        carry = (packed,)
    else:
        core = build_router(RouterSpec(algorithm="em", iterations=2))
        stage_b = lambda h: core(*h)                                # noqa
        carry = (packed, (B, cfg.num_l_caps))

    run = lambda m: pipeline.software_pipeline_scan(  # noqa: E731
        stage_a, stage_b, m)
    assert carry in _scan_carries(run, micro)
    piped = run(micro)
    seq = [stage_b(stage_a(m)) for m in micro]
    for got, *want in zip(jax.tree.leaves(piped),
                          *map(jax.tree.leaves, seq)):
        np.testing.assert_allclose(got, jnp.stack(want),
                                   rtol=1e-5, atol=1e-6)
