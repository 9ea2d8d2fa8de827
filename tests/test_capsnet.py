"""End-to-end CapsNet (the paper's model): forward shapes, margin loss,
training convergence on the synthetic dataset, Table-5 accuracy-delta
reproduction (exact vs approximated routing), pipeline equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.caps_benchmarks import CAPS_BENCHMARKS, smoke_caps
from repro.core import capsule_layers as CL
from repro.core import pipeline, routing
from repro.core.router import RouterSpec
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


def test_software_pipeline_matches_sequential(key):
    """paper §4 pipeline: overlapped schedule == sequential composition."""
    cfg = smoke_caps()
    params = capsnet.init_capsnet(key, cfg)
    ds = SyntheticCapsDataset(cfg.image_hw, cfg.image_channels,
                              cfg.num_h_caps)
    micro = jnp.stack([jnp.asarray(ds.batch(i, 4)["images"])
                       for i in range(3)])
    rc = routing.RoutingConfig(iterations=cfg.routing_iters)

    def stage_a(images):  # "host": conv + primary caps + votes
        u = capsnet.primary_caps(params, images, cfg)
        return CL.predict_votes(params["digit"], u)

    def stage_b(u_hat):   # "PIM": routing procedure
        return routing.dynamic_routing(u_hat, rc)

    piped = pipeline.software_pipeline_scan(stage_a, stage_b, micro)
    seq = jnp.stack([stage_b(stage_a(m)) for m in micro])
    np.testing.assert_allclose(piped, seq, rtol=1e-5, atol=1e-6)
