"""bench/work.py: model FLOPs and least bytes from shapes alone."""
import copy

import pytest

from bench import registry, work


def _cfg(name):
    return registry.config(name)


def test_caps_mn1_forward_flops_by_hand():
    cfg = _cfg("caps-mn1")
    enc = work.encoder_flops_per_image(cfg)
    # conv1: 20x20 outputs x 9x9x1 x 256, PrimaryCaps: 6x6 x 9x9x256 x 256
    assert enc["conv1"] == 2 * 20 * 20 * 81 * 1 * 256
    assert enc["primary_caps"] == 2 * 6 * 6 * 81 * 256 * 256
    assert enc["votes"] == 2 * 1152 * 10 * 8 * 16
    per_iter = (5 * 1152 * 10 + 4 * 8 * 1152 * 10 * 16
                + 8 * 10 * (3 * 16 + 4) + 1152 * 10)
    call = work.routing_call(cfg, 8)
    assert work.routing_flops(call) == 3 * per_iter
    total = work.forward_flops_per_image(cfg, 8)
    assert total == pytest.approx(sum(enc.values()) + 3 * per_iter / 8)
    assert 0.39e9 < total < 0.41e9


def test_caps_en3_streams_six_times_the_votes_of_caps_mn1():
    mn1, en3 = _cfg("caps-mn1"), _cfg("caps-en3")
    # u_hat of one image: 1152 x H x 16 fp32, 0.74 MB (H=10), 4.57 MB (H=62)
    one = {n: work.routing_call(c, 1) for n, c in (("mn1", mn1),
                                                   ("en3", en3))}
    assert work.routing_bytes(one["mn1"]) == (1152 * 160 + 160) * 4
    assert work.routing_bytes(one["en3"]) == (1152 * 992 + 992) * 4
    assert (work.encoder_flops_per_image(en3)["votes"]
            == 6.2 * work.encoder_flops_per_image(mn1)["votes"])


@pytest.mark.parametrize("change", [
    {"router": {"algorithm": "dynamic", "backend": "pallas",
                "iterations": 3, "fusion": "iteration"}},
    {"router": {"algorithm": "dynamic", "backend": "pallas",
                "iterations": 3, "fusion": "stage_split"}},
    {"routing_plan": "auto", "chips": 4},
    {"routing_plan": [["L", "vault"]], "chips": 4},
])
def test_routing_work_ignores_form_and_plan(change):
    base = _cfg("caps-en3")
    other = copy.deepcopy(base)
    other.update(change)
    for batch in (8, 100):
        a, b = work.routing_call(base, batch), work.routing_call(other, batch)
        assert work.routing_flops(a) == work.routing_flops(b)
        assert work.routing_bytes(a) == work.routing_bytes(b)
        assert work.routing_bwd_bytes(a) == work.routing_bwd_bytes(b)
    assert (work.forward_flops_per_image(base, 8)
            == work.forward_flops_per_image(other, 8))
    assert (work.train_flops_per_image(base, 100)
            == work.train_flops_per_image(other, 100))


def test_least_bytes_take_the_smaller_stream():
    cfg = _cfg("caps-mn1")
    small, big = work.routing_call(cfg, 8), work.routing_call(cfg, 100)
    # B=8: u_hat (5.9 MB) is less than u and W (6.2 MB)
    assert work.routing_bytes(small) == (8 * 1152 * 160 + 8 * 160) * 4
    # B=100: u and W (9.6 MB) are less than u_hat (73.7 MB)
    assert work.routing_bytes(big) == (100 * 1152 * 8 + 1152 * 10 * 128
                                       + 100 * 160) * 4


def test_least_seconds_is_the_larger_bound():
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert work.least_seconds(2e12, 1e9, peak) == 2.0
    assert work.least_seconds(1e12, 3e9, peak) == 3.0


def test_training_counts_backward_twice_but_conv1_input_grad_never():
    cfg = _cfg("caps-mn1")
    enc = work.encoder_flops_per_image(cfg)
    fwd = (sum(enc.values()) + work.decoder_flops_per_image(cfg)
           + work.routing_flops(work.routing_call(cfg, 100)) / 100)
    assert work.train_flops_per_image(cfg, 100) == pytest.approx(
        3 * fwd - enc["conv1"])
