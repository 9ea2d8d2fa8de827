"""runtime.tracing and the program's own counters: the server's queue-wait
counters under an injected clock (a retried wave included), their fleet
roll-up, the idempotent garbage-collection hook, and the named scopes in
the lowered serving wave and train step."""
import gc
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.caps_benchmarks import CapsConfig
from repro.core.router import RouterSpec
from repro.models import capsnet
from repro.optim import adamw_init
from repro.runtime import tracing
from repro.runtime.caps_serve import make_wave_fn
from repro.runtime.train_loop import make_capsnet_train_step
from repro.runtime.wave_serve import ServeConfig, WaveServer, \
    WorkloadAdapter


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class Doubler(WorkloadAdapter):
    """Numpy payloads, doubled; the first ``fail`` waves raise."""

    def __init__(self, fail: int = 0):
        self.fail = fail

    def validate(self, items):
        return [np.asarray(x, np.float32) for x in items]

    def make_wave_fn(self, cfg):
        def wave(x):
            if self.fail:
                self.fail -= 1
                raise RuntimeError("planted wave fault")
            return 2.0 * x
        return wave

    def pack(self, payloads, cfg):
        out = np.zeros((cfg.wave_lanes,), np.float32)
        out[:len(payloads)] = payloads
        return out

    def unpack(self, out, n):
        return [float(v) for v in out[:n]]


def test_queue_wait_is_summed_at_each_pop_on_the_servers_clock():
    clock = Clock()
    server = WaveServer(Doubler(), cfg=ServeConfig(microbatch=2, n_micro=1),
                        clock=clock)
    assert server.metrics.summary()["mean_queue_wait_s"] is None
    server.submit([1.0, 2.0, 3.0])          # t_submit 0
    clock.t = 1.0
    server.submit([4.0])                    # t_submit 1
    clock.t = 2.0
    server.step()                           # pops two at 2: waits 2 + 2
    clock.t = 5.0
    server.step()                           # pops two at 5: waits 5 + 4
    m = server.metrics
    assert m.dequeued == 4
    assert m.queue_wait_s == pytest.approx(13.0)
    assert m.summary()["mean_queue_wait_s"] == pytest.approx(13.0 / 4)


def test_a_retried_wave_is_popped_and_counted_again():
    clock = Clock()
    server = WaveServer(Doubler(fail=1),
                        cfg=ServeConfig(microbatch=2, n_micro=1),
                        clock=clock)
    server.submit([1.0, 2.0])
    clock.t = 1.0
    assert server.step() == []              # fails; both requeue
    clock.t = 3.0
    done = server.step()
    assert [c.pred for c in done] == [2.0, 4.0]
    m = server.metrics
    assert m.retried == 1 and m.dequeued == 4
    # each pop waits from the original submit: 1 + 1, then 3 + 3
    assert m.queue_wait_s == pytest.approx(8.0)
    assert m.mean_queue_wait_s == pytest.approx(2.0)


def test_the_fleet_rolls_up_the_queue_wait():
    from repro.runtime.caps_fleet import CapsFleet
    clock = Clock()
    fleet = CapsFleet(None, None, clock=clock, models={
        "a": (Doubler(), ServeConfig(microbatch=2, n_micro=1))})
    fleet.submit([1.0, 2.0, 3.0], model="a")
    clock.t = 2.0
    fleet.drain()
    summary = fleet.summary()
    assert summary["completed"] == 3
    assert summary["mean_queue_wait_s"] == pytest.approx(2.0)


def test_watch_gc_adds_one_hook():
    tracing.watch_gc()
    tracing.watch_gc()
    hooks = [cb for cb in gc.callbacks if cb is tracing._on_gc]
    assert len(hooks) == 1
    gc.collect()                            # no trace running: no effect
    assert tracing._gc_span == []


TINY = CapsConfig(name="Caps-MN1-tiny", dataset="MNIST", batch_size=4,
                  num_l_caps=32, num_h_caps=10, routing_iters=2,
                  l_caps_dim=8, h_caps_dim=16, image_hw=28,
                  image_channels=1, conv_channels=16, caps_channels=2)


@pytest.fixture(scope="module")
def tiny_params():
    return capsnet.init_capsnet(jax.random.key(0), TINY)


def _scopes(fn, *args) -> set:
    """The program scopes in the name stacks of the lowered text."""
    text = fn.lower(*args).as_text(debug_info=True)
    return set(re.findall(
        r"(?<![\w.])((?:capsnet|routing|train)\.\w+)(?=[/)])", text))


def test_the_serving_wave_carries_the_program_scopes(tiny_params):
    scfg = ServeConfig(microbatch=4, n_micro=2, pipeline="software")
    wave = make_wave_fn(tiny_params, TINY,
                        RouterSpec(backend="pallas", iterations=2), scfg)
    assert _scopes(wave, {"images": jnp.zeros((2, 4, 28, 28, 1)),
                          "mask": jnp.ones((2, 4))}) == {
        "capsnet.encode", "capsnet.routing", "routing.relayout"}


def test_the_train_step_carries_the_program_scopes(tiny_params):
    step = jax.jit(make_capsnet_train_step(TINY, plan="auto"))
    args = (tiny_params, adamw_init(tiny_params), jnp.zeros((4, 28, 28, 1)),
            jnp.zeros((4,), jnp.int32))
    assert _scopes(step, *args) == {
        "capsnet.encode", "capsnet.routing", "capsnet.decoder",
        "routing.relayout", "train.clip", "train.adamw"}
    # backward ops keep their forward scope in the name stack
    assert "transpose(jvp(capsnet.encode))" in step.lower(*args).as_text(
        debug_info=True)
