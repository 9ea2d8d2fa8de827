"""What the harness and its drivers share: the run's context and outcome,
host spans, and the mapping from a configuration file to the program's
configuration."""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, List, Optional

# The program's PrimaryCaps geometry is fixed (9x9 kernels, stride 2); a
# configuration that states another cannot run through it.
PROGRAM_GEOMETRY = {"conv_kernel": 9, "caps_kernel": 9, "caps_stride": 2}


@dataclasses.dataclass
class Context:
    """One run: the cell's configuration and mix, and the command line."""
    workload: str
    config: Dict[str, Any]
    mix: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    t_start: float              # process start, time.perf_counter()
    chips: int
    peak: Dict[str, float]
    scratch_dir: str            # for the trace; inside the checkout
    # "bf16": the reference in bf16 serves; "fewer_iters", "half_batch":
    # a fault planted in the program (bench/explore.py)
    control: Optional[str] = None
    # bench/explore.py: further reference precisions to read the gaps at
    ref_precisions: tuple = ()
    log: Callable[[str], None] = print


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to the harness."""
    attempted: int
    failed: int
    setup_s: float
    end_to_end: Dict[str, float]
    readings: List[tuple]            # (name, value, limit)
    counters: Dict[str, Any]
    memory_peak_bytes: int
    trace: Optional[dict] = None     # bench.trace.reduce() of the window


def annotate(ctx: Context, name: str):
    """A profiler host span in traced runs, nothing otherwise."""
    if not ctx.trace:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def path_readings(stated: Optional[str], form: Optional[str],
                  summary: Optional[dict]) -> List[tuple]:
    """Readings that hold the program to the path the configuration states.

    ``path_form`` is 0 when the routing kernel form the program resolved
    is the stated one (None: jnp routing), 1 otherwise: a router that
    falls back, say to jnp autodiff when a kernel does not fit, would be
    timing another path.  In a traced run that states a kernel form,
    ``kernels_absent`` is 0 when a routing kernel named in
    metrics/routing_roofline.py ran in the window, 1 otherwise."""
    from bench import registry, trace
    out = [("path_form", 0.0 if form == stated else 1.0, 0.0)]
    if summary is not None and stated is not None:
        kernels = registry.metric_module("routing_roofline").KERNELS
        names = [n for group in kernels.values() for n in group]
        ran = sum(trace.op_seconds(summary, names).values()) > 0
        out.append(("kernels_absent", 0.0 if ran else 1.0, 0.0))
    return out


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def caps_config(cfg: Dict[str, Any]):
    """The program's CapsConfig for a configuration file."""
    from repro.configs.caps_benchmarks import CapsConfig
    for key, value in PROGRAM_GEOMETRY.items():
        if cfg[key] != value:
            raise ValueError(f"{key}={cfg[key]}: the program's PrimaryCaps "
                             f"layer is fixed at {value}")
    return CapsConfig(
        name=cfg["network"], dataset=cfg["dataset"],
        batch_size=cfg["table1_batch"], num_l_caps=cfg["num_l_caps"],
        num_h_caps=cfg["num_h_caps"], routing_iters=cfg["routing_iters"],
        l_caps_dim=cfg["l_caps_dim"], h_caps_dim=cfg["h_caps_dim"],
        image_hw=cfg["image_hw"], image_channels=cfg["image_channels"],
        conv_channels=cfg["conv_channels"],
        caps_channels=cfg["caps_channels"])


def router_spec(cfg: Dict[str, Any]):
    from repro.core import router as router_lib
    r = cfg["router"]
    return router_lib.RouterSpec(algorithm=r["algorithm"],
                                 backend=r["backend"],
                                 iterations=r["iterations"])

