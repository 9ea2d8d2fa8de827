"""Routing kernels' share of their roofline, % (layer: routing kernels).

The least time of the routing work done in the window, over the device
time of the routing kernels' events in the trace.  The least time of a
call is max(FLOPs / peak, least bytes / HBM bandwidth) of dynamic routing
on the call's logical shape (``bench/work.py``), whatever kernel form or
plan ran it; on several chips each holds its share of it.  The kernels are
found by the names below, the program's jitted kernel entry points, which
name their custom calls in the trace."""

from bench import trace, work

KERNELS = {
    "routing_fwd": ("routing_procedure_fused", "routing_iteration_fused",
                    "routing_stage_votes", "routing_stage_update",
                    "routing_stage_update_fold"),
    "routing_bwd": ("routing_procedure_bwd",),
}
_WORK = {"routing_fwd": (work.routing_flops, work.routing_bytes),
         "routing_bwd": (work.routing_bwd_flops, work.routing_bwd_bytes)}


def read(outcome, ctx):
    if outcome.trace is None:
        return None
    names = [n for kind in KERNELS for n in KERNELS[kind]
             if kind in outcome.counters]
    kernel_s = sum(trace.op_seconds(outcome.trace, names).values())
    if kernel_s <= 0:
        return None
    least = 0.0
    for kind, (flops, nbytes) in _WORK.items():
        if kind in outcome.counters:
            call, n = outcome.counters[kind]
            least += n * work.least_seconds(flops(call), nbytes(call),
                                            ctx.peak)
    return 100.0 * least / ctx.chips / kernel_s
