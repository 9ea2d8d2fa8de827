"""EM routing kernels' share of their roofline, % (layer: routing kernels).

The least time of the EM routing work done in the window over the device
time of the EM kernels and of the cross-window fold between them.  The
least time of a routed layer's call is max(FLOPs / peak, least bytes /
HBM bandwidth) on the call's logical shape (``bench/work_em.py``),
whatever kernels run it.  The kernels are found by their trace names, the
program's jitted kernel entry points; the fold is XLA ops, found by the
program's ``routing.em_fold`` scope (the driver's ``em_fold_s``)."""

from bench import trace, work, work_em

KERNELS = {"em_routing": ("em_stage_stats", "em_stage_estep")}


def read(outcome, ctx):
    c = outcome.counters
    if outcome.trace is None or "em_routing" not in c:
        return None
    names = KERNELS["em_routing"]
    kernel_s = sum(trace.op_seconds(outcome.trace, names).values()) \
        + c.get("em_fold_s", 0.0)
    if kernel_s <= 0:
        return None
    least = sum(n * work.least_seconds(work_em.em_flops(call),
                                       work_em.em_bytes(call), ctx.peak)
                for call, n in c["em_routing"])
    return 100.0 * least / ctx.chips / kernel_s
