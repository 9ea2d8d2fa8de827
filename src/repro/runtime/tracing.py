"""Host spans on the profiler's clock.

``span(name, **args)`` is ``jax.profiler.TraceAnnotation``: while a
profiler trace runs (``jax.profiler.start_trace`` / ``trace``), the span
lands in the trace's host plane, on the same clock as the device's ops;
otherwise entering and leaving it costs about a microsecond and records
nothing.  ``watch_gc()`` puts every garbage collection of the process in
the trace as a ``host.gc`` span.

Spans the program opens (PERF.md names the metric that reads each):

  serve.step      one wave of ``WaveServer.step``, after the queue pop
                  (args: wave, lanes); inside it, in order,
  serve.pack      ``adapter.pack``: host fill and host->device copy
  serve.launch    the wave executable's dispatch
  serve.guard     ``adapter.finite``: the first host read of the output,
                  so the wait for the device and the device->host copy
  serve.unpack    ``adapter.unpack``
  serve.complete  the completion accounting under the server's lock
  host.gc         one collection (args: generation)

Named scopes inside the jitted programs (``jax.named_scope``) are set
where the code they cover lives: ``capsnet.encode``, ``capsnet.routing``,
``capsnet.decoder``, ``routing.relayout``, ``train.clip``,
``train.adamw``; in the matrix-capsule model ``emcaps.convcaps1``,
``emcaps.convcaps2`` and ``emcaps.class`` around each routed layer (its
votes under ``capsnet.encode``, its route call under ``capsnet.routing``)
and ``routing.em_fold`` around EM's cross-window normalisation.
"""
from __future__ import annotations

import gc
import threading

import jax

_gc_lock = threading.Lock()
_gc_span = []           # the open host.gc span, at most one


def span(name: str, **args):
    """A profiler host span named ``name``; ``args`` become its stats."""
    return jax.profiler.TraceAnnotation(name, **args)


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        s = span("host.gc", generation=info["generation"])
        s.__enter__()
        _gc_span.append(s)
    elif _gc_span:
        _gc_span.pop().__exit__(None, None, None)


def watch_gc() -> None:
    """Record every garbage collection as a ``host.gc`` span from now on.
    Idempotent: the hook is added once per process."""
    with _gc_lock:
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
