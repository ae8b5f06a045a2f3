"""Telemetry for the permutation executor stack (DESIGN.md §12).

Three layers, all zero-cost while disabled (the default — every
instrumentation site is one module-attribute check, and nothing is
recorded inside kernels or compiled jaxprs):

* :mod:`.trace`   — hierarchical spans (entry > program > stage >
  kernel dispatch, plus plan-cache misses), recorded at dispatch/trace
  time on the host, each also a ``repro.<name>`` profiler annotation.
* :mod:`.metrics` — labeled counters + histograms: kernel-class
  dispatch counts, fold_free eliminations, DMA descriptors, modeled
  round trips, request/step latency.
* :mod:`.export`  — ``export_trace(path)`` (Chrome trace / Perfetto
  JSON), ``report()`` (plain-text summary), ``snapshot()`` (the same as
  a dict, embedded in benchmark ``--json`` payloads).

Quick tour::

    from repro import obs
    obs.enable()                   # sync=True: measured wall-clock
    y = compiled(x)                # instrumented executor records
    print(obs.report())
    obs.export_trace("run.trace.json")   # open in chrome://tracing
    obs.reset(); obs.disable()

``obs.kernel_counts()`` uses the same vocabulary as
``CompiledExpr.cost(...)["kernels"]``, so model honesty is one dict
comparison; ``obs.cache_stats()`` aggregates every executor/ops cache.
"""
from .trace import (disable, enable, enabled, events, record_event, reset as
                    _reset_trace, span, sync_enabled)
from .metrics import (class_counts, counter_total, counter_value, counters,
                      histograms, inc, kernel_counts, observe,
                      reset as _reset_metrics)
from .export import (cache_stats, export_trace, model_vs_measured, report,
                     snapshot)


def reset() -> None:
    """Drop all recorded spans, counters and histograms (the enabled
    flag is untouched)."""
    _reset_trace()
    _reset_metrics()


__all__ = [
    "enable", "disable", "enabled", "sync_enabled", "reset", "span",
    "events", "record_event", "inc", "observe", "counters",
    "counter_value", "counter_total", "histograms", "kernel_counts",
    "class_counts", "cache_stats", "export_trace", "model_vs_measured",
    "report", "snapshot",
]
