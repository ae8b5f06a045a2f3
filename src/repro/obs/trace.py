"""Structured tracing: hierarchical spans with Chrome-trace export.

Spans record at *dispatch/trace time* — the host-side Python that plans,
traces jaxprs, and launches kernels — never inside kernel bodies, so the
layer adds nothing to the compiled program and forces no host sync of
its own.

Two sinks, one span: while telemetry is enabled each span is appended
to the in-memory buffer (``events()``, ``export_trace``, ``report()``)
AND entered as a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``,
so under ``jax.profiler.trace`` it lands on the profiler's host plane,
on the same clock as the caller's own annotations and JAX's dispatch
events. The ``TraceAnnotation`` is built and entered only while
telemetry is enabled. Disabled (the default), one module-attribute
check (``_state.enabled``) is the whole cost of a span: ``span()``
returns a shared no-op context, no annotation object is built and
nothing is recorded.

Span taxonomy (DESIGN.md §12.2):

* per call — ``entry.resolve`` (a CompiledExpr call's program and tile
  lookups, ring-1 audit), ``entry.route`` (choosing the dispatch) and
  ``entry.apply`` (the chosen dispatch up to its return: the
  custom-vjp boundary, then ``program.call`` > ``program.lookup`` (the
  whole-program executable's cache lookup) + ``program.enqueue`` (the
  executable's call up to its return)); ``dist.call`` (a sharded
  call's host path, ``core/distributed.py``);
* at trace time — ``stage.*`` (one primitive/fused stage as the
  executor walks the program) > ``kernel.dispatch`` /
  ``kernel.fused`` (one class-dispatch decision);
* on a plan-cache miss only — ``plan.lower``, ``plan.cluster``,
  ``plan.bwd``, ``plan.fused``, ``plan.class``, ``plan.dist``.

Drivers add ``serve.*`` / ``train.step`` roots.

``enable(sync=True)`` additionally lets *measurement sites* (program
calls, serve/train drivers) block on device results so recorded
wall-clock is end-to-end; ``sync=False`` keeps the layer strictly
non-blocking and the recorded durations are dispatch time only.
"""
from __future__ import annotations

import threading
import time
from typing import Optional

from jax import profiler as _jprof

_MAX_EVENTS = 200_000  # hard bound; events past it are counted, not kept


class _State:
    __slots__ = ("enabled", "sync")

    def __init__(self) -> None:
        self.enabled = False
        self.sync = True


_state = _State()
_events: list = []  # (name, cat, tid, ts µs, dur µs, args): events() expands
_dropped = 0
_lock = threading.Lock()
_tls = threading.local()
_clock_ns = time.perf_counter_ns
_get_ident = threading.get_ident


def enabled() -> bool:
    """Is telemetry recording?  The one check every site pays."""
    return _state.enabled


def sync_enabled() -> bool:
    """May measurement sites block on device results for end-to-end
    wall-clock?  (Never True when telemetry is off.)"""
    return _state.enabled and _state.sync


def enable(sync: bool = True) -> None:
    _state.enabled = True
    _state.sync = sync


def disable() -> None:
    _state.enabled = False


def reset() -> None:
    """Drop all recorded events (counters live in :mod:`.metrics`)."""
    global _dropped
    with _lock:
        _events.clear()
        _dropped = 0


def now_us() -> float:
    """The trace clock (µs); shared by every event so exports line up."""
    return time.perf_counter_ns() / 1e3


def _stack() -> list:
    try:
        return _tls.stack
    except AttributeError:
        _tls.stack = st = []
        return st


class _Span:
    """One enabled span: the profiler annotation entered around the
    timed body, the buffer event appended at exit."""

    __slots__ = ("name", "cat", "args", "annotation", "t0")

    def __init__(self, name: str, cat: str, args: dict) -> None:
        self.name, self.cat, self.args = name, cat, args
        self.annotation = _jprof.TraceAnnotation("repro." + name)

    def __enter__(self) -> dict:
        _stack().append(self.name)
        self.annotation.__enter__()
        self.t0 = _clock_ns()
        return self.args

    def __exit__(self, *exc) -> bool:
        t1 = _clock_ns()
        self.annotation.__exit__(None, None, None)
        stack = _tls.stack
        stack.pop()
        if _state.enabled:
            args = self.args
            if stack:
                args["parent"] = stack[-1]
                args["depth"] = len(stack)
            _append(self.name, self.cat, self.t0 / 1e3, (t1 - self.t0) / 1e3,
                    args)
        return False


class _Disabled:
    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False


_DISABLED = _Disabled()


def span(name: str, cat: str = "repro", **args):
    """Hierarchical trace span, used as ``with span(...) as sargs``.
    ``sargs`` is a mutable dict merged into the event's args at exit,
    so callers can attach facts discovered inside (e.g. the dispatched
    kernel). Enabled, the span is also the profiler annotation
    ``repro.<name>``. Disabled, ``sargs`` is None and the one
    ``_state.enabled`` check is the whole cost: no annotation is built
    and nothing is recorded."""
    if not _state.enabled:
        return _DISABLED
    return _Span(name, cat, args)


def record_event(name: str, cat: str, ts_us: float, dur_us: float,
                 args: Optional[dict] = None, parent: Optional[str] = None,
                 depth: int = 0) -> None:
    """Append one Chrome-trace complete event (``ph: "X"``)."""
    if not _state.enabled:
        return
    args = dict(args or {})
    if parent is not None:
        args["parent"] = parent
    if depth:
        args["depth"] = depth
    _append(name, cat, ts_us, dur_us, args)


def _append(name: str, cat: str, ts_us: float, dur_us: float,
            args: dict) -> None:
    global _dropped
    ev = (name, cat, _get_ident() % 1_000_000, ts_us, dur_us, args)
    with _lock:
        if len(_events) < _MAX_EVENTS:
            _events.append(ev)
        else:
            _dropped += 1


def events() -> list:
    """The recorded spans as Chrome-trace complete events (``ph: "X"``)."""
    with _lock:
        kept = list(_events)
    return [{"name": name, "cat": cat, "ph": "X", "pid": 1, "tid": tid,
             "ts": round(ts, 3), "dur": round(dur, 3), "args": args}
            for name, cat, tid, ts, dur, args in kept]


def count() -> int:
    """How many events are recorded, without building them."""
    return len(_events)


def dropped() -> int:
    return _dropped
