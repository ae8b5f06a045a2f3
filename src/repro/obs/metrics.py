"""Counters + histograms for the permutation executor stack.

Counters are labeled monotonic sums (``inc``); histograms keep running
count/sum/min/max plus a fixed-size deterministic reservoir for
percentiles (``observe``). Both are plain host-side Python — safe to
call at jit-trace time (values must be concrete Python numbers, which
every instrumentation site guarantees: they come from offline plans and
host clocks, never from traced arrays) — and both are no-ops while
telemetry is disabled.

Counter vocabulary used by the executor stack (DESIGN.md §12):

* ``dispatch.kernel{kernel=...}`` — one count per kernel dispatch, in
  the ``program_cost(...)["kernels"]`` vocabulary (``none`` / ``block``
  / ``lane`` / ``tiled`` / ``general`` / ``general2`` / ``fused`` /
  ``sweep``) plus ``ref`` for gather-oracle executions.
* ``dispatch.class{cls=...}`` — the BMMC *class* (identity / complement
  / block / lane / tiled / general) of each dispatched matrix.
* ``dma.descriptors`` / ``model.round_trips`` — modeled DMA descriptor
  and HBM-round-trip totals of everything dispatched.
* ``dma.box_sides{side=in|out}`` — tiled-pass sides dispatched whose
  rows form a box, each copied by one strided descriptor per tile
  (``TilePlan.in_box`` / ``out_box``).
* ``intra.xor_moves`` / ``intra.maps`` / ``intra.transposed`` — per
  tiled pass dispatched, the XOR moves its intra-tile schedule emits,
  the one-hot maps it runs, and 1 where it transposes the tile
  (``IntraSteps``).
* ``dist.rounds{kind=local|permute|exchange}`` /
  ``dist.bytes{kind=all_to_all|ppermute}`` — the rounds of a sharded
  program (``core/distributed.py``) and the bytes its plan sends
  between chips per call, summed over the chips; at trace time.
* ``dispatch.vjp{kind=...}`` — one count per custom-vjp backward rule
  executed (``perm`` / ``collapsed`` / ``replay`` / ``fused`` /
  ``stage``), i.e. which backward compilation path (DESIGN.md §13) a
  gradient took.
* ``model.vjp_round_trips`` — the slice of ``model.round_trips``
  attributable to backward-rule bodies: each vjp rule records the
  ``model.round_trips`` delta its own dispatches produced, so a cold
  backward call's ``model.vjp_round_trips`` delta equals the modeled
  cost of the compiled inverse/collapsed program
  (``CompiledExpr.vjp_round_trips`` — the backward honesty gate).
* ``optimize.fold_free_folds`` / ``optimize.clusters`` /
  ``optimize.cluster_stages_absorbed`` — planner decisions.
* ``dispatch.fused_fallback`` — clusters replayed stage-at-a-time.
* ``guard.trap{kind=..., engine=...}`` — one count per runtime guard
  flag that fired (``oob`` / ``nonfinite`` / ``parity``; DESIGN.md
  §14), labeled with the engine it fired on.
* ``guard.fallback{engine=...}`` / ``guard.recovered`` — graceful
  degradations: a trapped pallas call re-dispatched through ``engine``
  (always ``ref`` today), and how many of those fallbacks came back
  clean.
* ``guard.raised{error=...}`` — unrecovered traps that escaped as a
  typed ``GuardError`` (``GuardTrap`` / ``CachePoisoned``), by type.

* ``store.hit{kind=...}`` / ``store.miss{kind=...}`` — durable plan
  store (DESIGN.md §15) probes by entry kind (``class`` / ``fused``).
  A hit means the plan was decoded from disk AND re-passed its ring-1
  audit; everything else falls through to a miss.
* ``store.write{kind=...}`` / ``store.write_failed{kind=...}`` —
  write-backs after a replan (failures are non-fatal: the store
  degrades to a pure in-process cache on a read-only disk).
* ``store.corrupt{kind=...}`` / ``store.quarantined{kind=...}`` —
  integrity failures by cause (``corrupt`` = checksum/structure,
  ``audit`` = decoded fine but refused by ring 1). ``quarantined``
  counts the entries actually moved to ``quarantine/`` — under a
  detection race exactly one detector wins the move, so
  ``quarantined <= corrupt``.
* ``store.version_skew`` — entries from an older schema or planner
  generation: a plain miss (legal, just unusable), overwritten by the
  rebuild, never quarantined.
* ``store.plan_built{kind=...}`` — plans built from scratch (stays 0 on
  a disk-warm boot: ``tests/test_store.py``
  ``test_warm_boot_compiles_zero_plans`` and
  ``test_two_process_persistence_roundtrip``).

* ``resilience.breaker.open{engine=...}`` /
  ``resilience.breaker.probe{engine=...}`` /
  ``resilience.breaker.close{engine=...}`` — circuit-breaker
  transitions (DESIGN.md §16): a protected engine condemned after
  ``threshold`` consecutive traps, the half-open health probe admitted
  after the cool-down, and a clean probe restoring full service.
* ``resilience.breaker.shunt{engine=...}`` — calls routed straight to
  the fallback engine at plan level while a circuit is open (the
  chaos gate's ``traps_while_open == 0`` verifies these pay zero
  per-call trap cost).
* ``resilience.retry`` — bounded retries of retryable GuardErrors
  (request policy backoff, and the validated train step's transient
  trap retries).
* ``resilience.deadline`` — requests that exhausted their deadline
  budget (including refusing a backoff sleep that could only end past
  the deadline).
* ``resilience.shed`` — requests refused at admission: backlog at
  capacity, or the EWMA-estimated drain time already exceeds the
  deadline budget.

The guard counters are *also* mirrored into ``repro.guard.stats()``,
which records regardless of obs being enabled — guards must count even
when telemetry is off. The store counters mirror the same way:
``repro.store.stats()`` is the always-on session record (plus a
``store_quarantined`` mirror inside ``guard.stats()``), and the
``store.*`` obs counters light up only under telemetry. The resilience
counters follow suit: ``repro.resilience.stats()`` aggregates the
always-on request-policy record plus the breaker board's transition
counts and live circuit snapshots.

Span vocabulary for gradients mirrors the forward's: ``program.vjp`` /
``fused.vjp`` / ``stage.vjp`` wrap the corresponding backward rule
bodies, and ``kernel.fused_bwd`` wraps the (gated) gradient megakernel.
"""
from __future__ import annotations

import math
import threading
from typing import Dict, Tuple

from . import trace as _trace

_RESERVOIR = 1024

_lock = threading.Lock()
_counters: Dict[tuple, float] = {}
_hists: Dict[tuple, "_Hist"] = {}

Key = Tuple[str, tuple]


class _Hist:
    __slots__ = ("count", "total", "vmin", "vmax", "sample")

    def __init__(self) -> None:
        self.count = 0
        self.total = 0.0
        self.vmin = math.inf
        self.vmax = -math.inf
        self.sample: list = []

    def add(self, v: float) -> None:
        self.count += 1
        self.total += v
        if v < self.vmin:
            self.vmin = v
        if v > self.vmax:
            self.vmax = v
        if len(self.sample) < _RESERVOIR:
            self.sample.append(v)
        else:  # deterministic overwrite (no RNG: identical across runs)
            self.sample[self.count % _RESERVOIR] = v

    def summary(self) -> dict:
        s = sorted(self.sample)

        def pct(p: float) -> float:
            return s[min(len(s) - 1, int(p * len(s)))] if s else 0.0

        return {
            "count": self.count, "sum": self.total,
            "min": self.vmin if self.count else 0.0,
            "max": self.vmax if self.count else 0.0,
            "mean": self.total / self.count if self.count else 0.0,
            "p50": pct(0.50), "p90": pct(0.90), "p99": pct(0.99),
        }


def _key(name: str, labels: dict) -> Key:
    return (name, tuple(sorted(labels.items())))


def inc(name: str, value: float = 1, **labels) -> None:
    """Add ``value`` to a labeled counter. No-op when disabled."""
    if not _trace._state.enabled:
        return
    k = _key(name, labels)
    with _lock:
        _counters[k] = _counters.get(k, 0) + value


def observe(name: str, value: float, **labels) -> None:
    """Record one histogram observation. No-op when disabled."""
    if not _trace._state.enabled:
        return
    k = _key(name, labels)
    with _lock:
        h = _hists.get(k)
        if h is None:
            h = _hists[k] = _Hist()
        h.add(value)


def counters() -> dict:
    """Snapshot ``{(name, ((label, value), ...)): count}``."""
    with _lock:
        return dict(_counters)


def counter_value(name: str, **labels) -> float:
    with _lock:
        return _counters.get(_key(name, labels), 0)


def counter_total(name: str) -> float:
    """Sum of a counter across all label sets."""
    with _lock:
        return sum(v for (n, _), v in _counters.items() if n == name)


def histograms() -> dict:
    """Snapshot ``{(name, labels): summary-dict}``."""
    with _lock:
        return {k: h.summary() for k, h in _hists.items()}


def _label_counts(name: str, label: str) -> dict:
    out: dict = {}
    with _lock:
        for (n, labels), v in _counters.items():
            if n != name:
                continue
            key = dict(labels).get(label, "?")
            out[key] = out.get(key, 0) + int(v)
    return out


def kernel_counts() -> dict:
    """Per-kernel dispatch counts in the ``program_cost`` vocabulary —
    directly comparable to ``CompiledExpr.cost(...)["kernels"]``."""
    return _label_counts("dispatch.kernel", "kernel")


def class_counts() -> dict:
    """Per-BMMC-class dispatch counts (identity/complement/block/lane/
    tiled/general)."""
    return _label_counts("dispatch.class", "cls")


def reset() -> None:
    with _lock:
        _counters.clear()
        _hists.clear()
