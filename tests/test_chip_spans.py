"""The readings of ``chip_spans.py`` (the library's spans in a profiler
trace of a benchmark cell), checked on the CPU: its reductions on a
hand-made reduced trace, and its set-up and window readings on a trace
recorded here of a 2^8 compiled sort driven by the benchmark's own
loop."""
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "bench")]

import chip_spans  # noqa: E402
import harness  # noqa: E402
import xplane  # noqa: E402
from repro import obs  # noqa: E402


def _call(t0, spans):
    """One traced call at ``t0`` (ns) lasting 1,000 ns: the harness's
    three spans, and ``spans`` as [name, start offset, duration]."""
    bench = [["bench.call", t0, 1000], ["bench.enqueue", t0, 700],
             ["bench.wait", t0 + 700, 300]]
    return bench, [[name, t0 + a, d] for name, a, d in spans]


@pytest.mark.tier1
def test_readings_reduce_spans_per_call():
    """Per-call means of every library span, the outermost JAX dispatch
    only, the lookup and dispatch sums, and idle gaps named by the
    innermost harness or library span."""
    host, extra = [], []
    for t0 in (0, 10_000):
        b, e = _call(t0, [["repro.entry.resolve", 0, 400],
                          ["repro.entry.route", 400, 50],
                          ["repro.entry.apply", 450, 240],
                          ["repro.program.lookup", 460, 40],
                          ["repro.program.enqueue", 500, 180],
                          ["PjitFunction(run)", 510, 160],
                          ["PjitFunction(inner)", 520, 50]])
        host += b
        extra += e
    extra.sort(key=lambda h: (h[1], -h[2]))
    # the device runs during the waits only: the longer idle gap lies
    # between the two calls, the shorter one has its middle inside the
    # first call's entry.resolve
    ops = [["k", t0 + 700, 300, "op"] for t0 in (0, 10_000)]
    trace = {"devices": [{"name": "/device:TPU:0", "shift_ns": 0, "ops": ops}],
             "host": sorted(host, key=lambda h: h[1])}
    out = chip_spans.readings(trace, extra, [700e-9, 700e-9])
    assert out["calls"] == 2
    assert out["host_call_us"] == pytest.approx(0.7)
    per = out["spans_us_per_call"]
    assert per["repro.entry.resolve"] == pytest.approx(0.4)
    assert per["PjitFunction(run)"] == pytest.approx(0.16)
    assert "PjitFunction(inner)" not in per
    assert out["host_lookup_us"] == pytest.approx(0.49)
    assert out["host_dispatch_us"] == pytest.approx(0.18)
    labels = [name for name, _ in out["idle_gaps"]]
    assert labels == ["harness", "repro.entry.resolve"]


@pytest.mark.tier1
def test_readings_without_library_spans_use_jax_dispatch():
    """A bare ``jax.jit`` entry has no ``program.enqueue``: the
    dispatch is JAX's outermost ``PjitFunction`` event, and a trace
    without devices names no gaps."""
    host, extra = _call(0, [["PjitFunction(fn)", 10, 600]])
    out = chip_spans.readings({"devices": [], "host": host}, extra, [700e-9])
    assert out["host_lookup_us"] == 0.0
    assert out["host_dispatch_us"] == pytest.approx(0.6)
    assert out["idle_gaps"] == []


@pytest.mark.tier1
def test_union_s_counts_overlaps_once():
    events = [{"ts": 0.0, "dur": 2e6}, {"ts": 1e6, "dur": 2e6},
              {"ts": 1.5e6, "dur": 0.5e6}, {"ts": 5e6, "dur": 1e6}]
    assert chip_spans.union_s(events) == pytest.approx(4.0)
    assert chip_spans.union_s([]) == 0.0


@pytest.mark.tier1
def test_set_up_and_window_readings_on_a_recorded_trace(tmp_path):
    """The sort cell's entry at 2^8 on the CPU: set-up reports the
    plan spans of its first call and its descriptors; a window traced
    by the benchmark's own loop, telemetry on, yields every per-call
    span of the call path."""
    c = harness.cell("sort-fwd-20")
    cfg, mix = dict(c["cfg"], n=8), dict(c["mix"], inputs=1)
    calls, sharding = harness.load_module("entries", mix["entry"]).build(
        cfg, mix, jax.devices()[:1])
    xs = harness.make_inputs(cfg, mix, 2**40 + 3, sharding)
    clog = harness.CompileLog()
    jax.monitoring.register_event_time_span_listener(clog)
    try:
        setup = chip_spans.warm_up(calls, xs[0], clog)
    finally:
        jax.monitoring.unregister_event_time_span_listener(clog)
    assert {"plan.lower", "plan.cluster", "plan.bwd"} <= set(setup["plan_kinds"])
    assert 0 < setup["plan_s"] <= 1.000001 * sum(
        k["s"] for k in setup["plan_kinds"].values())
    assert setup["dma_descriptors_per_call"] > 0
    assert not obs.enabled() and obs.events() == []

    win = harness.Window(calls, xs, mix, 1)
    obs.enable(sync=False)
    try:
        win.run(0.0, tmp_path, 0.0, 3)
    finally:
        obs.disable()
        obs.reset()
    path = str(next(tmp_path.rglob("*.xplane.pb")))
    lo, hi = win.traced
    out = chip_spans.readings(xplane.extract(path), chip_spans.host_events(path),
                              win.enqueue_s[lo:hi])
    assert out["calls"] == hi - lo >= 3
    for name in ("repro.entry.resolve", "repro.entry.route", "repro.entry.apply",
                 "repro.program.lookup", "repro.program.enqueue"):
        assert out["spans_us_per_call"][name] > 0, name
    assert 0 < out["host_lookup_us"] + out["host_dispatch_us"] < out["host_call_us"]
