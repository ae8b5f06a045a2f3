"""Telemetry subsystem tests (DESIGN.md §12).

Pins the contracts of the :mod:`repro.obs` layer:

* spans nest (parent/depth recorded) and survive a Chrome-trace export
  round trip as valid ``ph: "X"`` events;
* the dispatch counters the executor records while tracing a sort
  program EXACTLY equal the transaction model's
  ``cost(..., clustered=True)["kernels"]`` counts — the model-honesty
  acceptance bar, here at 2^8;
* disabled telemetry records nothing (counters, histograms, spans all
  empty after an instrumented program runs);
* counter deltas are independent of the batch size (trace-time
  recording: the per-class counts describe the program, not the data),
  and warm same-shape calls add no dispatch counts at all;
* ``cache_stats()`` covers every executor/ops cache and
  ``clear_caches()`` resets the telemetry with them;
* enabled spans reach the profiler's host plane as ``repro.<name>``
  annotations, nested in the caller's own; disabled spans build no
  annotation; the ``plan.*`` spans fire on plan-cache misses only.
"""
import json
import random
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.combinators import cache_stats, clear_caches, compile_expr
from repro.combinators import vocab as V
from repro.combinators.sort import sort_expr
from repro.core.bmmc import Bmmc
from repro.kernels.ops import choose_tile


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts disabled with empty buffers and leaves no
    telemetry state behind for the rest of the suite."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


@pytest.fixture(autouse=True, scope="module")
def _bounded_caches():
    yield
    clear_caches()


def _payload(shape, seed):
    vals = np.random.default_rng(seed).normal(size=shape)
    return jnp.asarray(vals.astype(np.float32))


# ---------------------------------------------------------------------------
# Span nesting + Chrome-trace export round trip
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_span_nesting_and_export_roundtrip(tmp_path):
    obs.enable(sync=False)
    with obs.span("outer", cat="test", n=8) as oargs:
        oargs["discovered"] = "late-fact"
        with obs.span("inner", cat="test"):
            pass
    evs = obs.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # exit order
    inner, outer = evs
    assert inner["args"]["parent"] == "outer"
    assert inner["args"]["depth"] == 1
    assert "parent" not in outer["args"]
    assert outer["args"]["n"] == 8
    assert outer["args"]["discovered"] == "late-fact"
    for ev in evs:
        assert ev["ph"] == "X" and ev["dur"] >= 0

    path = tmp_path / "roundtrip.trace.json"
    obs.export_trace(str(path))
    with open(path) as f:
        loaded = json.load(f)
    assert loaded["traceEvents"] == evs
    assert loaded["displayTimeUnit"] == "ms"
    assert loaded["otherData"]["dropped"] == 0


@pytest.mark.tier1
def test_span_is_noop_when_disabled():
    with obs.span("ghost") as args:
        assert args is None
    assert obs.events() == []


# ---------------------------------------------------------------------------
# Counter honesty: recorded dispatches == transaction-model counts
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_sort_counters_match_program_cost():
    """The acceptance bar: execute the 2^8 sort once with telemetry on;
    the per-kernel dispatch counters must equal the clustered model's
    kernel-class counts exactly — same vocabulary, same values."""
    clear_caches()
    n = 8
    t = choose_tile(n, 4, 1)
    f = compile_expr(sort_expr(n), engine="pallas")
    want = {k: v for k, v in
            f.cost(n, t, clustered=True)["kernels"].items() if v}
    obs.enable(sync=True)
    jax.block_until_ready(f(_payload((1 << n,), 0)))
    got = {k: v for k, v in obs.kernel_counts().items() if v}
    assert got == want, (got, want)
    # the modeled round trips accumulate alongside
    assert obs.counter_total("model.round_trips") > 0
    mm = obs.model_vs_measured()
    assert mm["program_calls"] == 1
    assert mm["modeled_round_trips"] > 0
    assert mm["measured_wall_us"] > 0


@pytest.mark.tier1
def test_box_side_counter_matches_plans():
    """A cold sort call counts ``dma.box_sides`` per side: exactly the
    tiled-pass sides of its plans whose rows form a box."""
    from repro.combinators import execute as ex
    from repro.combinators.ir import Perm
    from repro.combinators.optimize import FusedStage
    from repro.kernels.ops import class_plan
    clear_caches()
    n = 8
    t = choose_tile(n, 4, 1)
    f = compile_expr(sort_expr(n), engine="pallas")
    plans = []
    for s in f.clustered_program(n, t):
        if isinstance(s, FusedStage):
            plans += ex._fused_plan_cached(s, t)[0]
        elif isinstance(s, Perm):
            kernel, payload = class_plan(s.bmmc, t)
            if kernel in ("tiled", "general", "general2"):
                plans += payload
    want = {"in": sum(p.in_box is not None for p in plans),
            "out": sum(p.out_box is not None for p in plans)}
    assert want["in"] and want["out"]
    obs.enable(sync=True)
    jax.block_until_ready(f(_payload((1 << n,), 3)))
    got = {side: obs.counter_value("dma.box_sides", side=side)
           for side in want}
    assert got == want


def _stacked_case(kind: str) -> tuple:
    """(rows, complements) of one stacked-table pass: a random BPC or
    a random BMMC at 2^10, or the local round of a sharded 2^11 plan
    whose shards' complements give the tiled passes different runs."""
    from repro.core import distributed as D, f2
    from repro.kernels import ops
    from repro.kernels.bmmc_permute import plan_geometry
    rng = random.Random(5)
    cs = (0, 0b11 << 7, 0b101, (0b11 << 7) | 0b101)
    if kind != "shard-round":
        draw = Bmmc.random_bpc if kind == "bpc" else Bmmc.random
        return draw(10, rng).rows, cs
    t = choose_tile(9, 4, 1)
    for _ in range(100):
        for r in D.make_plan(Bmmc.random(11, rng), 2):
            if not (isinstance(r, D.LocalRound) and any(r.ls_rows)):
                continue
            cs = tuple(r.c ^ f2.matvec(r.ls_rows, g) for g in range(4))
            got = [ops.class_plan(Bmmc(r.rows, c), t) for c in cs]
            if got[0][0] not in ("block", "lane") and len(
                    {tuple(plan_geometry(q)[:7] for q in pay)
                     for _, pay in got}) > 1:
                return r.rows, cs
    raise AssertionError("no local round with per-shard geometries")


@pytest.mark.tier1
@pytest.mark.parametrize("kind", ["bpc", "bmmc", "shard-round"])
def test_stacked_descriptor_counter_matches_kernel(monkeypatch, kind):
    """A pass whose tables are stacked per complement (a sharded local
    round) counts ``dma.descriptors`` and ``dma.box_sides`` as the
    kernels it launches issue them: per tile, each side's ``_Side``
    count under the geometry the kernel is given."""
    from repro.kernels import bmmc_permute as bp
    from repro.kernels import ops
    rows, cs = _stacked_case(kind)
    launched = []
    real = ops.tiled_permute_tables

    def spy(x, *tables, geometry, **kw):
        launched.append(geometry)
        return real(x, *tables, geometry=geometry, **kw)

    monkeypatch.setattr(ops, "tiled_permute_tables", spy)
    obs.enable(sync=True)
    fn = jax.jit(lambda v, i: ops.bmmc_permute_complements(v, rows, cs, i))
    jax.block_until_ready(fn(_payload((1 << len(rows),), 4), jnp.int32(1)))
    assert launched
    issued = sum(g[5] * sum(bp._Side(layout, g[2], 1 << (g[0] - g[1]),
                                     None).count for layout in g[3:5])
                 for g in launched)
    assert obs.counter_total("dma.descriptors") == issued
    boxes = {side: sum(isinstance(g[k], tuple) for g in launched)
             for k, side in ((3, "in"), (4, "out"))}
    assert {side: obs.counter_value("dma.box_sides", side=side)
            for side in boxes} == boxes
    assert obs.counter_total("model.round_trips") == len(launched)


@pytest.mark.tier1
def test_report_renders_after_execution():
    clear_caches()
    n = 7
    f = compile_expr(sort_expr(n), engine="pallas")
    obs.enable(sync=True)
    jax.block_until_ready(f(_payload((1 << n,), 1)))
    text = obs.report()
    assert "kernel dispatches" in text
    assert "model vs measured" in text
    assert "caches" in text
    snap = obs.snapshot()
    assert snap["kernel_counts"] == obs.kernel_counts()
    assert snap["trace_events"] == len(obs.events())
    json.dumps(snap)  # must be JSON-serializable (embedded in --json)


# ---------------------------------------------------------------------------
# Disabled mode is a strict no-op
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_disabled_mode_records_nothing():
    clear_caches()
    n = 7
    f = compile_expr(sort_expr(n), engine="pallas")
    assert not obs.enabled()
    jax.block_until_ready(f(_payload((1 << n,), 2)))
    assert obs.counters() == {}
    assert obs.histograms() == {}
    assert obs.events() == []
    assert obs.kernel_counts() == {}
    # inc/observe are guarded too, not just the executor sites
    obs.inc("dispatch.kernel", kernel="tiled")
    obs.observe("program.call_us", 1.0)
    assert obs.counters() == {} and obs.histograms() == {}


# ---------------------------------------------------------------------------
# Batch-size independence of trace-time counters
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_counter_deltas_independent_of_batch_size():
    """Counters record at trace time, so the dispatch counts describe
    the PROGRAM: re-tracing the same program for a different batch size
    yields the identical delta, and warm same-shape calls add nothing."""
    clear_caches()
    n = 8
    e = V.bit_reverse(n) >> V.perm(Bmmc.random(n, random.Random(3)))
    f = compile_expr(e, engine="pallas")
    obs.enable(sync=True)

    def delta(bsz, seed):
        before = obs.kernel_counts()
        jax.block_until_ready(
            f(_payload((bsz, 1 << n), seed), batched=True))
        after = obs.kernel_counts()
        return {k: v - before.get(k, 0) for k, v in after.items()
                if v - before.get(k, 0)}

    d2 = delta(2, 10)       # cold: executable traced here
    d4 = delta(4, 11)       # new shape: jit re-specializes, re-traces
    assert d2 == d4 and d2, (d2, d4)
    assert delta(4, 12) == {}   # warm same-shape call: no re-trace


# ---------------------------------------------------------------------------
# Cache hygiene: aggregate stats + telemetry reset
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_cache_stats_covers_every_executor_cache():
    stats = cache_stats()
    assert {"geom", "block", "lane", "program", "fused_plan", "w_planar",
            "lowered", "clustered", "plans", "class_plan",
            "compiled_exprs"} <= set(stats)
    for name, info in stats.items():
        assert info.hits >= 0 and info.misses >= 0, name
        assert info.currsize >= 0, name
    # obs.cache_stats() is the same data as plain dicts
    assert obs.cache_stats()["program"]["currsize"] == \
        stats["program"].currsize


@pytest.mark.tier1
def test_clear_caches_resets_telemetry_too():
    clear_caches()
    n = 7
    f = compile_expr(sort_expr(n), engine="pallas")
    obs.enable(sync=True)
    jax.block_until_ready(f(_payload((1 << n,), 3)))
    assert obs.counters() and obs.events()
    assert cache_stats()["program"].currsize > 0
    clear_caches()
    assert obs.counters() == {} and obs.events() == []
    assert obs.histograms() == {}
    for name in ("geom", "block", "lane", "program", "fused_plan",
                 "clustered", "class_plan"):
        assert cache_stats()[name].currsize == 0, name
    assert obs.enabled()    # reset drops data, not the enabled flag


# ---------------------------------------------------------------------------
# The profiler sink: spans on the profiler's clock
# ---------------------------------------------------------------------------

_CALL_SPANS = ("repro.entry.resolve", "repro.entry.route",
               "repro.entry.apply", "repro.program.lookup",
               "repro.program.enqueue")


@pytest.mark.tier1
def test_call_spans_reach_the_profiler_host_plane(tmp_path):
    """A warm 2^8 sort call inside a caller's annotation, under the
    profiler: each per-call span is on the host plane, inside the
    caller's span."""
    from jax.profiler import ProfileData
    n = 8
    f = compile_expr(sort_expr(n), engine="pallas")
    x = _payload((1 << n,), 4)
    jax.block_until_ready(f(x))
    obs.enable(sync=False)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("caller.call"):
            y = f(x)
        jax.block_until_ready(y)
    obs.disable()
    path = next(Path(tmp_path).rglob("*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    found.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    (lo, hi), = found["caller.call"]
    for name in _CALL_SPANS:
        assert len(found.get(name, ())) == 1, (name, sorted(found))
        (a, b), = found[name]
        assert lo <= a <= b <= hi, name
    # the executable's lookup and call lie inside the routed dispatch
    (lo, hi), = found["repro.entry.apply"]
    for name in ("repro.program.lookup", "repro.program.enqueue"):
        (a, b), = found[name]
        assert lo <= a <= b <= hi, name
    # the in-memory buffer still records the same spans
    recorded = {"repro." + e["name"] for e in obs.events()}
    assert set(_CALL_SPANS) <= recorded


@pytest.mark.tier1
def test_disabled_span_builds_no_annotation(monkeypatch):
    """Disabled, a span site is one attribute check: the annotation
    class is never constructed and nothing is recorded."""
    n = 8
    f = compile_expr(sort_expr(n), engine="pallas")
    x = _payload((1 << n,), 5)
    jax.block_until_ready(f(x))

    def boom(*args, **kwargs):
        raise AssertionError("TraceAnnotation built while disabled")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    assert not obs.enabled()
    with obs.span("ghost"):
        pass
    jax.block_until_ready(f(x))
    assert obs.events() == [] and obs.counters() == {}


@pytest.mark.tier1
def test_plan_spans_fire_on_cache_misses_only():
    """After ``clear_caches()`` the first call plans (lowering,
    clustering, the backward plan); a second same-shape call hits every
    plan cache and records no ``plan.*`` span."""
    clear_caches()
    n = 8
    f = compile_expr(sort_expr(n), engine="pallas")
    obs.enable(sync=False)

    def plan_spans(seed):
        obs.reset()
        jax.block_until_ready(f(_payload((1 << n,), seed)))
        return {e["name"] for e in obs.events()
                if e["name"].startswith("plan.")}

    first = plan_spans(6)
    assert {"plan.lower", "plan.cluster", "plan.bwd"} <= first, first
    assert plan_spans(7) == set()
