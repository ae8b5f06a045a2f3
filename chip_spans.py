#!/usr/bin/env python3
"""Where one benchmark cell's host time goes, read from the library's
own ``repro.obs`` spans on the chip.

    python3 chip_spans.py --workload sort-fwd-20 --seed 7

Builds the cell with the benchmark's own code (``bench/harness.py``:
configuration, traffic mix, entry point, inputs made on the device from
the seed), then:

1. set-up: the first and second call of each program with the
   library's telemetry on. It reports the union of the ``plan.*``
   spans (``plan_s``: planning paid on plan-cache misses), each kind's
   count and time (``plan.dist`` for a sharded cell), the first calls'
   time beyond the second less the compiles in them (as the
   benchmark's ``trace_plan_s``), and the ``dma.descriptors`` and
   ``dma.box_sides{side}`` counters over the number of programs; for a
   sharded cell also the ``dist.bytes{kind}`` counter over the number
   of programs, beside the least bytes a call must move between chips
   (the ``collective_roofline`` reader's count);
2. window: the benchmark's own closed loop and traced sub-window
   (``harness.Window``), with telemetry on all through it. Over the
   traced calls it reports ``host_call_us`` (as the benchmark's reader
   does), the mean per call of every ``repro.*`` span (``dist.call``
   for a sharded cell) and of JAX's
   outermost ``PjitFunction(<fn>)`` dispatch events, and the longest
   idle gaps of the device, each named by the innermost ``bench.*`` or
   ``repro.*`` span in its middle.

Compare ``host_call_us`` with a ``bench/run.py --trace 1`` run of the
same cell, where telemetry stays off, for what the spans cost. The
last line of standard output is one JSON object. Without a TPU it exits
2 and prints no result. It goes when the benchmark reads the spans
itself.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
LOOKUPS = ("repro.entry.resolve", "repro.entry.route", "repro.program.lookup")
ENQUEUE = "repro.program.enqueue"


def host_events(path: str) -> list:
    """[name, start ns, duration ns] of every ``repro.*`` and
    ``PjitFunction(`` event on the host plane of the trace at ``path``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("repro.", "PjitFunction(")):
                        out.append([ev.name, int(ev.start_ns), int(ev.duration_ns)])
    return sorted(out, key=lambda h: (h[1], -h[2]))


def union_s(events) -> float:
    """Seconds covered by the union of ``repro.obs`` events (µs)."""
    total, reach = 0.0, float("-inf")
    for a, b in sorted((e["ts"], e["ts"] + e["dur"]) for e in events):
        a = max(a, reach)
        if b > a:
            total, reach = total + b - a, b
    return total / 1e6


def warm_up(calls, x, clog) -> dict:
    """First and second call of each program with telemetry on."""
    import jax
    from repro import obs
    obs.reset()
    obs.enable(sync=False)
    first = second = 0.0
    wall0 = time.time()
    try:
        for _, fn in calls:
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x))
            t1 = time.perf_counter()
            jax.block_until_ready(fn(x))
            first += t1 - t0
            second += time.perf_counter() - t1
        plans = [e for e in obs.events() if e["name"].startswith("plan.")]
        descriptors = obs.counter_total("dma.descriptors")
        box_sides = {side: obs.counter_value("dma.box_sides", side=side)
                     for side in ("in", "out")}
        dist_bytes = {kind: obs.counter_value("dist.bytes", kind=kind)
                      for kind in ("all_to_all", "ppermute")}
    finally:
        obs.disable()
        obs.reset()
    compile_s = clog.compile_s(wall0, time.time())
    kinds = {}
    for e in plans:
        count, total = kinds.get(e["name"], (0, 0.0))
        kinds[e["name"]] = (count + 1, total + e["dur"] / 1e6)
    out = {"trace_plan_s": first - second - compile_s, "plan_s": union_s(plans),
           "plan_kinds": {k: {"count": c, "s": s} for k, (c, s) in sorted(kinds.items())},
           "dma_descriptors_per_call": descriptors / len(calls),
           "dma_box_sides_per_call": {k: v / len(calls)
                                      for k, v in box_sides.items()}}
    if any(dist_bytes.values()):
        out["dist_bytes_per_call"] = {k: v / len(calls)
                                      for k, v in dist_bytes.items()}
    return out


def readings(trace: dict, extra: list, enqueue_s: list) -> dict:
    """The window's readings from its reduced trace (``xplane.extract``),
    the ``host_events`` of the same trace, and the host times of the
    traced calls up to their return."""
    import xplane
    calls = xplane.calls(trace)
    totals = {}
    for _, lo, dur in calls:
        inside = [e for e in extra if lo <= e[1] < lo + dur]
        reach = lo
        for name, start, d in inside:
            if name.startswith("repro."):
                totals[name] = totals.get(name, 0) + d
            elif start >= reach:    # an outermost JAX dispatch
                totals[name] = totals.get(name, 0) + d
                reach = start + d
    per = {k: v / 1e3 / len(calls) for k, v in sorted(totals.items())}
    named = {**trace, "host": sorted(
        trace["host"] + [e for e in extra if e[0].startswith("repro.")],
        key=lambda h: h[1])}
    return {"calls": len(calls),
            "host_call_us": 1e6 * statistics.fmean(enqueue_s),
            "spans_us_per_call": per,
            "host_lookup_us": sum(per.get(k, 0.0) for k in LOOKUPS),
            "host_dispatch_us": per.get(ENQUEUE, sum(
                v for k, v in per.items() if k.startswith("PjitFunction("))),
            "idle_gaps": xplane.breakdown(named)["idle_gaps"] if trace["devices"] else []}


def probe(workload: str, seed: int, seconds: float) -> dict:
    import jax
    import harness
    import xplane
    from repro import obs
    c = harness.cell(workload)
    cfg, mix = dict(c["cfg"]), dict(c["mix"])
    chips = c["workload"]["chips"]
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise harness.NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                             f"{len(devices)} {devices[0].platform} device(s)")
    clog = harness.CompileLog()
    jax.monitoring.register_event_time_span_listener(clog)
    try:
        calls, sharding = harness.load_module("entries", mix["entry"]).build(
            cfg, mix, devices[:chips])
        xs = harness.make_inputs(cfg, mix, seed, sharding)
        setup = warm_up(calls, xs[0], clog)
        if "dist_bytes_per_call" in setup:
            setup["least_dist_bytes_per_call"] = harness.load_module(
                "metrics", "collective_roofline").least_bytes_per_call(cfg, mix)
    finally:
        jax.monitoring.unregister_event_time_span_listener(clog)
    win = harness.Window(calls, xs, mix, seed)
    gc.collect()
    gc.freeze()
    with tempfile.TemporaryDirectory(prefix="chip_spans_") as d:
        obs.enable(sync=False)
        try:
            win.run(seconds, Path(d), mix["trace_seconds"], mix["trace_min_calls"])
        finally:
            obs.disable()
            obs.reset()
            gc.unfreeze()
        path = str(next(Path(d).rglob("*.xplane.pb")))
        lo, hi = win.traced
        window = readings(xplane.extract(path), host_events(path), win.enqueue_s[lo:hi])
    return {"workload": workload, "seed": seed, "setup": setup, "window": window,
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind, "count": len(devices)}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cache = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                  str(ROOT / ".bench_cache" / "jax"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import harness
    try:
        out = probe(args.workload, args.seed, args.seconds)
    except harness.NoChip as e:
        print(f"chip_spans: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
