"""Reduction of a profiler trace to what the per-layer metrics read.

``extract`` turns a ``.xplane.pb`` into a small JSON-able form: the
harness's own ``bench.*`` host annotations, and for each device its ops
(name, start ns, duration ns, kind) from the "XLA Ops" line, moved onto
the host's clock (the TPU's clock is offset from it by a millisecond or
two). Every metric reads that form, so ``check_trace.py`` can recompute
them on the CPU from a recorded trace in ``testdata/``.

An op is named by its HLO name without the instance number
(``bmmc_tile``, ``pad_maximum_fusion``, ``all-to-all``). Its kind is
``pallas`` for the library's Pallas kernels, ``collective`` for an
all-to-all, a collective permute or another collective, and ``op``
otherwise.
"""
from __future__ import annotations

import re

KERNELS = ("bmmc_tile_bwd", "bmmc_tile", "bmmc_block", "bmmc_lane", "bmmc_copy")
_COLLECTIVE = re.compile(
    r"^(all-to-all|collective-permute|all-gather|all-reduce|reduce-scatter)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def op_name(text: str) -> str:
    """An op's name without its instance number: the trace names an op
    by its HLO text, ``%bmmc_tile.31 = f32[...] custom-call(...)``."""
    return re.sub(r"\.\d+$", "", text.split(" = ", 1)[0].lstrip("%"))


def kind(name: str) -> str:
    if name in KERNELS:
        return "pallas"
    if _COLLECTIVE.match(name):
        return "collective"
    return "op"


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            yield from line.events


def _shift_ns(modules, calls) -> int:
    """What to add to the device's times to put them on the host's
    clock: the median gap between the end of a call's last program on
    the device and the call's end on the host, pairing them in order
    when each call ran the same number of programs; 0 otherwise."""
    if not modules or not calls or len(modules) % len(calls):
        return 0
    per = len(modules) // len(calls)
    ends = sorted(s + d for s, d in modules)[per - 1::per]
    gaps = sorted(c[1] + c[2] - e for c, e in zip(calls, ends))
    return gaps[len(gaps) // 2]


def extract(path: str) -> dict:
    """The reduced form of the trace at ``path``: the harness's host
    spans, and each device's ops moved onto the host's clock."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    host = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    host.sort(key=lambda h: h[1])
    devices = []
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        ops = [(op_name(ev.name), int(ev.start_ns), int(ev.duration_ns))
               for ev in _events(plane, OPS_LINE)]
        if not ops:
            continue
        modules = [(int(ev.start_ns), int(ev.duration_ns))
                   for ev in _events(plane, MODULES_LINE)]
        shift = _shift_ns(modules, calls({"host": host}))
        devices.append({"name": plane.name, "shift_ns": shift, "ops": sorted(
            [name, start + shift, dur, kind(name)] for name, start, dur in ops)})
    devices.sort(key=lambda d: d["name"])
    return {"devices": devices, "host": host}


def inventory(path: str, per_line: int = 12) -> dict:
    """Planes, lines, event counts and a few named events with their
    stats: what to look at before trusting ``extract``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            names = {}
            for ev in events:
                if ev.name not in names and len(names) < per_line:
                    names[ev.name] = {k: (v if not isinstance(v, str)
                                          else v[:300]) for k, v in ev.stats}
            lines.append({"line": line.name, "events": len(events),
                          "first": names})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}


def trim(trace: dict, calls_kept: int) -> dict:
    """The first ``calls_kept`` host calls of a trace and the device ops
    that start inside them: a small trace for ``testdata/``."""
    spans = calls(trace)[:calls_kept]
    lo, hi = spans[0][1], spans[-1][1] + spans[-1][2]
    return {
        "devices": [{**d, "ops": [o for o in d["ops"] if lo <= o[1] < hi]}
                    for d in trace["devices"]],
        "host": [h for h in trace["host"] if lo <= h[1] < hi],
    }


# -- what the metrics read --------------------------------------------------
# A trace holds the traced calls and nothing else: the profiler starts
# after one call has completed and stops after another has, so every
# device op in it belongs to a traced call.

def calls(trace: dict) -> list:
    return [h for h in trace["host"] if h[0] == "bench.call"]


def window(trace: dict) -> tuple:
    """(start, end) in ns: from the first traced call to the end of the last."""
    spans = calls(trace)
    return spans[0][1], max(h[1] + h[2] for h in spans)


def intervals(ops, lo: float = float("-inf"), hi: float = float("inf")) -> list:
    """The union of the ops' intervals, clipped to [lo, hi], as sorted
    disjoint [start, end] pairs."""
    out = []
    for _, start, dur, _ in sorted(ops, key=lambda o: o[1]):
        a, b = max(start, lo), min(start + dur, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(ops) -> int:
    """Time in which at least one op ran."""
    return sum(b - a for a, b in intervals(ops))


def host_label(host, t: int) -> str:
    """The innermost harness span holding time ``t``, or ``harness``
    between spans."""
    best = None
    for name, start, dur in host:
        if start <= t < start + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else "harness"


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device ops that took most time (seconds per chip, by op name)
    and the longest idle gaps within the traced window, each named by
    what the host was doing in its middle."""
    lo, hi = window(trace)
    ndev = len(trace["devices"])
    per_name = {}
    gaps = []
    for k, dev in enumerate(trace["devices"]):
        for name, _, dur, _ in dev["ops"]:
            per_name[name] = per_name.get(name, 0) + dur
        prev = lo
        for a, b in intervals(dev["ops"], lo, hi) + [[hi, hi]]:
            if a > prev:
                gaps.append((a - prev, prev, k))
            prev = max(prev, b)
    named = []
    for dur, start, k in sorted(gaps, reverse=True)[:top]:
        label = host_label(trace["host"], start + dur // 2)
        named.append([label if ndev == 1 else f"chip{k}:{label}", dur / 1e9])
    ops = sorted(per_name.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[name, ns / 1e9 / ndev] for name, ns in ops],
            "idle_gaps": named}
