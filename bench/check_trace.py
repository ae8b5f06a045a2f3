#!/usr/bin/env python3
"""Recompute every per-layer metric from the recorded traces in
``testdata/`` and compare them with the values recorded there. Runs on
the CPU:

    python3 bench/check_trace.py

A recorded trace is the reduced form (``xplane.extract``) of a traced
chip run, trimmed to its first calls:

    python3 bench/check_trace.py --record chiprun_out/<dir>/run.json --calls 12

writes ``testdata/<workload>.json`` with the metrics the readers give
on the trimmed trace.
"""
import argparse
import json
import math
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import xplane  # noqa: E402


def readings(rec: dict, names=None) -> dict:
    """The per-layer metrics ``names`` (by default the cell's) of the
    recorded cell, from the record."""
    c = harness.cell(rec["workload"])
    run = {"trace": rec["trace"], "cfg": c["cfg"], "mix": c["mix"],
           "peak": rec["peak"], "setup": rec["setup"], "enqueue_s": rec["enqueue_s"]}
    out = {}
    for name in names or [m["name"] for m in c["per_layer"]]:
        value = harness.load_module("metrics", name).read(run)
        if value is not None:
            out[name] = value
    out["breakdown"] = xplane.breakdown(rec["trace"])
    return out


def record(path: str, calls: int) -> Path:
    run = json.loads(Path(path).read_text())
    trace = xplane.trim(run["trace"], calls)
    kept = [h[1] for h in xplane.calls(trace)]
    rec = {"workload": run["workload"], "source": run["source"],
           "peak": run["peak"], "setup": run["setup"],
           "enqueue_s": run["enqueue_s"][:len(kept)], "trace": trace}
    rec["recorded"] = readings(rec)
    out = BENCH / "testdata" / f"{run['workload']}.json"
    out.write_text(json.dumps(rec) + "\n")
    return out


def same(a, b) -> bool:
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(same(x, y) for x, y in zip(a, b))
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(same(a[k], b[k]) for k in a)
    if isinstance(a, float):
        return math.isclose(a, b, rel_tol=1e-9, abs_tol=1e-12)
    return a == b


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--record", help="a traced run's run.json to trim and record")
    ap.add_argument("--calls", type=int, default=12)
    args = ap.parse_args(argv)
    if args.record:
        print(f"wrote {record(args.record, args.calls)}")
        return 0
    bad = 0
    files = sorted((BENCH / "testdata").glob("*.json"))
    for path in files:
        rec = json.loads(path.read_text())
        got = readings(rec, [k for k in rec["recorded"] if k != "breakdown"])
        for name, want in rec["recorded"].items():
            ok = name in got and same(got[name], want)
            bad += not ok
            shown = want if name != "breakdown" else "(top ops and gaps)"
            print(f"{path.name}: {name} {'ok' if ok else 'DIFFERS'} {shown}")
        for name in set(got) - set(rec["recorded"]):
            bad += 1
            print(f"{path.name}: {name} read now but not recorded")
    if not files:
        print("no recorded traces in testdata/", file=sys.stderr)
        return 1
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
