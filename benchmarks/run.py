"""Benchmark harness: one module per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV rows. Run with
``PYTHONPATH=src python -m benchmarks.run [--only fig9,...]``.
``--json OUT.json`` additionally writes the rows (plus run metadata) as
machine-readable JSON — the format the ``BENCH_*.json`` perf-trajectory
files at the repo root record. Rows named ``*/model_error`` (modeled vs
measured ratio per workload) are additionally lifted into a structured
``model_error`` section of the payload — the input to check_bench's
model-honesty gate. ``--trace OUT.json`` enables :mod:`repro.obs` for
the whole run and writes the Chrome trace + telemetry snapshot.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: "
                         "fig9,fig10,transpose,sort,khc,roofline,"
                         "combinators,autodiff,stagefusion,classdispatch,"
                         "guard,store,resilience")
    ap.add_argument("--smoke", action="store_true",
                    help="fast sanity subset (combinators + autodiff + "
                         "stagefusion + classdispatch + guard + store + "
                         "resilience; pairs with `pytest -m tier1` as the "
                         "quick tier-1 smoke entry point)")
    ap.add_argument("--json", default=None, metavar="OUT.json",
                    help="also write rows + metadata as JSON")
    ap.add_argument("--trace", default=None, metavar="TRACE.json",
                    help="enable repro.obs telemetry for the whole run and "
                         "write the Chrome trace (chrome://tracing) here; "
                         "--json payloads gain a telemetry snapshot")
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    if args.trace:
        from repro import obs
        obs.enable(sync=True)
    if args.smoke and args.only:
        ap.error("--smoke and --only are mutually exclusive")
    want = set(args.only.split(",")) if args.only else None
    if args.smoke:
        want = {"combinators", "autodiff", "stagefusion", "classdispatch",
                "guard", "store", "resilience"}

    print("name,us_per_call,derived")
    suites = []
    if want is None or "fig9" in want:
        from . import paper_fig9
        suites.append(paper_fig9.rows)
    if want is None or "fig10" in want:
        from . import paper_fig10
        suites.append(paper_fig10.rows)
    if want is None or "transpose" in want:
        from . import transpose_table
        suites.append(transpose_table.rows)
    if want is None or "sort" in want:
        from . import sort_stages
        suites.append(sort_stages.rows)
    if want is None or "khc" in want:
        from . import kernel_hillclimb
        suites.append(kernel_hillclimb.rows)
    if want is None or "roofline" in want:
        from . import roofline
        suites.append(roofline.bench_roofline)
    if want is None or "combinators" in want:
        from . import combinator_fusion
        suites.append(combinator_fusion.rows)
    if want is None or "autodiff" in want:
        from . import autodiff_overhead
        suites.append(autodiff_overhead.rows)
    if want is None or "stagefusion" in want:
        from . import stage_fusion
        suites.append(stage_fusion.rows)
    if want is None or "classdispatch" in want:
        from . import class_dispatch
        suites.append(class_dispatch.rows)
    if want is None or "guard" in want:
        from . import guard_overhead
        suites.append(guard_overhead.rows)
    if want is None or "store" in want:
        from . import store_warmstart
        suites.append(store_warmstart.rows)
    if want is None or "resilience" in want:
        from . import resilience_soak
        suites.append(resilience_soak.rows)
    collected = []
    for rows_fn in suites:
        for name, us, derived in rows_fn():
            # model-only rows (offline transaction counts, telemetry
            # gates) carry no wall-clock measurement: us is None, the
            # CSV cell is empty and the JSON field is null so readers
            # and check_bench can't mistake them for measured 0.00 µs
            us_cell = "" if us is None else f"{us:.2f}"
            print(f"{name},{us_cell},{derived}")
            collected.append(
                {"name": name,
                 "us": None if us is None else round(float(us), 2),
                 "derived": str(derived)})
    if args.trace:
        from repro import obs
        obs.export_trace(args.trace)
        print(f"# wrote {len(obs.events())} trace events to {args.trace}",
              file=sys.stderr)
    if args.json:
        import jax
        import numpy as np
        payload = {
            "metadata": {
                "argv": sys.argv[1:],
                "suites": sorted(want) if want is not None else "all",
                "python": platform.python_version(),
                "jax": jax.__version__,
                "numpy": np.__version__,
                "platform": platform.platform(),
                "backend": jax.default_backend(),
            },
            "rows": collected,
            # modeled-vs-measured accounting per workload: the input to
            # check_bench's model-honesty gate
            "model_error": _model_error_section(collected),
            # durable-store warm-start + fault-coverage accounting: the
            # input to check_bench's store gates
            "store": _store_section(collected),
        }
        if args.trace:
            from repro import obs
            payload["telemetry"] = obs.snapshot()
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
        print(f"# wrote {len(collected)} rows to {args.json}",
              file=sys.stderr)


def _store_section(rows: list) -> list:
    """Lift ``store/*`` gate rows (``/warmstart``, ``/fault_injection``)
    into structured records."""
    out = []
    for row in rows:
        if not row["name"].startswith("store/"):
            continue
        if not row["name"].endswith(("/warmstart", "/fault_injection")):
            continue
        rec = {"row": row["name"]}
        for part in row["derived"].split(";"):
            k, _, v = part.partition("=")
            try:
                rec[k] = float(v)
            except ValueError:
                rec[k] = v
        out.append(rec)
    return out


def _model_error_section(rows: list) -> list:
    """Lift ``*/model_error`` rows into structured records."""
    out = []
    for row in rows:
        if not row["name"].endswith("/model_error"):
            continue
        rec = {"workload": row["name"].rsplit("/", 1)[0]}
        for part in row["derived"].split(";"):
            k, _, v = part.partition("=")
            try:
                rec[k] = float(v)
            except ValueError:
                rec[k] = v
        out.append(rec)
    return out


if __name__ == "__main__":
    main()
