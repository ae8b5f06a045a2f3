#!/usr/bin/env python3
"""Benchmark of the BMMC permutation library on a TPU: one run of one
cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload suite-tiled-24 --seed 7 --seconds 20 --trace 0

Run from the root of a checkout, on a machine that holds the chips the
cell asks for. Without a TPU, or with fewer chips, it exits non-zero
and prints no result. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and
last ``checks``: each number compared with the reference beside its
limit. The same checks are the last lines of standard error.

JAX's persistent compilation cache is kept in ``$JAX_COMPILATION_CACHE_DIR``
where that is set, and else in ``<checkout>/.bench_cache/jax``, so only
the first run in a checkout compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` where set, else a fixed directory
    in the checkout (the path is part of the cache's key)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(ROOT / ".bench_cache" / "jax"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-out", default=None,
                    help="with --trace 1: also write the reduced trace and "
                         "an inventory of the profiler's planes here")
    args = ap.parse_args(argv)

    cache = os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir()
    # the TPU runtime logs to a fixed path under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import harness
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START,
                               trace_out=args.trace_out)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    for name, check in out["checks"].items():
        print(f"check {name} = {check['value']!r} (limit {check['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
