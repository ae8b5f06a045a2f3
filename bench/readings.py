#!/usr/bin/env python3
"""The readings the comparison's limits are set from, at a cell's own
size, on the chip, in one process:

    python3 bench/readings.py --workload sort-fwd-20 --seeds 1-12 --seconds 2

For each seed it makes the seeded inputs, runs a short closed-loop window
through the cell's own library calls, keeps the sampled outputs as a run
does, and prints one JSON line with the worst number of each kind for
the program (the lower reading) and for the control: the reference
computed one precision below the configuration's (bfloat16 for float32,
int16 for int32), put in the program's place (the upper reading). The
benchmark's own runs never run the control.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def seeds(spec: str) -> list:
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 1-12 or 3,5,9")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".bench_cache" / "jax"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import numpy as np
    import jax
    import harness

    c = harness.cell(args.workload)
    cfg, mix, chips = c["cfg"], c["mix"], c["workload"]["chips"]
    devices = jax.devices()
    if (devices[0].platform != "tpu" or len(devices) < chips):
        print(f"readings: the cell needs {chips} TPU chip(s)", file=sys.stderr)
        return 2
    calls, sharding = harness.load_module("entries", mix["entry"]).build(
        cfg, mix, devices[:chips])
    ref = harness.load_module("reference", mix["entry"])
    warm = False
    for seed in seeds(args.seeds):
        xs = harness.make_inputs(cfg, mix, seed, sharding)
        if not warm:
            harness.warm_up(calls, xs[0])
            warm = True
        win = harness.Window(calls, xs, mix, seed).run(args.seconds)
        kept = {slot: harness.host_shards(y) for slot, y in win.kept.items()}
        xs_host = [np.asarray(x) for x in xs]
        del xs, win
        t0 = time.perf_counter()
        program, failed = harness.compare(ref, mix, xs_host, kept, chips)
        control, control_failed = harness.compare(ref, mix, xs_host, kept, chips,
                                                  control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "outputs": len(kept), "program": program,
                          "program_failed": failed, "control": control,
                          "control_failed": control_failed,
                          "limits": mix["limits"],
                          "compare_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
