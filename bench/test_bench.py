"""Tests of the benchmark itself, on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_bench.py

- every cell, driven end to end past the harness's look for a chip,
  comes out ``correct``;
- with the timed path broken underneath, it comes out not ``correct``:
  a call that returns its input unchanged, and an answer altered where
  it is produced;
- the control (the reference one precision lower, in the program's
  place) fails a limit;
- the trace reduction recomputes the recorded per-layer metrics.
"""
import os
import random
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import harness  # noqa: E402

SMALL_N = {"suite-tiled-24": 14, "sort-fwd-20": 8}
CELLS = sorted(SMALL_N)
SEED = 2**31 + 12345       # wider than 32 signed bits, as the driver's are


def small(workload: str) -> dict:
    """Overrides that shrink a cell: n, and matrices of that size."""
    from repro.core.bmmc import Bmmc
    n = SMALL_N[workload]
    out = {"n": n}
    mix = harness.cell(workload)["mix"]
    if "rows" in mix["ops"][0]:
        rng = random.Random(42)
        pool = {"bit-reverse": Bmmc.bit_reverse(n),
                "random-bpc": Bmmc.random_bpc(n, rng),
                "random-bmmc": Bmmc.random(n, rng)}
        out["ops"] = [{"name": op["name"], "rows": list(pool[op["name"]].rows),
                       "c": pool[op["name"]].c} for op in mix["ops"]]
    return out


def run(workload: str, wrap=None, seconds: float = 0.3) -> dict:
    return harness.run_cell(workload, SEED, seconds, False,
                            t_start=time.perf_counter(), require_chip=False,
                            overrides=small(workload), wrap=wrap,
                            log=lambda *a, **k: None)


def unchanged(fn):
    return lambda x: x


def altered(fn):
    return lambda x: fn(x).at[3].add(1)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_correct(workload):
    out = run(workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"perm_gbps", "peak_hbm_gib", "setup_s"}
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("fault", [unchanged, altered], ids=["unchanged", "altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_caught(workload, fault):
    out = run(workload, wrap=fault)
    assert not out["correct"], out["checks"]
    assert out["failed"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails(workload):
    c = harness.cell(workload)
    cfg, mix = dict(c["cfg"]), dict(c["mix"])
    for key, value in small(workload).items():
        (cfg if key in cfg else mix)[key] = value
    xs = [np.asarray(x) for x in harness.make_inputs(cfg, mix, SEED, None)]
    kept = {slot: None for slot in range(len(mix["ops"]) * len(xs))}
    ref = harness.load_module("reference", mix["entry"])
    worst, failed = harness.compare(ref, mix, xs, kept, 1, control=True)
    assert failed == len(kept)
    assert any(worst[k] > mix["limits"][k] for k in worst)


def test_trace_reduction_matches_recorded():
    import check_trace
    assert check_trace.main([]) == 0
