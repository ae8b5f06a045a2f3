"""Tests of the four-chip reshard cell, ``reshard-bmmc-26``, on four
fake CPU devices at a small size:

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_reshard.py

- driven end to end past the harness's look for a chip, the cell comes
  out ``correct``;
- it comes out not ``correct`` when a call returns its input, when an
  answer is altered where it is produced, and when the program's
  ``all_to_all`` moves nothing;
- the control (the reference one precision lower, in the program's
  place) fails the limit;
- the collectives reader counts the elements that change chips as a
  brute-force count does.

The device count is fixed when JAX starts, so this module asks for four
host devices before anything imports JAX.
"""
import os
import random
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_FLAG = "--xla_force_host_platform_device_count=4"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()
BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import harness  # noqa: E402

CELL = "reshard-bmmc-26"
SMALL_N = 12
SEED = 2**31 + 12345       # wider than 32 signed bits, as a large seed is


def small_ops(n: int) -> list:
    """The mix's three reshards at 2^n: rows-to-cols of a 2^(n/2) x
    2^(n/2) matrix, the shuffled row relabel, a random BMMC."""
    from repro.core.bmmc import Bmmc
    h = n // 2
    p = list(range(n))
    for j in range(h - 2, h):
        p[j] = n - 2 + j - (h - 2)        # the column split's bits
    for j in range(h, n):
        p[j] = h - 2 + j - h              # the row bits, below them
    relabel = Bmmc(tuple([1 << k for k in range(n - 2)]
                         + [1 << (n - 1), (1 << (n - 2)) | (1 << (n - 1))]))
    pool = {"rows-to-cols": Bmmc.from_perm(p), "rows-relabel": relabel,
            "random-bmmc": Bmmc.random(n, random.Random(42))}
    mix = harness.cell(CELL)["mix"]
    return [{"name": op["name"], "rows": list(pool[op["name"]].rows),
             "c": pool[op["name"]].c} for op in mix["ops"]]


@pytest.fixture(scope="module", autouse=True)
def four_devices():
    import jax
    assert len(jax.devices()) >= 4, (
        "JAX started before this module could ask for four host devices; "
        "run bench/test_reshard.py in a process of its own")


def run(wrap=None, seconds: float = 0.3) -> dict:
    return harness.run_cell(CELL, SEED, seconds, False,
                            t_start=time.perf_counter(), require_chip=False,
                            overrides={"n": SMALL_N, "ops": small_ops(SMALL_N)},
                            wrap=wrap, log=lambda *a, **k: None)


def unchanged(fn):
    return lambda x: x


def altered(fn):
    return lambda x: fn(x).at[3].add(1)


def test_cell_correct():
    out = run()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"perm_gbps", "peak_hbm_gib", "setup_s"}
    assert out["checks"]["mismatches"]["value"] == 0


@pytest.mark.parametrize("fault", [unchanged, altered], ids=["unchanged", "altered"])
def test_fault_is_caught(fault):
    out = run(wrap=fault)
    assert not out["correct"], out["checks"]
    assert out["failed"] > 0


def test_skipped_all_to_all_is_caught(monkeypatch):
    """The exchange rounds' ``all_to_all`` returning its input unmoved."""
    import jax
    from repro.core import distributed
    monkeypatch.setattr(jax.lax, "all_to_all", lambda x, *a, **k: x)
    distributed._plan_executable.cache_clear()
    try:
        out = run()
    finally:
        distributed._plan_executable.cache_clear()
    assert not out["correct"], out["checks"]
    # rows-to-cols and random-bmmc exchange; rows-relabel does not
    assert out["failed"] == 2 * 2


def test_control_fails():
    c = harness.cell(CELL)
    cfg, mix = dict(c["cfg"]), dict(c["mix"])
    cfg["n"], mix["ops"] = SMALL_N, small_ops(SMALL_N)
    xs = [np.asarray(x) for x in harness.make_inputs(cfg, mix, SEED, None)]
    kept = {slot: None for slot in range(len(mix["ops"]) * len(xs))}
    ref = harness.load_module("reference", mix["entry"])
    worst, failed = harness.compare(ref, mix, xs, kept, 1, control=True)
    assert failed == len(kept)
    assert any(worst[k] > mix["limits"][k] for k in worst)


@pytest.mark.parametrize("chips", [2, 4, 8])
def test_collective_reader_counts_moved_elements(chips):
    from repro.core.bmmc import Bmmc
    reader = harness.load_module("metrics", "collective_roofline")
    rng = random.Random(chips)
    n = 10
    s = chips.bit_length() - 1
    i = np.arange(1 << n, dtype=np.int64)
    for b in [Bmmc.random(n, rng), Bmmc.random_bpc(n, rng)] + [
            Bmmc(tuple(op["rows"]), op["c"]) for op in small_ops(n)]:
        want = np.count_nonzero((b.apply_all(i) >> (n - s)) != (i >> (n - s)))
        assert reader.moved_elements(list(b.rows), b.c, n, chips) == want


def test_collective_reader_counts_all_to_all():
    """The collectives reader sums the device time of every collective op,
    the ``all_to_all`` the trace files as a plain ``op`` included, and of
    nothing else."""
    reader = harness.load_module("metrics", "collective_roofline")
    c = harness.cell(CELL)
    cfg, mix = dict(c["cfg"]), dict(c["mix"])
    cfg["n"], mix["ops"] = SMALL_N, small_ops(SMALL_N)
    ms = 1_000_000
    calls = [["bench.call", k * 10 * ms, 9 * ms] for k in range(3)]
    ops = [["all_to_all", 1 * ms, 2 * ms, "op"],
           ["collective-permute-done", 4 * ms, 1 * ms, "collective"],
           ["bmmc_tile", 6 * ms, 3 * ms, "pallas"],
           ["copy", 9 * ms, 1 * ms, "op"]]
    chips = cfg["chips"]
    trace = {"host": calls,
             "devices": [{"name": f"/device:TPU:{k}", "shift_ns": 0,
                          "ops": ops} for k in range(chips)]}
    peak = harness.load_json(harness.BENCH / "peaks.json")["devices"][
        "TPU v5 lite"]
    got = reader.read({"trace": trace, "cfg": cfg, "mix": mix, "peak": peak})
    least_s = reader.least_bytes_per_call(cfg, mix) / (
        chips * reader.ICI_BYTES_PER_S["TPU v5 lite"])
    spent_s = 3e-3 * chips / len(calls) / chips
    assert got == pytest.approx(100 * least_s / spent_s)
