"""Distributed BMMC: offline plan verification + on-device executor.

The executor tests run in subprocesses with fake CPU devices (device
count is locked at first jax import in the main pytest process).
"""
import json
import os
import random
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.bmmc import Bmmc
from repro.core.distributed import (ExchangeRound, make_plan, plan_cost,
                                    plan_to_bmmc)

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _no_adjacent_repeats(plan) -> bool:
    return all(type(a) is not type(b) for a, b in zip(plan, plan[1:]))


@given(st.integers(5, 12), st.integers(1, 4), st.integers(0, 10**6),
       st.booleans())
@settings(max_examples=60, deadline=None)
def test_plan_composes_to_bmmc(n, s, seed, bpc):
    """Offline: rounds compose exactly back to the global BMMC."""
    if s >= n - 1:
        return
    rng = random.Random(seed)
    b = Bmmc.random_bpc(n, rng) if bpc else Bmmc.random(n, rng)
    plan = make_plan(b, s)  # internal check: plan_to_bmmc(plan) == b
    got = plan_to_bmmc(plan, n, s)
    assert got.rows == b.rows and got.c == b.c
    assert _no_adjacent_repeats(plan)


@given(st.integers(5, 12), st.integers(1, 4), st.integers(0, 10**6),
       st.booleans())
@settings(max_examples=60, deadline=None)
def test_bpc_plan_has_one_exchange(n, s, seed, complement):
    """A bit permutation, with or without a complement, exchanges once:
    the bits that change sides go in one all_to_all."""
    if s >= n - 1:
        return
    rng = random.Random(seed)
    b = Bmmc.random_bpc(n, rng)
    if not complement:
        b = Bmmc(b.rows, 0)
    plan = make_plan(b, s)
    assert plan_cost(plan)["exchange"] <= 1
    assert _no_adjacent_repeats(plan)
    p = b.perm()
    crossing = sum(1 for j in range(n - s) if p[j] >= n - s)
    ex = [r.k for r in plan if isinstance(r, ExchangeRound)]
    assert ex == ([crossing] if crossing else [])


@given(st.integers(5, 12), st.integers(1, 4), st.integers(0, 10**6))
@settings(max_examples=40, deadline=None)
def test_two_exchange_round_bound(n, s, seed):
    """Sharded analogue of paper §5.2: <= 2 exchange (all-to-all) rounds."""
    if s >= n - 1:
        return
    b = Bmmc.random(n, random.Random(seed))
    cost = plan_cost(make_plan(b, s))
    assert cost["exchange"] <= 2
    assert cost["permute"] <= 6


def test_separable_needs_no_exchange():
    """Shard-separable BMMCs (A_sl = 0) need zero all-to-all rounds."""
    # pure local permutation + shard relabel
    n, s = 10, 3
    rng = random.Random(0)
    local = Bmmc.random(n - s, rng)
    rows = tuple(local.rows) + tuple(1 << i for i in range(n - s, n))
    b = Bmmc(rows, 5)
    cost = plan_cost(make_plan(b, s))
    assert cost["exchange"] == 0 and cost["permute"] <= 1


EXEC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import random
import numpy as np, jax.numpy as jnp
from repro.core.bmmc import Bmmc
from repro.core.distributed import distributed_bmmc, binary_mesh
from repro.kernels.ref import bmmc_ref

rng = random.Random(1)
for s in (2, 4):
    mesh = binary_mesh(s)
    for n in (s + 2, s + 5):
        for trial in range(3):
            b = Bmmc.random(n, rng) if trial % 2 else Bmmc.random_bpc(n, rng)
            x = jnp.arange(1 << n, dtype=jnp.float32)
            got = np.asarray(distributed_bmmc(x, b, s, mesh))
            want = np.asarray(bmmc_ref(x, b))
            assert np.array_equal(got, want), (n, s, trial)
print("OK")
"""


@pytest.mark.slow
def test_executor_on_fake_devices():
    out = subprocess.run(
        [sys.executable, "-c", EXEC_SCRIPT], capture_output=True, text=True,
        # JAX_PLATFORMS=cpu: the fake devices are host-platform shards;
        # without it a scrubbed env lets jax probe real accelerator
        # backends (a baked-in libtpu stalls ~8 min) and the probe
        # alone blows the timeout
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
             "JAX_PLATFORMS": "cpu"},
        timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


MIX = json.loads((Path(__file__).resolve().parent.parent / "bench" / "traffic"
                  / "reshard_round_robin.json").read_text())


@pytest.mark.parametrize("name,exchanges,permutes,locals_", [
    ("rows-to-cols", 1, 0, 1), ("rows-relabel", 0, 1, 0),
    ("random-bmmc", 2, 3, 4)])
def test_reshard_mix_plans(name, exchanges, permutes, locals_):
    """The reshard cell's three calls at 2^26 over 4 chips: the row to
    column reshard is one exchange of 2 bits and one local round, the
    shuffled row split one shard permute, the random BMMC the general
    route."""
    op = next(o for o in MIX["ops"] if o["name"] == name)
    plan = make_plan(Bmmc(tuple(op["rows"]), op["c"]), 2)
    cost = plan_cost(plan)
    assert (cost["exchange"], cost["permute"], cost["local"]) == (
        exchanges, permutes, locals_)
    assert _no_adjacent_repeats(plan)


def _complement_cases():
    """(rows, complements) per kernel class the class dispatch picks
    for ``rows``; the identity's complements span every class. The
    bit-reverse's tiled pass runs on the transposed tile."""
    n, rng = 10, random.Random(5)
    block = list(range(n))
    block[n - 1], block[7] = block[7], block[n - 1]
    lane = list(reversed(range(5))) + list(range(5, n))
    cases = {
        "transposed": Bmmc.bit_reverse(n).rows,
        "tiled": Bmmc.random_bpc(n, rng).rows,
        "general": Bmmc.random(n, rng).rows,
        "block": Bmmc.from_perm(block).rows,
        "lane": Bmmc.from_perm(lane).rows,
        "identity": Bmmc.identity(n).rows,
    }
    cs = (0, 0b11 << 7, 0b101, (0b11 << 7) | 0b101)
    return {k: (rows, cs) for k, rows in cases.items()}


@pytest.mark.parametrize("kind", sorted(_complement_cases()))
def test_permute_complements_by_index(kind):
    """One kernel, its tables chosen by a traced index, equals the
    reference for each complement; the complements' tiled passes share
    one orientation and the union of their intra-tile schedules."""
    import jax
    import jax.numpy as jnp
    from repro.core.tiling import IntraSteps
    from repro.kernels import ops
    from repro.kernels.ref import bmmc_ref
    rows, cs = _complement_cases()[kind]
    t = ops.choose_tile(len(rows), 4)
    got = [ops.class_plan(Bmmc(rows, c), t) for c in cs]
    if got[0][0] not in ("block", "lane") and len({k for k, _ in got}) == 1:
        for passes in zip(*(pay for _, pay in got)):
            geometry, tables = ops._shared_pass(passes, 1)
            chosen = [next(im for im in p.intra_options
                           if im.ktab is tab[2] and im.words is tab[3])
                      for p, tab in zip(passes, tables)]
            assert geometry[7] == IntraSteps.union(
                im.steps for im in chosen)
            assert geometry[7].transpose == (kind == "transposed")
    x = jnp.arange(1 << len(rows), dtype=jnp.int32)
    fn = jax.jit(lambda v, i: ops.bmmc_permute_complements(v, rows, cs, i))
    for i, c in enumerate(cs):
        want = np.asarray(bmmc_ref(x, Bmmc(rows, c)))
        assert np.array_equal(np.asarray(fn(x, jnp.int32(i))), want), (kind, i)


ENGINE_SCRIPT = r"""
import json, os, random
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from repro import obs
from repro.core import distributed as D, f2
from repro.core.bmmc import Bmmc
from repro.kernels import ops
from repro.kernels.bmmc_permute import plan_geometry
from repro.kernels.ref import bmmc_ref

N = 12


def traffic(n):
    h = n // 2
    p = list(range(n))
    for j in range(h - 2, h):
        p[j] = n - 2 + j - (h - 2)
    for j in range(h, n):
        p[j] = h - 2 + j - h
    relabel = Bmmc(tuple([1 << k for k in range(n - 2)]
                         + [1 << (n - 1), (1 << (n - 2)) | (1 << (n - 1))]))
    return {"rows-to-cols": Bmmc.from_perm(p), "rows-relabel": relabel,
            "random-bmmc": Bmmc.random(n, random.Random(42))}


def shard_rounds(b, s):
    return [r for r in D.make_plan(b, s)
            if isinstance(r, D.LocalRound) and any(r.ls_rows)]


def geometries_differ(b, s):
    for r in shard_rounds(b, s):
        t = ops.choose_tile(b.n - s, 4)
        got = [ops.class_plan(Bmmc(r.rows, r.c ^ f2.matvec(r.ls_rows, g)), t)
               for g in range(1 << s)]
        if got[0][0] in ("block", "lane"):
            continue
        if len({tuple(plan_geometry(p)[:7] for p in pay) for _, pay in got}) > 1:
            return True
    return False


def search(pred, n, seed):
    rng = random.Random(seed)
    while True:
        b = Bmmc.random(n, rng)
        if pred(b):
            return b


def cases():
    out = []
    for s in (1, 2):
        for name, b in traffic(N).items():
            out.append((f"{name}-s{s}", b, s))
        out.append((f"bpc-s{s}", Bmmc.random_bpc(N, random.Random(s)), s))
        out.append((f"shard-complement-s{s}",
                    search(lambda b: shard_rounds(b, s), N, s), s))
    nl = N - 2
    rows = [(1 << i) | ((i % 3 + 1) << nl if i in (2, 8) else 0)
            for i in range(nl)] + [1 << nl, 1 << (nl + 1)]
    out.append(("identity-by-shard-s2", Bmmc(tuple(rows), 0b1001), 2))
    out.append(("shared-geometry-s2",
                search(lambda b: geometries_differ(b, 2), 11, 0), 2))
    return out


def primitives(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    primitives(inner, out)
    return out


results = {}
for name, b, s in cases():
    mesh = D.binary_mesh(s, jax.devices()[:1 << s])
    x = jax.random.bits(jax.random.key(len(results)), (1 << b.n,), jnp.uint32)
    x = jax.lax.bitcast_convert_type(x, jnp.int32)
    obs.reset()
    obs.enable(sync=False)
    try:
        got = np.asarray(D.make_distributed_bmmc(b, s, mesh)(x))
        kernels = obs.kernel_counts()
        rounds = {k: obs.counter_value("dist.rounds", kind=k)
                  for k in ("local", "permute", "exchange")}
        sent = {k: obs.counter_value("dist.bytes", kind=k)
                for k in ("all_to_all", "ppermute")}
        spans = sorted({e["name"] for e in obs.events()})
    finally:
        obs.disable()
        obs.reset()
    plan = D.make_plan(b, s)
    exe = D._plan_executable(tuple(plan), s, mesh)
    results[name] = {
        "equal": bool(np.array_equal(got, np.asarray(bmmc_ref(x, b)))),
        "primitives": sorted(primitives(jax.make_jaxpr(exe)(x).jaxpr, set())),
        "kernels": kernels, "rounds": rounds, "bytes": sent, "spans": spans,
        "cost": D.plan_cost(plan),
        "plan_bytes": D.plan_bytes(plan, s, x.nbytes),
        "shard_rounds": len(shard_rounds(b, s))}
print("RESULTS " + json.dumps(results))
"""

ENGINE_CASES = [f"{name}-s{s}" for s in (1, 2) for name in (
    "rows-to-cols", "rows-relabel", "random-bmmc", "bpc", "shard-complement")
] + ["identity-by-shard-s2", "shared-geometry-s2"]
PALLAS_KERNELS = {"tiled", "general", "general2", "block", "lane"}


@pytest.fixture(scope="module")
def engine():
    """Every case of ``ENGINE_SCRIPT`` run once on 4 fake CPU devices,
    telemetry on: the sharded engine against ``bmmc_ref``, its jaxpr's
    primitives, and the ``dispatch``/``dist`` counters and spans."""
    out = subprocess.run(
        [sys.executable, "-c", ENGINE_SCRIPT], capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu"), timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = next(ln for ln in out.stdout.splitlines() if ln.startswith("RESULTS "))
    got = json.loads(line[len("RESULTS "):])
    assert sorted(got) == sorted(ENGINE_CASES)
    return got


@pytest.mark.parametrize("case", ENGINE_CASES)
def test_engine_matches_reference(engine, case):
    assert engine[case]["equal"]


@pytest.mark.parametrize("case", ENGINE_CASES)
def test_engine_has_no_gather(engine, case):
    """Outside the Pallas kernels the sharded program holds no gather:
    no local round takes the ``jnp.take`` path."""
    assert "gather" not in engine[case]["primitives"]
    assert "pallas_call" in engine[case]["primitives"] or (
        engine[case]["cost"]["local"] == 0)


@pytest.mark.parametrize("case", ENGINE_CASES)
def test_local_rounds_dispatch_pallas_kernels(engine, case):
    kernels = engine[case]["kernels"]
    assert set(kernels) <= PALLAS_KERNELS, kernels
    assert sum(kernels.values()) == engine[case]["cost"]["local"]


@pytest.mark.parametrize("case", ENGINE_CASES)
def test_dist_counters_match_plan(engine, case):
    r = engine[case]
    assert r["rounds"] == {k: r["cost"][k] for k in ("local", "permute", "exchange")}
    assert r["bytes"] == r["plan_bytes"]


def test_engine_cases_cover_shard_complements(engine):
    """The cases reach the stacked-table route: local rounds whose
    complement depends on the shard, on both mesh sizes."""
    assert engine["shard-complement-s1"]["shard_rounds"] > 0
    assert engine["shard-complement-s2"]["shard_rounds"] > 0
    assert engine["identity-by-shard-s2"]["shard_rounds"] > 0
    assert engine["rows-to-cols-s2"]["bytes"]["all_to_all"] == 3 * (1 << 12)


def test_dist_spans(engine):
    for r in engine.values():
        assert {"plan.dist", "dist.call"} <= set(r["spans"])
