#!/usr/bin/env python3
"""Smoke run of the BMMC permutation engine, compiled for a TPU.

    python3 chip_smoke.py             # one chip: phases (a)-(c)
    python3 chip_smoke.py --chips 4   # the four-chip reshard only

One chip runs, through the public entry points and with ``repro.obs``
recording:

  (a) the paper's suite at 2^24 int32: the copy baseline, bit-reverse,
      random BPC and random BMMC (the matrices of the ``suite-tiled-24``
      cell, read from ``bench/traffic/suite_round_robin.json``) and one
      block- and one lane-class BMMC, each through
      ``ops.make_bmmc_permute``;
  (b) the compiled sort and FFT of 2^20 elements, forward and under
      ``jax.value_and_grad``;
  (c) a batched permutation of 64 x 2^16 f32.

``--chips 4`` runs only ``make_distributed_bmmc`` over a binary mesh of
the four chips of one host: bit-reverse and a random BMMC of 2^26
int32, each local round on the Pallas kernels.

Every result is compared with a host-side numpy evaluation
(``out[A i ^ c] = x[i]``, ``np.sort``, ``np.fft``); gradients are also
compared with the ``engine="ref"`` program. The ``dispatch.kernel``
counters must show each phase's kernel class, never ``ref``, with no
fused fallback, and each jitted program must hold a compiled Pallas
kernel (``tpu_custom_call``). Each phase prints one line with its
shape, kernel classes and wall times (smoke timing, not a benchmark).
The last line is ``{"ok": true, "device": {...}}``. Without a TPU, on a
wrong result or on any error the script exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 42

N_SUITE = 24        # paper suite, int32
SUITE_TRAFFIC = os.path.join(ROOT, "bench", "traffic",
                             "suite_round_robin.json")
N_SORT = 20         # compiled sort (f32) and FFT (planar f32)
# value_and_grad of the sort: its collapsed backward bakes three 2^n
# int32 tables per compare link into the program, which passes the
# 2 GiB limit of a serialized program at 2^20 (210 links)
N_SORT_GRAD = 18
N_BATCH, BATCH = 16, 64
N_SHARDED = 26      # --chips 4: 2^24 per chip
KERNEL_MARK = "tpu_custom_call"   # a compiled Pallas TPU kernel


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def dest_index(b) -> np.ndarray:
    """``A i ^ c`` for every index i, evaluated on the host."""
    i = np.arange(1 << b.n, dtype=np.int64)
    out = np.full_like(i, b.c)
    for k, row in enumerate(b.rows):
        out ^= (np.bitwise_count(i & row) & 1).astype(np.int64) << k
    return out


def np_permute(x: np.ndarray, b, axis: int = 0) -> np.ndarray:
    """``out[A i ^ c] = x[i]`` along ``axis``."""
    out = np.empty_like(x)
    idx = [slice(None)] * x.ndim
    idx[axis] = dest_index(b)
    out[tuple(idx)] = x
    return out


def rel_l2(got, want) -> float:
    want = np.asarray(want)
    return float(np.linalg.norm(np.asarray(got) - want)
                 / np.linalg.norm(want))


def timed(fn, *args):
    """(result, seconds) of one call, waited for."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def warm_seconds(fn, *args, reps: int = 3) -> float:
    return min(timed(fn, *args)[1] for _ in range(reps))


def report(phase: str, **fields) -> None:
    print("smoke " + json.dumps({"phase": phase, **fields}), flush=True)


def run_phase(phase: str, fn, args: tuple, kernels: dict, exact=True):
    """Trace, compile and run the jitted ``fn`` once with telemetry
    counters reset, and check what it dispatched while tracing: exactly
    the ``kernels`` counts (at least them when not ``exact``: a backward
    adds its own passes), never ``ref``, no fused fallback, and a
    compiled Pallas kernel (``tpu_custom_call``) in its program. Return
    the result, the kernel counts, the cold seconds (trace + compile +
    run) and the compiled program."""
    import jax
    from repro import obs
    obs.reset()
    t0 = time.perf_counter()
    lowered = fn.lower(*args)
    counts = {k: int(v) for k, v in obs.kernel_counts().items()}
    fallback = obs.counter_total("dispatch.fused_fallback")
    check("ref" not in counts, f"{phase}: dispatched the ref gather {counts}")
    check(fallback == 0, f"{phase}: {fallback} fused fallbacks")
    check(counts == kernels if exact else all(
        counts.get(k, 0) >= v for k, v in kernels.items()),
        f"{phase}: dispatched {counts}, expected {kernels}")
    check(KERNEL_MARK in lowered.as_text(),
          f"{phase}: no {KERNEL_MARK} in the program")
    compiled = lowered.compile()
    out = jax.block_until_ready(compiled(*args))
    return out, counts, time.perf_counter() - t0, compiled


# ---------------------------------------------------------------------------
# (a) the paper's suite
# ---------------------------------------------------------------------------

def suite_cases(n: int, t: int) -> list:
    """(name, BMMC, kernel class) of the suite at 2^n with tile t: the
    ``suite-tiled-24`` cell's three matrices, then a block swap and a
    lane reverse."""
    from repro.core.bmmc import Bmmc
    kinds = {"bit-reverse": "tiled", "random-bpc": "tiled",
             "random-bmmc": "general"}
    with open(SUITE_TRAFFIC) as f:
        ops = json.load(f)["ops"]
    out = [(op["name"], Bmmc(tuple(op["rows"]), op["c"]), kinds[op["name"]])
           for op in ops]
    check(all(b.n == n for _, b, _ in out),
          f"{SUITE_TRAFFIC}: matrices are not of 2^{n} elements")
    perm = list(range(n))          # swap a high bit pair: whole blocks move
    perm[n - 1], perm[t + 2] = perm[t + 2], perm[n - 1]
    out.append(("block-swap", Bmmc.from_perm(perm), "block"))
    lane = list(reversed(range(t))) + list(range(t, n))  # rows stay put
    out.append(("lane-reverse", Bmmc.from_perm(lane), "lane"))
    return out


def phase_suite(n: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.kernels.bmmc_permute import copy_through_vmem

    host = np.random.default_rng(SEED).integers(
        -2**31, 2**31 - 1, size=1 << n, dtype=np.int32)
    x = jnp.asarray(host)
    out, counts, cold, run = run_phase(
        "a/copy", jax.jit(copy_through_vmem), (x,), {})
    check(np.array_equal(np.asarray(out), host), "a/copy: output differs")
    report("a/copy", shape=list(x.shape), dtype="int32", kernels=counts,
           cold_s=cold, warm_s=warm_seconds(run, x))

    t = ops.choose_tile(n, 4)
    for name, b, kind in suite_cases(n, t):
        fn = ops.make_bmmc_permute(b)
        out, counts, cold, run = run_phase(f"a/{name}", fn, (x,),
                                           {kind: 1})
        check(np.array_equal(np.asarray(out), np_permute(host, b)),
              f"a/{name}: output differs from out[A i ^ c] = x[i]")
        report(f"a/{name}", shape=list(x.shape), dtype="int32", t=t,
               kernels=counts, cold_s=cold, warm_s=warm_seconds(run, x))


# ---------------------------------------------------------------------------
# (b) compiled sort and FFT, forward and value_and_grad
# ---------------------------------------------------------------------------

def planned_kernels(f, n: int, d: int = 1) -> dict:
    """The kernel counts the planner's cost model gives the clustered
    f32 program (the ``dispatch.kernel`` vocabulary)."""
    from repro.kernels import ops
    t = ops.choose_tile(n, 4, d)
    return dict(f.cost(n, t, 4, clustered=True)["kernels"])


def _value_and_grad(f):
    import jax
    import jax.numpy as jnp
    return jax.jit(jax.value_and_grad(lambda x, w: jnp.sum(f(x) * w)))


def phase_sort(n: int, n_grad: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.combinators.sort import compiled_sort

    rng = np.random.default_rng(SEED + 1)
    # distinct values: the gradient of a sort is then a plain scatter
    host = rng.permutation(1 << n).astype(np.float32)
    f = compiled_sort(n, engine="pallas")
    fwd = jax.jit(f)
    x = jnp.asarray(host)
    out, counts, cold, run = run_phase("b/sort", fwd, (x,),
                                       planned_kernels(f, n))
    check(np.array_equal(np.asarray(out), np.sort(host)),
          "b/sort: output differs from np.sort")
    report("b/sort", shape=list(x.shape), dtype="float32", kernels=counts,
           cold_s=cold, warm_s=warm_seconds(run, x))

    host = rng.permutation(1 << n_grad).astype(np.float32)
    w = rng.standard_normal(1 << n_grad).astype(np.float32)
    x, wj = jnp.asarray(host), jnp.asarray(w)
    f = compiled_sort(n_grad, engine="pallas")
    vg = _value_and_grad(f)
    (val, grad), counts, cold, run = run_phase(
        "b/sort-grad", vg, (x, wj), planned_kernels(f, n_grad), exact=False)
    want = np.empty_like(w)
    want[np.argsort(host)] = w
    check(np.array_equal(np.asarray(grad), want),
          "b/sort-grad: gradient differs from the numpy scatter")
    ref_val, ref_grad = _value_and_grad(compiled_sort(n_grad, engine="ref"))(
        x, wj)
    check(np.array_equal(np.asarray(grad), np.asarray(ref_grad)),
          "b/sort-grad: gradient differs from the ref program")
    check(np.isclose(float(val), float(ref_val), rtol=1e-6),
          f"b/sort-grad: value {float(val)} vs ref {float(ref_val)}")
    report("b/sort-grad", shape=list(x.shape), dtype="float32",
           kernels=counts, cold_s=cold, warm_s=warm_seconds(run, x, wj))


def phase_fft(n: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.combinators.fft import compiled_fft

    rng = np.random.default_rng(SEED + 2)
    host = rng.standard_normal((1 << n, 2)).astype(np.float32)
    xc = host[:, 0].astype(np.float64) + 1j * host[:, 1]
    x = jnp.asarray(host)
    f = compiled_fft(n, engine="pallas")
    fwd = jax.jit(f)
    out, counts, cold, run = run_phase("b/fft", fwd, (x,),
                                       planned_kernels(f, n, 2))
    want = np.fft.fft(xc)
    got = np.asarray(out)
    err = rel_l2(got[:, 0] + 1j * got[:, 1], want)
    check(err < 1e-5, f"b/fft: relative L2 error {err} vs np.fft")
    report("b/fft", shape=list(x.shape), dtype="float32", kernels=counts,
           rel_l2_vs_numpy=err, cold_s=cold, warm_s=warm_seconds(run, x))

    w = rng.standard_normal((1 << n, 2)).astype(np.float32)
    wj = jnp.asarray(w)
    vg = _value_and_grad(f)
    (val, grad), counts, cold, run = run_phase(
        "b/fft-grad", vg, (x, wj), planned_kernels(f, n, 2), exact=False)
    # loss = Re <w, F x>  =>  dloss/dx = F^H w = 2^n ifft(w)
    g = (1 << n) * np.fft.ifft(w[:, 0] + 1j * w[:, 1].astype(np.float64))
    grad = np.asarray(grad)
    err = rel_l2(grad[:, 0] + 1j * grad[:, 1], g)
    check(err < 1e-5, f"b/fft-grad: relative L2 error {err} vs numpy")
    ref_val, ref_grad = _value_and_grad(compiled_fft(n, engine="ref"))(x, wj)
    err_ref = rel_l2(grad, np.asarray(ref_grad))
    check(err_ref < 1e-5, f"b/fft-grad: relative L2 {err_ref} vs ref")
    check(np.isclose(float(val), float(ref_val), rtol=1e-4),
          f"b/fft-grad: value {float(val)} vs ref {float(ref_val)}")
    report("b/fft-grad", shape=list(x.shape), dtype="float32",
           kernels=counts, rel_l2_vs_numpy=err, rel_l2_vs_ref=err_ref,
           cold_s=cold, warm_s=warm_seconds(run, x, wj))


# ---------------------------------------------------------------------------
# (c) batched
# ---------------------------------------------------------------------------

def phase_batched(n: int, batch: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core.bmmc import Bmmc
    from repro.kernels import ops

    b = Bmmc.random(n, random.Random(SEED))
    host = np.random.default_rng(SEED + 3).standard_normal(
        (batch, 1 << n)).astype(np.float32)
    x = jnp.asarray(host)
    fn = jax.jit(lambda v: ops.bmmc_permute(v, b, batched=True))
    out, counts, cold, run = run_phase("c/batched", fn, (x,), {"general": 1})
    check(np.array_equal(np.asarray(out), np_permute(host, b, axis=1)),
          "c/batched: output differs from out[A i ^ c] = x[i]")
    report("c/batched", shape=list(x.shape), dtype="float32",
           kernels=counts, cold_s=cold, warm_s=warm_seconds(run, x))


# ---------------------------------------------------------------------------
# --chips 4: the reshard
# ---------------------------------------------------------------------------

def phase_sharded(n: int, s: int = 2) -> None:
    import jax
    from jax.sharding import NamedSharding
    from repro import obs
    from repro.core.bmmc import Bmmc
    from repro.core.distributed import (binary_mesh, make_distributed_bmmc,
                                        shard_spec)

    check(len(jax.devices()) >= 1 << s,
          f"the reshard needs {1 << s} devices, found {len(jax.devices())}")
    mesh = binary_mesh(s)
    host = np.random.default_rng(SEED + 4).integers(
        -2**31, 2**31 - 1, size=1 << n, dtype=np.int32)
    # the leading axis split over every mesh axis, as the sharded
    # program takes it, so no call below moves the input between devices
    x = jax.device_put(host, NamedSharding(mesh, shard_spec(s)))
    for name, b in (("bit-reverse", Bmmc.bit_reverse(n)),
                    ("random-bmmc", Bmmc.random(n, random.Random(SEED)))):
        obs.reset()
        fn = make_distributed_bmmc(b, s, mesh)
        out, cold = timed(fn, x)
        kernels = {k: int(v) for k, v in obs.kernel_counts().items()}
        check(bool(kernels) and "ref" not in kernels,
              f"d/{name}: local rounds dispatched {kernels}")
        want = np_permute(host, b)
        shards = out.addressable_shards
        devices = {sh.device for sh in shards}
        check(len(shards) == 1 << s and len(devices) == 1 << s,
              f"d/{name}: {len(shards)} shards on {len(devices)} devices")
        for sh in shards:
            check(sh.data.shape == (1 << (n - s),),
                  f"d/{name}: shard shape {sh.data.shape}")
            check(np.array_equal(np.asarray(sh.data), want[sh.index]),
                  f"d/{name}: shard on {sh.device} differs")
        check(np.array_equal(np.asarray(out), want),
              f"d/{name}: output differs from out[A i ^ c] = x[i]")
        report(f"d/{name}", shape=list(out.shape), dtype="int32",
               shards={str(sh.device): [sh.index[0].start, sh.index[0].stop]
                       for sh in shards}, kernels=kernels,
               cold_s=cold, warm_s=warm_seconds(fn, x))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip reshard")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import setup_compile_cache
    cache = setup_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 1
    print(f"smoke device={dev.device_kind} count={len(devices)} "
          f"jax={jax.__version__} compile_cache={cache}", flush=True)

    from repro import obs
    obs.enable(sync=True)
    if args.chips == 4:
        phase_sharded(N_SHARDED)
    else:
        phase_suite(N_SUITE)
        phase_sort(N_SORT, N_SORT_GRAD)
        phase_fft(N_SORT)
        phase_batched(N_BATCH, BATCH)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
