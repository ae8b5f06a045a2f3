"""Distributed BMMC: offline plan verification + on-device executor.

The executor test runs in a subprocess with 16 fake CPU devices (device
count is locked at first jax import in the main pytest process).
"""
import random
import subprocess
import sys

import pytest
from hypothesis import given, settings, strategies as st

from repro.core.bmmc import Bmmc
from repro.core.distributed import make_plan, plan_cost, plan_to_bmmc


@given(st.integers(5, 12), st.integers(1, 4), st.integers(0, 10**6),
       st.booleans())
@settings(max_examples=60, deadline=None)
def test_plan_composes_to_bmmc(n, s, seed, bpc):
    """Offline: rounds compose exactly back to the global BMMC."""
    if s >= n - 1:
        return
    rng = random.Random(seed)
    b = Bmmc.random_bpc(n, rng) if bpc else Bmmc.random(n, rng)
    plan = make_plan(b, s)  # internal assert: plan_to_bmmc(plan) == b
    got = plan_to_bmmc(plan, n, s)
    assert got.rows == b.rows and got.c == b.c


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
