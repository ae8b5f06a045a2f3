"""Engine-parity fuzz: "ref" vs "pallas" (interpret mode) agreement.

Randomized BMMCs × dtypes (int32 / float32 / bfloat16) × trailing dims ×
tile geometries × batch sizes. A permutation moves values without
arithmetic, so agreement must be bit-exact in every dtype. Also pins the
batched-execution contracts: vmap fallback for 2-arg engines, and a
geometry cache that does not grow with the batch size.
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.combinators import cache_stats, clear_caches, compile_expr
from repro.combinators import vocab as V
from repro.core.bmmc import Bmmc
from repro.kernels.ops import bmmc_permute
from repro.kernels.ref import bmmc_ref

DTYPES = (jnp.int32, jnp.float32, jnp.bfloat16)


@pytest.fixture(autouse=True, scope="module")
def _bounded_caches():
    """This module sweeps many tile geometries; drop the pinned jitted
    executables when the sweep is done (ISSUE 4 satellite)."""
    yield
    clear_caches()


def _payload(shape, dtype, seed):
    vals = np.random.default_rng(seed).integers(0, 1 << 16, shape)
    return jnp.asarray(vals).astype(dtype)


def _assert_same(got, want, ctx):
    assert got.dtype == want.dtype, ctx
    assert np.array_equal(np.asarray(got).view(np.uint8),
                          np.asarray(want).view(np.uint8)), ctx


@pytest.mark.tier1
@given(st.integers(4, 8), st.integers(0, 10**6))
@settings(max_examples=8, deadline=None)
def test_engine_parity_unbatched(n, seed):
    rng = random.Random(seed)
    b = Bmmc.random(n, rng) if seed % 2 else Bmmc.random_bpc(n, rng)
    t = rng.choice([None, 2, min(3, n // 2)])
    dtype = DTYPES[seed % len(DTYPES)]
    tail = rng.choice([(), (2,), (3,)])
    x = _payload((1 << n,) + tail, dtype, seed)
    got = bmmc_permute(x, b, t=t, engine="pallas")
    want = bmmc_ref(x, b)
    _assert_same(got, want, (n, seed, t, dtype, tail))


@pytest.mark.tier1
@given(st.integers(4, 8), st.integers(0, 10**6), st.integers(1, 5))
@settings(max_examples=8, deadline=None)
def test_engine_parity_batched(n, seed, bsz):
    """Batched pallas pass == per-row ref gather, any dtype/tail/tile."""
    rng = random.Random(seed)
    b = Bmmc.random(n, rng) if seed % 2 else Bmmc.random_bpc(n, rng)
    t = rng.choice([None, 2, min(3, n // 2)])
    dtype = DTYPES[seed % len(DTYPES)]
    tail = rng.choice([(), (3,)])
    x = _payload((bsz, 1 << n) + tail, dtype, seed)
    got = bmmc_permute(x, b, t=t, engine="pallas", batched=True)
    want = jnp.stack([bmmc_ref(x[i], b) for i in range(bsz)])
    _assert_same(got, want, (n, seed, bsz, t, dtype, tail))


@pytest.mark.tier1
def test_batched_matches_vmap_of_unbatched():
    """The native batched path == jax.vmap of the unbatched ref path."""
    rng = random.Random(7)
    b = Bmmc.random(7, rng)
    x = _payload((6, 128), jnp.float32, 7)
    native = bmmc_ref(x, b, batched=True)
    vmapped = jax.vmap(lambda r: bmmc_ref(r, b))(x)
    _assert_same(native, vmapped, "vmap parity")


@pytest.mark.tier1
def test_injected_engine_vmap_fallback():
    """A legacy (x, bmmc) engine is transparently vmapped when batched."""
    calls = []

    def legacy(x, bmmc):
        calls.append(x.shape)
        assert x.ndim <= 2  # must only ever see unbatched slices
        return bmmc_ref(x, bmmc)

    n = 6
    e = V.riffle(n) >> V.bit_reverse(n)
    f = compile_expr(e, engine=legacy)
    x = _payload((3, 1 << n), jnp.float32, 0)
    got = f(x, batched=True)
    want = compile_expr(e, engine="ref")(x, batched=True)
    _assert_same(got, want, "fallback parity")
    assert calls, "legacy engine was never invoked"


@pytest.mark.tier1
def test_geometry_cache_constant_in_batch():
    """ISSUE 2 acceptance: growing B adds no geometry-cache entries."""
    n = 9
    e = V.bit_reverse(n) >> V.perm(Bmmc.random(n, random.Random(3)))
    f = compile_expr(e, engine="pallas")
    f(_payload((2, 1 << n), jnp.float32, 0), batched=True)  # warm
    before = cache_stats()["geom"]
    for bsz in (3, 4, 8, 16):
        f(_payload((bsz, 1 << n), jnp.float32, bsz), batched=True)
    after = cache_stats()["geom"]
    assert after.misses == before.misses, (before, after)
    assert after.currsize == before.currsize


@pytest.mark.tier1
def test_batched_roundtrip_through_tiled_kernels():
    """(B, 2^n) through a compiled program and its inverse is identity."""
    n = 9
    rng = random.Random(11)
    e = V.perm(Bmmc.random(n, rng)) >> V.riffle(n)
    f = compile_expr(e, engine="pallas")
    finv = f.inverse(n)
    x = _payload((4, 1 << n), jnp.float32, 5)
    _assert_same(finv(f(x, batched=True), batched=True), x, "roundtrip")
