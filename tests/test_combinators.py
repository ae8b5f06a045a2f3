"""Combinator IR: vocabulary semantics, algebraic laws, optimizer, executor."""
import random

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.combinators import (compile_expr, fuse, lower, num_perm_stages,
                               run_program, vocab as V)
from repro.combinators.execute import get_engine, register_engine
from repro.combinators.ir import Bfly, CmpHalves, Id, Map, Perm, Seq, seq
from repro.combinators.optimize import program_cost
from repro.combinators.sort import compiled_sort, sort_expr
from repro.core.bmmc import Bmmc
from repro.core.parm import parm_ref


def run_ref(expr, n, xs):
    return np.asarray(run_program(lower(expr, n), jnp.asarray(xs), "ref"))


# ---------------------------------------------------------------------------
# Vocabulary semantics vs numpy oracles
# ---------------------------------------------------------------------------

def test_riffle_is_perfect_shuffle():
    n = 4
    xs = np.arange(1 << n, dtype=np.int32)
    got = run_ref(V.riffle(n), n, xs)
    h = 1 << (n - 1)
    want = np.empty_like(xs)
    want[0::2], want[1::2] = xs[:h], xs[h:]
    assert np.array_equal(got, want)


def test_unriffle_and_evens_odds():
    n = 5
    xs = np.arange(1 << n, dtype=np.int32)
    want = np.concatenate([xs[0::2], xs[1::2]])
    assert np.array_equal(run_ref(V.unriffle(n), n, xs), want)
    assert np.array_equal(run_ref(V.evens_odds(n), n, xs), want)


def test_interleave_alias():
    assert V.interleave(6) == V.riffle(6)


def test_rev_reverses():
    n = 6
    xs = np.arange(1 << n, dtype=np.int32)
    assert np.array_equal(run_ref(V.rev(n), n, xs), xs[::-1])


def test_transpose_matches_numpy():
    rb, cb = 3, 4
    xs = np.arange(1 << (rb + cb), dtype=np.int32)
    got = run_ref(V.transpose(rb, cb), rb + cb, xs)
    want = xs.reshape(1 << rb, 1 << cb).T.reshape(-1)
    assert np.array_equal(got, want)


def test_stride_permute_gathers_with_stride():
    n, k = 6, 2
    xs = np.arange(1 << n, dtype=np.int32)
    got = run_ref(V.stride_permute(n, k), n, xs)
    # out visits x at stride 2^k: out[c * 2^(n-k) + r] = x[r * 2^k + c]
    want = xs.reshape(1 << (n - k), 1 << k).T.reshape(-1)
    assert np.array_equal(got, want)
    assert V.stride_permute(n, 1) == V.unriffle(n)
    assert V.stride_permute(n, n - 1) == V.riffle(n)


@given(st.integers(2, 7), st.integers(0, 10**6))
@settings(max_examples=25, deadline=None)
def test_parm_combinator_matches_parm_ref(n, seed):
    rng = random.Random(seed)
    mask = rng.randrange(1, 1 << n)
    xs = np.random.default_rng(seed).integers(0, 100, 1 << n).astype(np.int32)
    e = V.parm(mask, V.rev(n - 1))
    want = parm_ref(mask, lambda h: h[::-1], xs)
    assert np.array_equal(run_ref(e, n, xs), want)


def test_two_and_ilv_lifts():
    n = 5
    xs = np.arange(1 << n, dtype=np.int32)
    h = 1 << (n - 1)
    got = run_ref(V.two(V.rev(n - 1)), n, xs)
    want = np.concatenate([xs[:h][::-1], xs[h:][::-1]])
    assert np.array_equal(got, want)
    got = run_ref(V.ilv(V.rev(n - 1)), n, xs)
    want = parm_ref(1, lambda s: s[::-1], xs)
    assert np.array_equal(got, want)


def test_emap_applies_elementwise_through_lifts():
    n = 4
    xs = np.arange(1 << n, dtype=np.int32)
    e = V.two(V.ilv(V.emap("double", lambda x: x * 2)))
    assert np.array_equal(run_ref(e, n, xs), xs * 2)


# ---------------------------------------------------------------------------
# Algebraic laws / optimizer properties
# ---------------------------------------------------------------------------

def test_riffle_unriffle_cancels_to_identity():
    n = 8
    assert fuse(lower(V.riffle(n) >> V.unriffle(n), n)) == ()
    assert fuse(lower(V.unriffle(n) >> V.riffle(n), n)) == ()


def test_perm_inverse_cancels():
    b = Bmmc.random(7, random.Random(0))
    e = V.perm(b) >> V.perm(b.inverse())
    assert fuse(lower(e, 7)) == ()


@given(st.integers(3, 8), st.integers(0, 10**6), st.integers(2, 6))
@settings(max_examples=20, deadline=None)
def test_fusion_preserves_semantics(n, seed, depth):
    """Fused program == unfused oracle on a random perm/cmp expression."""
    rng = random.Random(seed)
    parts = []
    for _ in range(depth):
        r = rng.random()
        if r < 0.5:
            parts.append(V.perm(Bmmc.random_bpc(n, rng)))
        elif r < 0.75:
            parts.append(V.perm(Bmmc.random(n, rng)))
        else:
            parts.append(V.cmp_halves())
    e = seq(*parts)
    raw = lower(e, n)
    fz = fuse(raw)
    xs = np.random.default_rng(seed).integers(0, 1000, 1 << n).astype(np.int32)
    got_raw = np.asarray(run_program(raw, jnp.asarray(xs), "ref"))
    got_fz = np.asarray(run_program(fz, jnp.asarray(xs), "ref"))
    assert np.array_equal(got_raw, got_fz)
    assert num_perm_stages(fz) <= num_perm_stages(raw)


@given(st.integers(4, 9), st.integers(0, 10**6))
@settings(max_examples=10, deadline=None)
def test_optimizer_never_increases_pass_count(n, seed):
    """Tiled pass count (the §5.2 cost) of fused <= unfused — always."""
    rng = random.Random(seed)
    e = seq(V.parm(rng.randrange(1, 1 << n), V.rev(n - 1)),
            V.riffle(n), V.perm(Bmmc.random(n, rng)), V.bit_reverse(n))
    t = max(2, n // 3)
    raw_cost = program_cost(lower(e, n), t)
    fz_cost = program_cost(fuse(lower(e, n)), t)
    assert fz_cost["tiled_passes"] <= raw_cost["tiled_passes"]
    assert fz_cost["perm_stages"] <= raw_cost["perm_stages"]


def test_seq_flattens_and_drops_id():
    a, b = V.bit_reverse(4), V.rev(4)
    assert seq(a, Id(), b) == Seq((a, b))
    assert seq(Id(), Id()) == Id()
    assert seq(a) == a
    assert (a >> b) == Seq((a, b))


# ---------------------------------------------------------------------------
# Executor: engines, caching
# ---------------------------------------------------------------------------

def test_engine_registry_and_custom_engine():
    calls = []

    def counting_engine(x, bmmc):
        calls.append(bmmc)
        return get_engine("ref")(x, bmmc)

    n = 6
    xs = jnp.arange(1 << n, dtype=jnp.int32)
    e = V.riffle(n) >> V.bit_reverse(n)
    got = np.asarray(run_program(fuse(lower(e, n)), xs, counting_engine))
    want = np.asarray(run_program(lower(e, n), xs, "ref"))
    assert np.array_equal(got, want)
    assert len(calls) == 1  # fused into a single Perm stage

    register_engine("counting-test", counting_engine)
    assert get_engine("counting-test") is counting_engine


def test_compile_expr_cache_returns_same_object():
    e = V.riffle(8) >> V.unriffle(8)
    f1 = compile_expr(e, engine="ref")
    f2 = compile_expr(e, engine="ref")
    assert f1 is f2
    f3 = compile_expr(e, engine="pallas")
    assert f3 is not f1


def test_compiled_expr_pallas_matches_ref():
    n = 9
    e = V.bit_reverse(n) >> V.parm(0b101, V.rev(n - 1)) >> V.riffle(n)
    xs = jnp.arange(1 << n, dtype=jnp.float32)
    got = np.asarray(compile_expr(e, engine="pallas")(xs))
    want = np.asarray(compile_expr(e, engine="ref")(xs))
    assert np.array_equal(got, want)


def test_compiled_expr_rejects_bad_length():
    f = compile_expr(V.rev(4), engine="ref")
    with pytest.raises(ValueError):
        f(jnp.arange(24.0))


# ---------------------------------------------------------------------------
# End-to-end sort acceptance (ISSUE 1): 2^12 through the pallas engine
# ---------------------------------------------------------------------------

def test_sort_expr_small_all_sizes():
    for n in range(0, 7):
        xs = np.random.default_rng(n).integers(0, 997, 1 << n).astype(np.int32)
        got = np.asarray(compiled_sort(n, engine="ref")(jnp.asarray(xs)))
        assert np.array_equal(got, np.sort(xs)), n


def test_sort_fusion_strictly_reduces_perm_stages():
    n = 12
    raw = lower(sort_expr(n), n)
    fz = fuse(raw)
    assert num_perm_stages(fz) < num_perm_stages(raw)
    # exactly one fused BMMC between consecutive compare-exchange sweeps
    kinds = [type(s).__name__ for s in fz]
    assert "Perm Perm" not in " ".join(kinds)


@pytest.mark.slow
def test_sort_2pow12_through_pallas_engine():
    """ISSUE 1 acceptance: compiled balanced-periodic sort on 2^12 elements
    matches np.sort and executes through the pallas engine."""
    n = 12
    xs = np.random.default_rng(0).integers(0, 1 << 30, 1 << n).astype(np.int32)
    f = compiled_sort(n, engine="pallas")
    got = np.asarray(f(jnp.asarray(xs)))
    assert np.array_equal(got, np.sort(xs))
