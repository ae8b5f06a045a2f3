"""Class-dispatch kernel hierarchy tests (DESIGN.md §11).

Pins the contracts of this PR's dispatch stack:

* the classification predicates partition fuzzed BMMC space (n=2..16)
  and stay consistent with ``is_bp`` / ``is_bpc`` / ``is_tiled``;
* each fast-path kernel (block-permute, lane-permute) is bitwise-equal
  to the ref engine across dtypes x trailing dims x batch sizes for
  BMMCs sampled from its class;
* the generalized witness-direction planner gives EVERY invertible BMMC
  a one-pass plan (2t <= n) whose tables drive the unchanged tiled
  kernel to the exact permutation, and whose analytic stats match the
  enumerated tables;
* the block plan's descriptor count equals the copy-through-VMEM
  baseline's whenever the class grants copy-block granularity;
* free-stage folding (complement / tile-index-only) erases the folded
  stage's HBM round trip and stays lossless;
* the program-executable and class-plan caches are registered with
  ``clear_caches`` and their keys are independent of the batch size.
"""
import random

import jax.numpy as jnp
import numpy as np
import pytest

from repro.combinators import (cache_stats, clear_caches, cluster,
                               compile_expr, expand_clusters, fold_free,
                               program_cost, vocab as V)
from repro.combinators.ir import CmpHalves, Perm
from repro.core.bmmc import Bmmc
from repro.core.tiling import (class_stats, copy_descriptors, dispatch_kernel,
                               plan_block, plan_bmmc, plan_general,
                               plan_stats_general, plan_tiled)
from repro.kernels.bmmc_permute import (block_permute, copy_pad_elems,
                                        lane_permute, tiled_permute)
from repro.kernels.ops import bmmc_permute, choose_tile, class_plan
from repro.kernels.ref import bmmc_ref


@pytest.fixture(autouse=True, scope="module")
def _bounded_caches():
    yield
    clear_caches()


def _payload(shape, dtype, seed):
    vals = np.random.default_rng(seed).integers(0, 1 << 16, shape)
    return jnp.asarray(vals).astype(dtype)


def _assert_bitwise(got, want, ctx):
    assert got.dtype == want.dtype, ctx
    assert np.array_equal(np.asarray(got).view(np.uint8),
                          np.asarray(want).view(np.uint8)), ctx


def _sample_of_class(cls: str, n: int, t: int, rng) -> Bmmc:
    """A random BMMC whose ``bmmc_class(t)`` is exactly ``cls`` (a draw
    from a structural family can collapse into an earlier class — e.g. a
    1-bit "block" sub-BMMC is the identity — so resample until exact)."""
    ident = tuple(1 << i for i in range(n))
    while True:
        if cls == "identity":
            return Bmmc.identity(n)
        elif cls == "complement":
            b = Bmmc(ident, rng.randrange(1, 1 << n))
        elif cls == "block":
            # needs >= 2 permutable high bits or it collapses to
            # identity/complement
            k = rng.randrange(t, n - 1)
            sub = Bmmc.random(n - k, rng)
            b = Bmmc(ident[:k] + tuple(r << k for r in sub.rows),
                     sub.c << k)
        elif cls == "lane":
            k = rng.randrange(2, t + 1)  # closed on the low k <= t bits
            sub = Bmmc.random(k, rng)
            b = Bmmc(tuple(sub.rows) + ident[k:], sub.c)
        elif cls == "tiled":
            b = Bmmc.random_bpc(n, rng)
        else:
            b = Bmmc.random(n, rng)
        if b.bmmc_class(t) == cls:
            return b


# ---------------------------------------------------------------------------
# Classification predicates partition BMMC space
# ---------------------------------------------------------------------------

@pytest.mark.tier1
@pytest.mark.parametrize("n", range(2, 17))
def test_classes_partition_and_agree_with_bp_bpc_tiled(n):
    rng = random.Random(n)
    t = max(1, n // 2)
    samples = [Bmmc.identity(n), Bmmc.reverse_array(n),
               Bmmc.bit_reverse(n)]
    reachable = ["complement"]
    if n >= t + 2:
        reachable.append("block")
    if t >= 2:
        reachable.append("lane")
    for _ in range(12):
        samples.append(Bmmc.random(n, rng))
        samples.append(Bmmc.random_bpc(n, rng))
        samples.append(_sample_of_class(rng.choice(reachable), n, t, rng))
    for b in samples:
        cls = b.bmmc_class(t)
        # the class is the FIRST matching predicate -> partition
        preds = {
            "identity": b.is_identity_perm(),
            "complement": b.is_complement_only(),
            "block": b.is_tile_index_only(t),
            "lane": b.is_lane_local(t),
            "tiled": b.is_tiled(t),
            "general": True,
        }
        order = list(preds)
        assert preds[cls], (cls, b)
        for earlier in order[:order.index(cls)]:
            assert not preds[earlier], (cls, earlier, b)
        # consistency with the PR-2 classification predicates
        if cls in ("identity", "complement"):
            assert b.is_bpc()
            assert b.is_bp() == (b.c == 0)
        if b.is_bpc():
            assert b.is_tiled(t)          # every BPC is tiled
            assert cls != "general"
        if cls == "block":
            assert b.block_bits() >= t
            assert b.is_tiled(t)          # whole-row moves are tiled too
        if cls == "lane":
            assert b.is_tiled(t)
        if cls == "general":
            assert not b.is_tiled(t)


@pytest.mark.tier1
def test_block_and_lane_predicates_are_semantic():
    """Predicates match the permutation's actual behaviour: block never
    splits an aligned 2^t run; lane never moves an element across rows."""
    rng = random.Random(7)
    n, t = 9, 3
    for cls in ("block", "lane"):
        b = _sample_of_class(cls, n, t, rng)
        for x in rng.sample(range(1 << n), 32):
            y = b.apply(x)
            if cls == "block":
                assert (y & ((1 << t) - 1)) == (x & ((1 << t) - 1))
                assert b.apply(x ^ 1) == (y ^ 1)   # lanes ride along
            else:
                assert (y >> t) == (x >> t)        # row is fixed


# ---------------------------------------------------------------------------
# Fast-path kernels: bitwise parity with the ref oracle
# ---------------------------------------------------------------------------

@pytest.mark.tier1
@pytest.mark.parametrize("cls", ["block", "lane"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bfloat16])
@pytest.mark.parametrize("tail,bsz", [((), None), ((3,), None), ((), 2),
                                      ((2,), 3)])
def test_fast_path_kernels_bitwise_vs_ref(cls, dtype, tail, bsz):
    rng = random.Random(hash((cls, str(dtype), tail, bsz)) % 9973)
    n = 10
    t = choose_tile(n, jnp.dtype(dtype).itemsize, tail[0] if tail else 1)
    b = _sample_of_class(cls, n, t, rng)
    kernel, payload = class_plan(b, t)
    assert kernel == cls, (kernel, b)
    batched = bsz is not None
    shape = ((bsz,) if batched else ()) + (1 << n,) + tail
    x = _payload(shape, dtype, seed=rng.randrange(1 << 20))
    if cls == "block":
        got = block_permute(x, payload, batched=batched)
    else:
        got = lane_permute(x, payload, batched=batched)
    want = bmmc_ref(x, b, batched=batched)
    _assert_bitwise(got, want, (cls, dtype, tail, bsz))
    # and the public dispatcher picks the same fast path
    got2 = bmmc_permute(x, b, batched=batched)
    _assert_bitwise(got2, want, ("dispatch", cls, dtype, tail, bsz))


@pytest.mark.tier1
@pytest.mark.parametrize("seed", range(3))
def test_complement_dispatch_all_shapes(seed):
    """Pure complements: high-only -> block kernel, low-only -> lane
    kernel, mixed -> one tiled pass; all bitwise == ref."""
    n = 10
    t = choose_tile(n, 4, 1)
    rng = random.Random(seed)
    cases = {
        "block": rng.randrange(1, 1 << (n - t)) << t,
        "lane": rng.randrange(1, 1 << t),
        "tiled": (rng.randrange(1, 1 << t)
                  | (rng.randrange(1, 1 << (n - t)) << t)),
    }
    x = _payload((1 << n,), jnp.float32, seed)
    for want_kernel, c in cases.items():
        b = Bmmc.xor_shift(n, c)
        assert b.bmmc_class(t) == "complement"
        assert dispatch_kernel(b, t) == want_kernel, (want_kernel, hex(c))
        _assert_bitwise(bmmc_permute(x, b), bmmc_ref(x, b),
                        (want_kernel, hex(c)))


# ---------------------------------------------------------------------------
# Generalized one-pass planner
# ---------------------------------------------------------------------------

@pytest.mark.tier1
@pytest.mark.parametrize("n,t", [(8, 3), (8, 4), (10, 5), (12, 6), (9, 4)])
def test_general_bmmc_plans_one_pass_and_matches_ref(n, t):
    rng = random.Random(n * 31 + t)
    for _ in range(4):
        b = Bmmc.random(n, rng)
        plans = plan_bmmc(b, t)
        assert len(plans) == 1, "2t <= n must always yield ONE pass"
        x = jnp.arange(1 << n, dtype=jnp.int32)
        got = tiled_permute(x, plans[0])
        _assert_bitwise(got, bmmc_ref(x, b), (n, t))


@pytest.mark.tier1
@pytest.mark.parametrize("n,t", [(8, 3), (10, 4), (12, 6)])
def test_general_plan_stats_match_tables(n, t):
    rng = random.Random(n + t)
    for _ in range(6):
        b = Bmmc.random(n, rng)
        if b.is_tiled(t):
            continue
        p = plan_general(b, t)
        s = plan_stats_general(b, t)
        assert p is not None and s is not None
        assert (s.n_tiles, s.rows_per_tile, s.in_run, s.out_run) == \
            (p.n_tiles, p.rows_per_tile, p.in_run, p.out_run)
        assert s.dma_descriptors() == p.dma_descriptors()


@pytest.mark.tier1
def test_classic_witness_still_preferred_for_tiled():
    """BPCs keep the tuned classic planner (contiguity-preferring
    witness search), not the generalized one."""
    n, t = 10, 4
    b = Bmmc.random_bpc(n, random.Random(5))
    plans = plan_bmmc(b, t)
    assert len(plans) == 1
    assert plans[0].row_cols, "classic plan carries witness columns"
    assert plan_tiled(b, t) is not None


# ---------------------------------------------------------------------------
# Block plan == copy roofline
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_block_plan_descriptors_equal_copy():
    """ISSUE 5 acceptance: at copy-block granularity the block-permute
    plan issues exactly copy_through_vmem's descriptor count."""
    n = 13
    rng = random.Random(1)
    ident = tuple(1 << i for i in range(n))
    sub = Bmmc.random(n - 11, rng)
    b = Bmmc(ident[:11] + tuple(r << 11 for r in sub.rows), sub.c << 11)
    plan = plan_block(b, choose_tile(n, 4, 1))
    assert copy_pad_elems(1 << n) == 0     # baseline is exact, not padded
    assert plan.dma_descriptors() == copy_descriptors(n)
    cs = class_stats(b, choose_tile(n, 4, 1))
    assert cs["kernel"] == "block"
    assert cs["roofline_ratio"] == 1.0


# ---------------------------------------------------------------------------
# Free-stage folding
# ---------------------------------------------------------------------------

@pytest.mark.tier1
@pytest.mark.parametrize("free_cls", ["complement", "block"])
def test_fold_free_erases_round_trip_and_is_lossless(free_cls):
    n, t = 10, 4
    rng = random.Random(3)
    base = (Perm(Bmmc.random(n, rng)), CmpHalves(),
            Perm(Bmmc.random(n, rng)))
    clustered = cluster(base, n, t)
    free = Perm(_sample_of_class(free_cls, n, t, rng))
    prog = clustered + (free,)
    folded = fold_free(prog, n, t)
    assert expand_clusters(folded) == expand_clusters(prog)
    assert not any(isinstance(s, Perm) and s is free for s in folded)
    assert (program_cost(folded, t)["round_trips"]
            < program_cost(tuple(clustered) + (free,), t)["round_trips"]
            + 1), "free stage must not add a round trip"
    c_folded = program_cost(folded, t)
    c_apart = program_cost(prog, t)
    assert c_folded["round_trips"] == c_apart["round_trips"] - 1
    # execution equivalence through the pallas engine
    e_folded = compile_expr(V.seq(*expand_clusters(folded)), engine="pallas")
    e_ref = compile_expr(V.seq(*expand_clusters(prog)), engine="ref")
    x = _payload((1 << n,), jnp.float32, 11)
    _assert_bitwise(e_folded(x), e_ref(x), free_cls)


@pytest.mark.tier1
def test_clustered_program_round_trips_acceptance():
    """The clustered 2^12 sort (t = 6) models at most 23 round trips
    (157 unfused) with at most 6 compare sweeps and no two-pass general
    permutation; the 2^12 FFT stays at ONE."""
    from repro.combinators.sort import sort_expr
    from repro.combinators.fft import fft_expr
    n = 12
    t = choose_tile(n, 4, 1)
    assert t == 6
    f = compile_expr(sort_expr(n), engine="pallas")
    cost = f.cost(n, t, clustered=True)
    assert cost["round_trips"] <= 23, cost
    assert cost["round_trips_unfused"] == 157, cost
    kernels = cost["kernels"]
    assert kernels and kernels.get("sweep", 0) <= 6, cost
    assert not kernels.get("general2"), cost
    g = compile_expr(fft_expr(n), engine="pallas")
    gcost = g.cost(n, choose_tile(n, 4, 2), clustered=True)
    assert gcost["round_trips"] == 1, gcost


# ---------------------------------------------------------------------------
# Cache registration + batch-size independence (ISSUE 5 satellite)
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_program_and_class_caches_clear_and_ignore_batch_size():
    from repro.kernels import ops

    clear_caches()
    n = 9
    e = V.bit_reverse(n) >> V.perm(Bmmc.random(n, random.Random(3)))
    f = compile_expr(e, engine="pallas")
    f(_payload((2, 1 << n), jnp.float32, 0), batched=True)   # warm
    before_prog = cache_stats()["program"]
    before_class = cache_stats()["class_plan"]
    assert before_prog.currsize > 0
    for bsz in (3, 4, 8, 16):
        f(_payload((bsz, 1 << n), jnp.float32, bsz), batched=True)
    after_prog = cache_stats()["program"]
    after_class = cache_stats()["class_plan"]
    assert after_prog.misses == before_prog.misses
    assert after_prog.currsize == before_prog.currsize
    assert after_class.currsize == before_class.currsize
    clear_caches()
    assert cache_stats()["program"].currsize == 0
    assert ops._class_plan_cached.cache_info().currsize == 0


@pytest.mark.tier1
def test_executable_matches_per_stage_path():
    """The whole-program executable and the stage-at-a-time dispatcher
    compute the same bits (the executable only removes host overhead)."""
    from repro.combinators.sort import sort_expr
    n = 7
    f = compile_expr(sort_expr(n), engine="pallas")
    x = _payload((1 << n,), jnp.float32, 5)
    _assert_bitwise(f(x), f.call_per_stage(x), "executable parity")
