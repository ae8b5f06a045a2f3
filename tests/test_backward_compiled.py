"""The compiled backward pass (DESIGN.md §13).

Gradients of compiled programs no longer replay the forward stage by
stage under ``jax.vjp``: a permutation-only program's backward IS the
offline-inverted (clustered) program, and a compute-bearing program's
backward is the COLLAPSED plan — every transposed pairwise compute
conjugated into forward-output coordinates plus at most ONE composed
inverse BMMC pass. These tests pin, in order:

* the inverse-program algebra (clusters invert to clusters; per-class
  closure; cost symmetry);
* the residual policy (permutation-only forwards save NOTHING);
* the collapsed-plan structure (sort's composed sigma is the identity,
  so its backward needs ZERO permutation passes);
* bitwise parity of the collapsed backward against the per-stage
  ``jax.vjp`` replay oracle across dtypes, tail shapes, batching, and
  tied inputs (the 0.5-mask path);
* the backward honesty gate: one COLD backward call's
  ``model.vjp_round_trips`` counter delta equals
  ``CompiledExpr.vjp_round_trips``, and a permutation-only backward's
  kernel-class histogram mirrors the forward's;
* the (gated) gradient megakernel agrees with the collapsed default.
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.combinators import (Bfly, CmpHalves, FusedStage, Map,
                               clear_caches, compile_expr, inverse_program,
                               is_perm_program, program_cost, run_program,
                               vocab as V)
from repro.combinators import execute as EX
from repro.combinators.fft import compiled_fft, fft_expr, to_planar
from repro.combinators.sort import sort_expr
from repro.core.bmmc import Bmmc
from repro.kernels.ops import choose_tile

N = 8


def _x(n, seed, shape=(), dtype=np.float32):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=shape + (1 << n,)).astype(dtype))


def _perm_expr(n, seed=0):
    rng = random.Random(seed)
    return (V.bit_reverse(n) >> V.perm(Bmmc.random(n, rng)) >> V.riffle(n))


# ---------------------------------------------------------------------------
# Inverse-program algebra: clusters invert to clusters, per-class closure
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_inverse_clustered_mirrors_forward_cost():
    """inverse(clustered perm program) is itself clustered (FusedStage
    of inverted members, reversed) and models the SAME kernel-class
    histogram and round-trip count — the backward re-dispatches the
    classes the forward did."""
    t = choose_tile(N, 4, 1)
    f = compile_expr(_perm_expr(N), engine="pallas")
    prog = f.clustered_program(N, t)
    inv = inverse_program(prog)
    assert is_perm_program(inv)
    assert len(inv) == len(prog)
    for st, ist in zip(reversed(prog), inv):
        assert type(ist) is type(st)
        if isinstance(st, FusedStage):
            assert not ist.computes
    fcost, icost = program_cost(prog, t), program_cost(inv, t)
    assert icost["round_trips"] == fcost["round_trips"]
    assert icost["kernels"] == fcost["kernels"]


@pytest.mark.tier1
def test_inverse_is_involution_on_cost():
    """Inverting twice restores the forward's modeled cost exactly."""
    t = choose_tile(N, 4, 1)
    f = compile_expr(_perm_expr(N, seed=3), engine="pallas")
    prog = f.clustered_program(N, t)
    twice = inverse_program(inverse_program(prog))
    assert program_cost(twice, t) == program_cost(prog, t)


@pytest.mark.tier1
def test_inverse_program_rejects_compute_clusters():
    t = choose_tile(N, 4, 1)
    f = compile_expr(sort_expr(N), engine="pallas")
    prog = f.clustered_program(N, t)
    assert not is_perm_program(prog)
    with pytest.raises(TypeError):
        inverse_program(prog)


# ---------------------------------------------------------------------------
# Residual policy: permutations save nothing
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_perm_only_program_saves_no_residual():
    t = choose_tile(N, 4, 1)
    f = compile_expr(_perm_expr(N), engine="pallas")
    prog = f.clustered_program(N, t)
    x = _x(N, 0)
    _, res = EX._program_apply_fwd(x, prog, t, "pallas", False)
    assert res is None


@pytest.mark.tier1
def test_compute_free_cluster_saves_no_residual():
    from repro.combinators.ir import Perm
    from repro.combinators.optimize import _run_fused
    rng = random.Random(4)
    fs = _run_fused((Perm(Bmmc.random(N, rng)), Perm(Bmmc.random(N, rng))), N)
    assert not fs.computes
    x = _x(N, 1)
    _, res = EX._fused_fwd(x, fs, "pallas", False)
    assert res is None


@pytest.mark.tier1
def test_compute_bearing_program_saves_inputs_at_compute_stages():
    """Residuals are the inputs of compute-bearing stages only — NOT a
    copy per stage (the old replay saved the whole forward input even
    for pure permutations)."""
    t = choose_tile(N, 4, 1)
    f = compile_expr(sort_expr(N), engine="pallas")
    prog = f.clustered_program(N, t)
    x = _x(N, 2)
    _, res = EX._program_apply_fwd(x, prog, t, "pallas", False)
    n_compute = sum(
        1 for st in prog
        if isinstance(st, (CmpHalves, Bfly, Map))
        or (isinstance(st, FusedStage) and st.computes))
    assert res is not None and len(res) == 1 + n_compute


# ---------------------------------------------------------------------------
# Collapsed-plan structure
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_sort_collapsed_plan_has_identity_final():
    """The balanced-periodic sorter's perms compose to the identity in
    backward time, so the collapsed backward needs ZERO permutation
    passes: all transposed cmp links run in forward-output coordinates
    and ``plan.final`` is None."""
    t = choose_tile(N, 4, 1)
    f = compile_expr(sort_expr(N), engine="pallas")
    plan = EX._program_bwd_plan(f.clustered_program(N, t), False)
    assert plan is not None
    assert plan.final is None
    assert not plan.has_bfly
    assert all(lk[0] == "cmp" for lk in plan.links)
    assert f.vjp_round_trips(N, t) == 0


@pytest.mark.tier1
def test_nonidentity_sigma_collapses_to_one_compute_free_pass():
    """A trailing permutation after the computes must survive as exactly
    ONE composed compute-free pass in the collapsed backward."""
    t = choose_tile(N, 4, 1)
    f = compile_expr(sort_expr(N) >> V.bit_reverse(N), engine="pallas")
    prog = f.clustered_program(N, t)
    plan = EX._program_bwd_plan(prog, False)
    assert plan is not None
    assert isinstance(plan.final, FusedStage) and not plan.final.computes
    modeled = f.vjp_round_trips(N, t)
    assert modeled == program_cost((plan.final,), t)["round_trips"] > 0


@pytest.mark.tier1
def test_map_stage_has_no_collapsed_plan():
    t = choose_tile(N, 4, 1)
    f = compile_expr(V.emap("double", lambda v: v * 2.0) >> V.riffle(N),
                     engine="pallas")
    assert EX._program_bwd_plan(f.clustered_program(N, t), False) is None
    assert f.vjp_round_trips(N, t) is None


# ---------------------------------------------------------------------------
# Bitwise parity: collapsed backward vs per-stage jax.vjp replay oracle
# ---------------------------------------------------------------------------

def _replay_grad(f, n, x, w, batched=False):
    """The pre-§13 backward: jax.vjp per-stage replay of the expanded
    program on the ref engine — the oracle the collapsed plan must
    reproduce bit for bit (its masks are constructed to be bitwise
    identical to the replayed where/select VJPs)."""
    prog = f.program(n)

    def loss(v):
        return jnp.sum(w * run_program(prog, v, "ref", batched=batched))

    return jax.grad(loss)(x)


@pytest.mark.tier1
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
@pytest.mark.parametrize("shape,batched", [
    ((), False),          # flat vector: permuted axis only
    ((8,), True),         # leading batch axis, then the permuted axis
    ((3,), True),         # ragged (non-power-of-2) batch width
])
def test_collapsed_backward_bitwise_vs_replay(dtype, shape, batched):
    f = compile_expr(sort_expr(N), engine="pallas")
    x = _x(N, 7, shape=shape, dtype=dtype)
    w = _x(N, 77, shape=shape, dtype=dtype)
    g = jax.grad(lambda v: jnp.sum(w * f(v, batched=batched)))(x)
    oracle = _replay_grad(f, N, x, w, batched=batched)
    assert g.dtype == x.dtype
    assert np.array_equal(np.asarray(g), np.asarray(oracle)), (dtype, shape)


@pytest.mark.tier1
def test_collapsed_backward_bitwise_on_ties():
    """Tied inputs exercise the balanced 0.5 masks: d(min)/d(max) at a
    tie splits evenly between the pair. The collapsed select-form masks
    must equal the replayed VJP exactly even there."""
    f = compile_expr(sort_expr(N), engine="pallas")
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.integers(0, 4, size=1 << N).astype(np.float32))
    w = _x(N, 55)
    g = jax.grad(lambda v: jnp.sum(w * f(v)))(x)
    oracle = _replay_grad(f, N, x, w)
    assert np.array_equal(np.asarray(g), np.asarray(oracle))


@pytest.mark.tier1
def test_permchain_backward_is_inverse_program_bitwise():
    """Permutation-only: grad == clustered inverse program applied to
    the cotangent, exactly, on both engines."""
    for engine in ("ref", "pallas"):
        f = compile_expr(_perm_expr(N), engine=engine)
        x, w = _x(N, 9), _x(N, 99)
        g = jax.grad(lambda v: jnp.sum(w * f(v)))(x)
        oracle = run_program(f.vjp_program(N), w, "ref")
        assert np.array_equal(np.asarray(g), np.asarray(oracle)), engine


@pytest.mark.tier1
def test_fft_planar_grad_collapsed_vs_replay():
    """Butterfly (bfly) links in the collapsed sweep: planar complex
    FFT gradients agree with the replay oracle (regression for the
    side-table broadcast bug the fused bfly sweep shipped with)."""
    n = 6
    rng = np.random.default_rng(13)
    x = to_planar((rng.normal(size=1 << n)
                   + 1j * rng.normal(size=1 << n)).astype(np.complex64))
    w = jnp.asarray(rng.normal(size=(1 << n, 2)).astype(np.float32))
    f = compile_expr(fft_expr(n), engine="pallas")
    g = jax.grad(lambda v: jnp.sum(w * f(v)))(x)
    prog = f.program(n)
    oracle = jax.grad(lambda v: jnp.sum(
        w * run_program(prog, v, "ref")))(x)
    assert np.allclose(np.asarray(g), np.asarray(oracle),
                       rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Backward honesty gate: cold counter delta == model; histogram mirror
# ---------------------------------------------------------------------------

def _cold_bwd_counters(f, x):
    """One cold loss-forward and one cold grad call, each from cleared
    executor caches (counters fire at executable trace time)."""
    was = obs.enabled()
    obs.enable(sync=True)
    try:
        clear_caches()
        obs.reset()
        jax.block_until_ready(jax.jit(lambda v: jnp.sum(f(v) ** 2))(x))
        fwd_kernels = obs.kernel_counts()
        clear_caches()
        obs.reset()
        jax.block_until_ready(
            jax.jit(jax.grad(lambda v: jnp.sum(f(v) ** 2)))(x))
        delta = int(obs.counter_total("model.vjp_round_trips"))
        grad_kernels = obs.kernel_counts()
    finally:
        if not was:
            obs.disable()
        obs.reset()
    bwd_kernels = {k: v - fwd_kernels.get(k, 0)
                   for k, v in grad_kernels.items()
                   if v - fwd_kernels.get(k, 0)}
    return delta, fwd_kernels, bwd_kernels


@pytest.mark.tier1
def test_cold_backward_counter_delta_equals_model_permchain():
    t = choose_tile(N, 4, 1)
    f = compile_expr(_perm_expr(N), engine="pallas")
    modeled = f.vjp_round_trips(N, t)
    delta, fwd_kernels, bwd_kernels = _cold_bwd_counters(f, _x(N, 0))
    assert modeled is not None and delta == modeled
    # perm-only: the inverse program re-dispatches the same classes
    assert bwd_kernels == fwd_kernels


@pytest.mark.tier1
def test_cold_backward_counter_delta_equals_model_sort():
    t = choose_tile(N, 4, 1)
    f = compile_expr(sort_expr(N), engine="pallas")
    modeled = f.vjp_round_trips(N, t)
    delta, _, bwd_kernels = _cold_bwd_counters(f, _x(N, 0))
    assert modeled == 0 and delta == 0
    # collapsed with identity sigma: the backward dispatches NOTHING
    assert bwd_kernels == {}


@pytest.mark.tier1
def test_vjp_dispatch_counter_labels_kind():
    was = obs.enabled()
    obs.enable(sync=True)
    try:
        obs.reset()
        clear_caches()
        f = compile_expr(_perm_expr(N), engine="pallas")
        jax.block_until_ready(
            jax.jit(jax.grad(lambda v: jnp.sum(f(v) ** 2)))(_x(N, 0)))
        counts = {labels: v for (name, labels), v in obs.counters().items()
                  if name == "dispatch.vjp"}
        assert sum(counts.values()) >= 1
    finally:
        if not was:
            obs.disable()
        obs.reset()


# ---------------------------------------------------------------------------
# Gradient megakernel (gated): agrees with the collapsed default
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_bwd_megakernel_gate_default_off():
    assert EX.BWD_MEGAKERNEL is False


@pytest.mark.tier1
def test_bwd_megakernel_matches_collapsed(monkeypatch):
    f = compile_expr(sort_expr(N), engine="pallas")
    x, w = _x(N, 21), _x(N, 22)

    def grad():
        clear_caches()
        return np.asarray(jax.grad(
            lambda v: jnp.sum(w * f(v)))(x))

    g_default = grad()
    monkeypatch.setattr(EX, "BWD_MEGAKERNEL", True)
    g_mega = grad()
    assert np.allclose(g_mega, g_default, rtol=1e-5, atol=1e-6)


@pytest.mark.tier1
@pytest.mark.parametrize("megakernel", [False, True])
def test_sort_grad_through_transposed_tiles(monkeypatch, megakernel):
    """The 2^8 sort has a cluster whose tile transposes, forward and
    inverse, and clusters with sparse shears: its gradient, stage by
    stage through each cluster's collapsed backward or its gradient
    megakernel, equals the ref engine's bit for bit."""
    t = choose_tile(N, 4)
    f = compile_expr(sort_expr(N), engine="pallas")
    plans = [ex_plan for s in f.clustered_program(N, t)
             if isinstance(s, FusedStage) and s.computes
             for ex_plan in EX._fused_plan_cached(s, t)[0]]
    assert any(p.intra.steps.transpose and p.intra_inv.steps.transpose
               for p in plans)
    monkeypatch.setattr(EX, "BWD_MEGAKERNEL", megakernel)
    kernels = []
    real = EX._fused_bwd_pallas
    monkeypatch.setattr(EX, "_fused_bwd_pallas",
                        lambda *a: kernels.append(a) or real(*a))
    clear_caches()
    x, w = _x(N, 31), _x(N, 32)
    ref = compile_expr(sort_expr(N), engine="ref")
    g = jax.grad(lambda v: jnp.sum(w * f.call_per_stage(v)))(x)
    want = jax.grad(lambda v: jnp.sum(w * ref(v)))(x)
    assert np.asarray(g).tobytes() == np.asarray(want).tobytes()
    assert bool(kernels) == megakernel
