"""Resilience layer (DESIGN.md §16): breaker state machine properties,
retry/backoff/deadline policy, admission control, the guard-runtime
breaker wiring (zero per-call trap cost while open, counter-verified),
the chaos soak SLOs, and the serve.py SIGTERM graceful-drain contract.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro import guard, resilience
from repro.resilience import breaker, chaos, policy


@pytest.fixture(autouse=True)
def _fresh_resilience():
    """Every test starts and ends with a clean board + zeroed counters
    (and fresh guard stats: breaker tests trip guard counters too)."""
    resilience.reset()
    guard.reset_stats()
    yield
    resilience.reset()
    guard.reset_stats()


def _opened(threshold: int, cooldown: int) -> breaker.Breaker:
    b = breaker.Breaker(threshold, cooldown)
    for _ in range(threshold):
        b.on_failure(False)
    assert b.state == breaker.OPEN
    return b


# ---------------------------------------------------------------------------
# breaker state machine properties
# ---------------------------------------------------------------------------

@pytest.mark.tier1
@settings(max_examples=40, deadline=None)
@given(st.integers(1, 5), st.integers(1, 8), st.integers(0, 255))
def test_no_exit_from_open_before_cooldown(threshold, cooldown, noise):
    """OPEN holds for the full cool-down no matter what outcome
    notifications arrive (shunted calls report against the fallback —
    they must never advance the protected circuit)."""
    b = _opened(threshold, cooldown)
    for i in range(cooldown - 1):
        assert b.decide() == "shunt"
        if noise & (1 << (i % 8)):
            b.on_success(False)
            b.on_failure(False)
        assert b.state == breaker.OPEN
    assert b.decide() == "shunt"   # the cool-down-completing call still
    assert b.state == breaker.HALF_OPEN   # routes away; the NEXT probes


@pytest.mark.tier1
@settings(max_examples=40, deadline=None)
@given(st.integers(1, 4), st.integers(1, 6), st.integers(2, 9))
def test_half_open_admits_exactly_one_probe(threshold, cooldown, calls):
    b = _opened(threshold, cooldown)
    for _ in range(cooldown):
        b.decide()
    decisions = [b.decide() for _ in range(calls)]
    assert decisions[0] == "probe"
    assert all(d == "shunt" for d in decisions[1:])
    assert b.probes == 1


@pytest.mark.tier1
@settings(max_examples=40, deadline=None)
@given(st.integers(1, 4), st.integers(1, 6))
def test_trap_during_probe_reopens(threshold, cooldown):
    b = _opened(threshold, cooldown)
    for _ in range(cooldown):
        b.decide()
    assert b.decide() == "probe"
    b.on_failure(True)
    assert b.state == breaker.OPEN
    assert b.cool_remaining == cooldown    # a FULL fresh cool-down
    assert b.opens == 2
    # ... and the machine still works: cool down again, probe, close
    for _ in range(cooldown + 1):
        b.decide()
    assert b.probe_inflight
    b.on_success(True)
    assert b.state == breaker.CLOSED and b.closes == 1


@pytest.mark.tier1
def test_closed_successes_reset_consecutive_failures():
    b = breaker.Breaker(threshold=3, cooldown=2)
    for _ in range(10):                    # never 3 consecutive
        b.on_failure(False)
        b.on_failure(False)
        b.on_success(False)
    assert b.state == breaker.CLOSED and b.opens == 0


# ---------------------------------------------------------------------------
# breaker board routing
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_board_opens_shunts_probes_and_closes():
    board = breaker.BreakerBoard(threshold=2, cooldown=3)
    for _ in range(2):
        r = board.route("pallas")
        assert r.engine == "pallas" and not r.engaged
        board.on_trap(r, ("oob",))
    assert board.engaged("pallas")
    # open: exactly `cooldown` calls shunt to ref with no accounting
    # against the protected circuit
    for _ in range(3):
        r = board.route("pallas")
        assert r.shunted and r.engine == "ref" and r.requested == "pallas"
        board.on_success(r)                # shunted success: no close
    assert board.engaged("pallas")
    r = board.route("pallas")
    assert r.probe and r.engine == "pallas"
    board.on_success(r)
    assert not board.engaged("pallas")
    s = board.stats()
    assert s == {"open": 1, "probe": 1, "close": 1, "shunt": 3}


@pytest.mark.tier1
def test_board_trapped_probe_reopens_all_half_open():
    board = breaker.BreakerBoard(threshold=1, cooldown=2)
    r = board.route("pallas")
    board.on_trap(r, ("oob", "parity"))    # two circuits open at once
    for _ in range(2):
        assert board.route("pallas").shunted
    r = board.route("pallas")
    assert r.probe
    board.on_trap(r, ("oob",))             # probe traps on ONE kind...
    assert board.engaged("pallas")
    snap = board.snapshot()
    assert snap["pallas/oob"]["state"] == breaker.OPEN
    assert snap["pallas/parity"]["state"] == breaker.OPEN  # ...reopens BOTH


@pytest.mark.tier1
def test_board_never_protects_the_engine_of_last_resort():
    board = breaker.BreakerBoard(threshold=1, cooldown=1)
    r = board.route("ref")
    assert r.engine == "ref" and not r.engaged
    board.on_trap(r, ("oob",))             # ref has nowhere to degrade to
    assert not board.engaged("ref")
    assert board.snapshot() == {}
    fn = len                               # injected engine callables too
    r2 = board.route(fn)
    assert r2.engine is fn and not r2.engaged


# ---------------------------------------------------------------------------
# retry/backoff policy
# ---------------------------------------------------------------------------

@pytest.mark.tier1
@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10_000), st.integers(0, 500), st.integers(0, 6))
def test_backoff_jitter_deterministic_and_bounded(seed, rid, attempt):
    p = policy.RetryPolicy(seed=seed)
    d = p.delay_s(attempt, rid)
    assert d == policy.RetryPolicy(seed=seed).delay_s(attempt, rid)
    cap = min(p.max_delay_s, p.base_delay_s * 2 ** attempt)
    assert cap * (1.0 - p.jitter) <= d <= cap


@pytest.mark.tier1
def test_backoff_decorrelates_requests_under_one_seed():
    p = policy.RetryPolicy(seed=0)
    delays = {p.delay_s(2, rid) for rid in range(16)}
    assert len(delays) == 16


@pytest.mark.tier1
def test_classification_table():
    assert policy.classify(guard.CachePoisoned("x")) == policy.RETRYABLE
    assert policy.classify(guard.GuardTrap(("oob",), "pallas")) \
        == policy.RETRYABLE
    # the step-level nonfinite health check recomputes deterministically
    assert policy.classify(guard.GuardTrap(("nonfinite",), "train")) \
        == policy.TERMINAL
    assert policy.classify(guard.BadInput("x")) == policy.TERMINAL
    assert policy.classify(guard.NotInvertible("x")) == policy.TERMINAL
    assert policy.classify(ValueError("x")) == policy.TERMINAL


def _virtual_clock():
    t = [0.0]
    slept = []

    def sleep(d):
        slept.append(d)
        t[0] += d

    return (lambda: t[0]), sleep, slept


@pytest.mark.tier1
def test_policy_retries_transient_then_succeeds():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise guard.CachePoisoned("transient")
        return 42

    clock, sleep, slept = _virtual_clock()
    pol = policy.RetryPolicy(max_retries=2, seed=1)
    res = policy.run_with_policy(flaky, policy=pol, request_id=9,
                                 clock=clock, sleep=sleep)
    assert res.ok and res.value == 42
    assert res.attempts == 3 and res.retries == 2
    assert slept == [pol.delay_s(0, 9), pol.delay_s(1, 9)]
    assert resilience.stats()["retries"] == 2


@pytest.mark.tier1
def test_policy_terminal_errors_never_retry():
    calls = {"n": 0}

    def bad():
        calls["n"] += 1
        raise guard.BadInput("malformed request")

    clock, sleep, slept = _virtual_clock()
    res = policy.run_with_policy(bad, clock=clock, sleep=sleep)
    assert res.outcome == "error" and res.error_class == policy.TERMINAL
    assert calls["n"] == 1 and slept == []


@pytest.mark.tier1
def test_policy_exhausted_retries_return_structured_error():
    def always():
        raise guard.GuardTrap(("parity",), "pallas")

    clock, sleep, _ = _virtual_clock()
    res = policy.run_with_policy(
        always, policy=policy.RetryPolicy(max_retries=2),
        clock=clock, sleep=sleep)
    assert res.outcome == "error" and res.error_class == policy.RETRYABLE
    assert res.attempts == 3
    assert "GuardTrap" in res.describe()


@pytest.mark.tier1
def test_policy_never_sleeps_into_a_guaranteed_timeout():
    def always():
        raise guard.CachePoisoned("transient")

    clock, sleep, slept = _virtual_clock()
    pol = policy.RetryPolicy(max_retries=5, base_delay_s=10.0,
                             max_delay_s=10.0, jitter=0.0)
    res = policy.run_with_policy(always, policy=pol, deadline_s=1.0,
                                 clock=clock, sleep=sleep)
    assert res.outcome == "deadline" and slept == []
    assert isinstance(res.error, resilience.DeadlineExceeded)
    assert resilience.stats()["deadline_exceeded"] == 1


@pytest.mark.tier1
def test_policy_deadline_checked_between_attempts():
    clock, sleep, _ = _virtual_clock()

    def slow():
        sleep(2.0)                          # attempt burns the budget
        raise guard.CachePoisoned("transient")

    res = policy.run_with_policy(
        slow, policy=policy.RetryPolicy(max_retries=3, jitter=0.0),
        deadline_s=1.0, clock=clock, sleep=sleep)
    assert res.outcome == "deadline" and res.attempts == 1


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_admission_queue_depth_bound_and_release():
    q = policy.AdmissionQueue(max_depth=2)
    assert q.admit() and q.admit()
    assert not q.admit()                   # full -> shed
    assert q.shed == 1 and resilience.stats()["shed"] == 1
    q.complete(0.1)
    assert q.admit() and q.depth == 2


@pytest.mark.tier1
def test_admission_queue_sheds_doomed_backlog():
    # 0.6s/request observed; a 2nd concurrent request could not drain
    # inside the 1s deadline -> shed at admission, not timed out later
    q = policy.AdmissionQueue(max_depth=10, deadline_s=1.0,
                              est_latency_s=0.6)
    assert q.admit()
    assert not q.admit()
    q.complete(0.2)                        # EWMA drops the estimate
    assert q.est_latency_s < 0.6
    assert q.admit()


# ---------------------------------------------------------------------------
# train-step retry integration
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_train_step_retries_transient_trap_then_succeeds():
    from repro.train.step import _guard_step

    calls = {"n": 0}

    def step(p, o, b):
        calls["n"] += 1
        if calls["n"] == 1:
            raise guard.CachePoisoned("poisoned plan cache")
        return p, o, {"loss": jnp.float32(1.0),
                      "grad_norm": jnp.float32(0.5)}

    out = _guard_step(step, trap_retries=1)(1, 2, {})
    assert calls["n"] == 2 and float(out[2]["loss"]) == 1.0
    assert resilience.stats()["retries"] == 1


@pytest.mark.tier1
def test_train_step_nonfinite_is_terminal_not_retried():
    from repro.train.step import _guard_step

    calls = {"n": 0}

    def step(p, o, b):
        calls["n"] += 1
        return p, o, {"loss": jnp.float32(np.nan),
                      "grad_norm": jnp.float32(1.0)}

    with pytest.raises(guard.GuardTrap):
        _guard_step(step, trap_retries=3)(1, 2, {})
    assert calls["n"] == 1                 # health check is outside the
    assert resilience.stats()["retries"] == 0   # retry loop by design
    assert guard.stats()["traps"].get(("nonfinite", "train"), 0) == 1


# ---------------------------------------------------------------------------
# chaos soak (live guarded request loop + scheduled injectors)
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_chaos_soak_pallas_memory_fault_holds_slos():
    rep = chaos.soak(engine="pallas", fault="poison_plan", requests=32,
                     window=(8, 16), threshold=2, cooldown=4)
    assert rep.passed, rep.slo_violations
    assert rep.silent_wrong == 0
    assert rep.faults_injected == 8
    assert rep.faults_caught == rep.faults_injected
    # the breaker arc happened: open -> shunted ref service -> probe ->
    # close, and while open the per-call trap cost was verifiably zero
    assert rep.breaker["open"] >= 1 and rep.breaker["close"] >= 1
    assert rep.shunted > 0 and rep.traps_while_open == 0
    assert rep.recovery_requests is not None
    assert rep.recovery_requests <= rep.recovery_k


@pytest.mark.tier1
def test_chaos_soak_disk_fault_quarantines_and_recovers():
    rep = chaos.soak(engine="pallas", fault="disk_bitflip", requests=14,
                     window=(6, 8), threshold=2, cooldown=4)
    assert rep.passed, rep.slo_violations
    assert rep.silent_wrong == 0 and rep.errors == 0
    assert rep.detected >= 1               # quarantine caught the flip
    assert rep.breaker["open"] == 0        # plan-load healing; the
    # breaker never needed to engage


@pytest.mark.slow
def test_chaos_full_matrix_passes():
    reports = chaos.run_matrix()
    assert len(reports) == 4
    bad = [r.summary() for r in reports if not r.passed]
    assert not bad, bad


# ---------------------------------------------------------------------------
# serve.py drain contract
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_serve_tokens_1_reports_na_throughput(capsys):
    from repro.launch import serve

    gen = serve.main(["--arch", "mistral-nemo-12b", "--batch", "1",
                      "--prompt-len", "4", "--tokens", "1"])
    out = capsys.readouterr().out
    assert gen.shape == (1, 1)
    assert "n/a tok/s" in out
    assert "resilience: requests=1" in out


@pytest.mark.slow
def test_serve_sigterm_drains_gracefully(monkeypatch):
    # the drill's serve child inherits this environment; pin it to the CPU
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    drill = chaos.sigterm_drill()
    assert drill["started"], drill["output"][-2000:]
    assert drill["ok"], drill["output"][-2000:]
