"""Ring 2 — checkify-style guarded dispatch (opt-in; DESIGN.md §14).

The guarded executable jits the *inner* whole-program executable
(:func:`~repro.combinators.execute._program_executable` — jit-of-jit,
so the unguarded executable cache still populates under its usual
keys and the per-kernel counters still fire at the inner trace)
together with the probes into ONE outer dispatch returning
``(y, flags)`` — checkify's pattern, with a warm guarded call costing
a single XLA dispatch just like an unguarded one. The flags are an
in-program int32 bitmask — no host sync happens inside the program;
the single ``int(flags)`` readback at the API edge is the resolve
step. Per-call ``program.call`` telemetry is mirrored from the
unguarded path in :func:`_observed_guarded_call`. The probed trap
kinds:

* **OOB descriptor trap** (bit 1): every gather/DMA table the program
  bakes in is bounds-checked *inside the traced program* (the tables
  are trace-time constants, so a clean table's check constant-folds to
  zero — the trap is free unless it fires at trace time, which is
  exactly when a poisoned table would be baked in).
* **NaN/Inf sentinel** (bit 2): compute-bearing float programs flag an
  output nonfinite that the input did not already carry — a compute
  epilogue manufactured it.
* **XOR-parity round-trip probe** (bit 4): for permutation-only
  programs the composed BMMC σ is built offline, and the program's
  claim ``y[σ(i)] == x[i]`` is checked at a deterministic sampled slice
  — ``apply ∘ inverse`` on the sample, with the inverse collapsed
  offline so the probe costs two K-element gathers, not a second pass.

Graceful degradation (the fallback state machine): a trapped "pallas"
call re-dispatches the same program through the guarded "ref" engine —
whose gather table is independent of every pallas plan cache — records
``guard.trap{kind}`` / ``guard.fallback{engine}`` counters, and returns
the recovered result. Only if the fallback traps too does the request
fail loudly: :class:`~.errors.CachePoisoned` when the live plan tables
no longer match their ring-1 fingerprints, :class:`~.errors.GuardTrap`
otherwise.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core.bmmc import Bmmc
from .errors import GuardTrap

TRAP_KINDS = {"oob": 1, "nonfinite": 2, "parity": 4}
_PARITY_SAMPLES = 64


def resolve_flags(mask: int) -> tuple:
    """Decode a flag bitmask into the trap-kind names that fired."""
    return tuple(k for k, bit in sorted(TRAP_KINDS.items()) if mask & bit)


def _trace_state_clean(x) -> bool:
    """False when a leaf of ``x`` is a tracer, i.e. the call sits inside
    an outer ``jit``/``grad``/``vmap`` trace where no flag can be read
    back."""
    return not any(isinstance(leaf, jax.core.Tracer)
                   for leaf in jax.tree.leaves(x))


# ---------------------------------------------------------------------------
# probe construction (host side, per (program, t, engine); tables and
# sample indices are offline — only the checks themselves are traced)
# ---------------------------------------------------------------------------

def _stage_tables(prog, t: int, engine: str):
    """Every (table, exclusive upper bound) pair the resolved program
    will bake into its trace — the OOB trap's audit list."""
    from ..combinators.ir import Perm
    from ..combinators.optimize import FusedStage
    from ..combinators import execute as _ex
    from ..kernels import ops, ref as _ref

    out = []

    def add_tile(plan):
        n_rows = 1 << (plan.n - plan.t)
        out.append((plan.in_rows, n_rows))
        out.append((plan.out_rows, n_rows))
        out.append((plan.xor_low, plan.row_len))
        out.append((plan.src0, plan.rows_per_tile * plan.row_len))

    for st in prog:
        if isinstance(st, Perm):
            if engine == "ref" or t is None:
                out.append((_ref._src_table(st.bmmc.rows, st.bmmc.c),
                            st.bmmc.size))
                continue
            kernel, payload = ops.class_plan(st.bmmc, t)
            if kernel == "block":
                out.append((payload.src_rows, payload.n_rows))
            elif kernel == "lane":
                out.append((payload.src_lane, 1 << payload.t))
            elif kernel != "none":
                for plan in payload:
                    add_tile(plan)
        elif isinstance(st, FusedStage):
            if engine != "pallas" or t is None:
                for ss in st.stages:
                    if hasattr(ss, "bmmc"):
                        out.append((_ref._src_table(ss.bmmc.rows, ss.bmmc.c),
                                    ss.bmmc.size))
                continue
            got = _ex._fused_plan_cached(st, t)
            if got is None:
                continue
            for plan in got[0]:
                add_tile(plan)
    return out


def _program_sigma(prog):
    """The composed input→output BMMC of a permutation-only program
    (``out[σ(i)] = x[i]``), or None for compute-bearing programs."""
    from ..combinators.optimize import is_perm_program

    if not prog or not is_perm_program(prog):
        return None
    sigma = None
    for st in prog:
        b = st.bmmc
        sigma = b if sigma is None else b @ sigma
    return sigma


def _parity_sample(sigma: Bmmc):
    size = sigma.size
    k = min(size, _PARITY_SAMPLES)
    xs = (np.arange(k, dtype=np.int64) * max(1, size // k)) % size
    ys = np.fromiter((sigma.apply(int(i)) for i in xs),
                     dtype=np.int64, count=k)
    return xs.astype(np.int32), ys.astype(np.int32)


def _has_compute(prog) -> bool:
    from ..combinators.ir import Bfly, CmpHalves
    from ..combinators.optimize import FusedStage

    return any(isinstance(st, (CmpHalves, Bfly))
               or (isinstance(st, FusedStage) and st.computes)
               for st in prog)


def _build_probe(prog, t, engine: str, batched: bool):
    """Closure ``(x, y) -> int32 flags`` traced inside the guarded
    executable. All table/sample data is resolved offline here."""
    tables = _stage_tables(prog, t, engine)
    sigma = _program_sigma(prog)
    sample = _parity_sample(sigma) if sigma is not None else None
    check_finite = _has_compute(prog)
    axis = 1 if batched else 0

    def probe(x, y):
        flags = jnp.int32(0)
        oob = jnp.asarray(False)
        for tab, hi in tables:
            ta = jnp.asarray(tab)
            oob = oob | (ta.min() < 0) | (ta.max() >= hi)
        flags = flags | (jnp.int32(TRAP_KINDS["oob"])
                         * oob.astype(jnp.int32))
        if check_finite and jnp.issubdtype(y.dtype, jnp.floating):
            made_bad = ((~jnp.isfinite(y)).any()
                        & jnp.isfinite(x).all())
            flags = flags | (jnp.int32(TRAP_KINDS["nonfinite"])
                             * made_bad.astype(jnp.int32))
        if sample is not None:
            xs, ys = sample
            a = jnp.take(x, jnp.asarray(xs), axis=axis)
            b = jnp.take(y, jnp.asarray(ys), axis=axis)
            eq = a == b
            if jnp.issubdtype(y.dtype, jnp.floating):
                eq = eq | (jnp.isnan(a) & jnp.isnan(b))
            flags = flags | (jnp.int32(TRAP_KINDS["parity"])
                             * (~eq.all()).astype(jnp.int32))
        return flags

    return probe


# ---------------------------------------------------------------------------
# guarded executables
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=256)
def _guarded_executable(prog: tuple, t, engine: str, batched: bool):
    """One jitted ``x -> (y, flags)`` per (program, engine) — the
    program and its probes fused into a single dispatch, flags resolved
    only by the caller.

    The traced body calls the *inner jitted*
    :func:`~repro.combinators.execute._program_executable` rather than
    re-tracing ``run_program`` itself: the inner lru populates under
    the exact unguarded cache key (so ``cache_stats()["program"]`` and
    its batch-size-independence hold with guards on), the per-kernel
    dispatch counters fire at the inner trace exactly as they do with
    guards off, and XLA inlines the nested call — a warm guarded call
    is still one dispatch. The probe's tables are baked at the *outer*
    trace, so a plan cache poisoned after the inner executable warmed
    is still re-read here and trapped."""
    from ..combinators import execute as _ex

    probe = _build_probe(prog, t, engine, batched)

    def run(x):
        y = _ex._program_executable(prog, engine, batched)(x)
        return y, probe(x, y)

    return jax.jit(run)


# Identity-keyed front memo over _guarded_executable, the same trick
# (and the same id-aliasing defense) as validate._VALIDATED_FAST:
# resolved program tuples are stable lru-cached objects, and skipping
# the deep lru-key hash on warm calls is what keeps guarded dispatch
# inside the ≤5% overhead budget. Bounded (LRU) so a long-lived serving
# process can't grow it without limit; cleared alongside the lru caches
# in validate.clear_guard_caches and inject._clear_runtime_only.
from .validate import IdentityMemo as _IdentityMemo  # noqa: E402

_EXEC_MEMO = _IdentityMemo(maxsize=1024)


def _guarded_exec_fast(prog: tuple, t, engine: str, batched: bool):
    key = (id(prog), t, engine, batched)
    hit = _EXEC_MEMO.lookup(key, prog)
    if hit is not None:
        return hit
    ex = _guarded_executable(prog, t, engine, batched)
    _EXEC_MEMO.store(key, prog, ex)
    return ex


def _observed_guarded_call(prog: tuple, t, x, engine: str, batched: bool):
    """Telemetry mirror of
    :func:`~repro.combinators.execute._observed_program_call` for the
    guarded executable: one ``program.call`` span + latency histogram
    per invocation (the executor sites inside fire at trace time only),
    cold/warm labeled by the guarded cache."""
    from ..obs import metrics as _ometrics, trace as _otrace

    with _otrace.span("program.call", engine=engine, stages=len(prog),
                      path="guarded", batched=batched) as sargs:
        t0 = time.perf_counter_ns()
        misses0 = _guarded_executable.cache_info().misses
        y, flags = _guarded_exec_fast(prog, t, engine, batched)(x)
        cold = _guarded_executable.cache_info().misses > misses0
        if _otrace._state.sync:
            jax.block_until_ready(y)
        dur_us = (time.perf_counter_ns() - t0) / 1e3
        sargs["dur_us"] = round(dur_us, 1)
        sargs["cache"] = "cold" if cold else "warm"
    _ometrics.observe("program.call_us", dur_us, engine=engine,
                      cache="cold" if cold else "warm")
    return y, flags


@functools.lru_cache(maxsize=256)
def _guarded_permute_executable(rows: tuple, c: int, t, engine: str,
                                batched: bool):
    """Guarded twin of :func:`repro.kernels.ops.bmmc_permute` for one
    BMMC: kernel dispatch + probes in one jit."""
    from ..combinators.ir import Perm
    from ..kernels import ops

    bmmc = Bmmc(rows, c)
    probe = _build_probe((Perm(bmmc),), t, engine, batched)

    def run(x):
        y = ops.bmmc_permute(x, bmmc, t=t, engine=engine, batched=batched)
        return y, probe(x, y)

    return jax.jit(run)


def _diagnose(prog, t, kinds, engine):
    """Classify an unrecovered trap: poisoned caches get the precise
    :class:`CachePoisoned`; anything else fails as :class:`GuardTrap`."""
    from .errors import CachePoisoned
    from . import validate as _v

    poisoned = _v.check_fingerprints(prog, t)
    if poisoned:
        return CachePoisoned(
            f"guard trap(s) {sorted(kinds)} with {len(poisoned)} plan "
            f"fingerprint mismatch(es) — cached tables were mutated "
            f"after validation: {poisoned[:3]!r}")
    return GuardTrap(kinds, engine)


def _resolve_or_fallback(prog, t, x, engine, batched, run_engine):
    """The fallback state machine: run guarded on ``engine``; on a trap,
    degrade pallas → ref; raise typed only when the last engine traps.

    The resilience breaker board (DESIGN.md §16) fronts the dispatch:
    an open circuit rewrites ``engine`` to its fallback *before* the
    call — one clean ref dispatch, zero per-call trap/fallback cost on
    the condemned engine — and clean/trapped outcomes on the requested
    engine feed the circuit state (shunted outcomes deliberately do
    not: a shunted call's behavior says nothing about pallas health)."""
    from .. import guard as _g
    from ..resilience import breaker as _breaker

    board = _breaker.board()
    route = board.route(engine)
    engine = route.engine      # an open circuit shunts to the fallback
    y, flags = run_engine(engine)(x)
    mask = int(flags)          # the ONE host readback, at the API edge
    if not mask:
        board.on_success(route)
        return y
    kinds = resolve_flags(mask)
    for k in kinds:
        _g._record_trap(k, engine)
    board.on_trap(route, kinds)
    if engine != "ref":
        _g._record_fallback("ref")
        y2, flags2 = run_engine("ref")(x)
        mask2 = int(flags2)
        if not mask2:
            _g._record_recovered()
            return y2
        kinds = resolve_flags(mask2)
        for k in kinds:
            _g._record_trap(k, "ref")
    err = _diagnose(prog, t, kinds, "ref")
    _g._record_raised(err)
    raise err


def guarded_call(prog, t, x, engine, batched: bool):
    """Guarded :class:`~repro.combinators.execute.CompiledExpr` call:
    ring-1 validation (cached), then the guarded executable with
    in-program flags and the pallas → ref → loud-failure machine."""
    from ..combinators import execute as _ex
    from ..obs import trace as _otrace
    from . import validate as _v

    prog = tuple(prog)
    _v.validate_program_fast(prog, t)
    _v.validate_input(x.shape, x.dtype, batched=batched)
    if not isinstance(engine, str) or _ex._has_map(prog):
        # injected engines and user-Map programs stay on the eager
        # unguarded dispatch path (jitting an unknown callable would
        # break the Map contract's trace-tolerance); ring 1 still ran
        return _ex._dispatch_program(prog, x, engine, batched)

    def run_engine(eng):
        if _otrace._state.enabled:
            return lambda xx: _observed_guarded_call(
                prog, t, xx, eng, batched)
        return _guarded_exec_fast(prog, t, eng, batched)

    return _resolve_or_fallback(prog, t, x, engine, batched, run_engine)


def guarded_bmmc_permute(x, bmmc: Bmmc, *, t, engine: str, batched: bool):
    """Guarded :func:`repro.kernels.ops.bmmc_permute`: verify + dispatch
    validation, probes in-program, pallas → ref fallback."""
    from ..kernels import ops
    from . import validate as _v

    _v.verify_bmmc(bmmc)
    _v.validate_input(x.shape, x.dtype, batched=batched, n=bmmc.n)
    teff = ops.choose_tile(bmmc.n, x.dtype.itemsize,
                           x.shape[2 if batched else 1]
                           if x.ndim == (3 if batched else 2) else 1, t)
    if teff is not None and engine == "pallas":
        _v.validate_dispatch(bmmc.rows, bmmc.c, teff)

    def run_engine(eng):
        return _guarded_permute_executable(bmmc.rows, bmmc.c, t, eng,
                                           batched)

    from ..combinators.ir import Perm
    return _resolve_or_fallback((Perm(bmmc),), teff, x, engine, batched,
                                run_engine)
