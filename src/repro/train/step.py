"""Train step: loss + grads + (optionally 8-bit) AdamW update.

Pure function of (params, opt_state, batch); gradient accumulation folds
microbatches with a ``lax.scan`` so the peak activation footprint is one
microbatch regardless of global batch.

``loss_fn`` may override the model loss with any ``(params, batch) ->
(loss, parts_dict)`` — e.g. a loss routed through a
:class:`repro.models.permute.PermuteLayer`, so ``jax.grad`` exercises
the pallas BMMC custom VJP inside a full (grads + AdamW) training step.

Telemetry (:mod:`repro.obs`, when enabled): each *eager* step call
records a ``train.step`` span, a ``train.step_us`` latency histogram
entry, and the permute share of the step — the modeled permutation
round trips dispatched while the step traced plus the fraction of step
wall-clock spent in ``program.call`` permute executions. Callers that
``jax.jit`` the returned function still get the trace-time dispatch
counters (they fire while the jaxpr is built); the wall-clock pieces
are skipped under tracing, never measured wrong.

``make_train_step(..., validate=True)`` returns the guarded variant:
the step body runs under :mod:`repro.guard` and eager calls raise a
typed ``GuardTrap`` on a nonfinite loss/grad norm (DESIGN.md §14).
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .. import obs
from ..configs.base import ArchConfig
from ..models import model as M
from ..optim.adamw import AdamWConfig, adamw_init, adamw_update, state_shapes


def _trace_state_clean(tree) -> bool:
    """False when a leaf of ``tree`` is a tracer (the step is being
    traced by an outer ``jit``, not executed)."""
    return not any(isinstance(leaf, jax.core.Tracer)
                   for leaf in jax.tree.leaves(tree))


def _instrument_step(train_step: Callable) -> Callable:
    """Wrap a step fn with per-step telemetry; transparent when obs is
    disabled (one attribute check) or when the wrapper itself is being
    jit-traced (timing a trace is not timing a step)."""

    @functools.wraps(train_step)
    def observed(params, opt_state, batch):
        if not (obs.enabled() and _trace_state_clean(batch)):
            return train_step(params, opt_state, batch)
        rt0 = obs.counter_total("model.round_trips")
        vjp0 = obs.counter_total("model.vjp_round_trips")
        perm0 = sum(s["sum"] for (nm, _), s in obs.histograms().items()
                    if nm == "program.call_us")
        with obs.span("train.step") as sargs:
            t0 = time.perf_counter_ns()
            out = train_step(params, opt_state, batch)
            if obs.sync_enabled():
                jax.block_until_ready(out)
            dur_us = (time.perf_counter_ns() - t0) / 1e3
            sargs["dur_us"] = round(dur_us, 1)
        obs.observe("train.step_us", dur_us)
        rt = obs.counter_total("model.round_trips") - rt0
        if rt:  # permute stages traced/dispatched inside this step
            obs.inc("train.permute_round_trips", rt)
        vjp = obs.counter_total("model.vjp_round_trips") - vjp0
        if vjp:  # backward-rule passes traced/dispatched inside this step
            obs.inc("train.permute_vjp_round_trips", vjp)
        perm_us = sum(s["sum"] for (nm, _), s in obs.histograms().items()
                      if nm == "program.call_us") - perm0
        if perm_us and dur_us > 0:
            # eager CompiledExpr permute calls inside the step: their
            # measured share of the step wall clock
            obs.observe("train.permute_share", perm_us / dur_us)
        return out

    return observed


def _guard_step(train_step: Callable, trap_retries: int = 1) -> Callable:
    """Guarded step variant (DESIGN.md §14): the step body runs with
    :mod:`repro.guard` rings active — plan validation plus guarded
    permute dispatch inside the loss — and each *eager* call resolves a
    step-level health check: a nonfinite loss or gradient norm raises
    the typed :class:`repro.guard.GuardTrap` instead of silently
    poisoning the optimizer state. Under an outer jit trace the
    host-side resolution is skipped (the in-program guards still
    recorded at trace time); the returned metrics are unchanged.

    Transient traps retry (DESIGN.md §16): a *retryable*
    :class:`~repro.guard.GuardError` escaping the step body — e.g. a
    poisoned plan cache that quarantine + replan clears — is retried up
    to ``trap_retries`` times (counted as ``resilience.retry``) before
    it propagates. The step is a pure function of its inputs, so a
    retry is safe; the nonfinite health check is deliberately OUTSIDE
    the retry loop — a nonfinite loss recomputes deterministically on
    the same batch, so retrying it would just re-prove the trap."""
    from .. import guard
    from ..resilience import policy as _rp

    @functools.wraps(train_step)
    def validated(params, opt_state, batch):
        attempt = 0
        while True:
            try:
                with guard.guarded():
                    out = train_step(params, opt_state, batch)
                break
            except guard.GuardError as e:
                if (_rp.classify(e) != _rp.RETRYABLE
                        or attempt >= trap_retries):
                    raise
                attempt += 1
                _rp._record("retries", obs_name="resilience.retry")
        if not _trace_state_clean(out):
            return out
        metrics = out[2]
        bad = [k for k in ("loss", "grad_norm")
               if k in metrics and not bool(jnp.isfinite(metrics[k]))]
        if bad:
            err = guard.GuardTrap(("nonfinite",), "train")
            err.args = (f"guarded train step: nonfinite {bad} — the "
                        f"update would poison the optimizer state",)
            guard._record_trap("nonfinite", "train")
            guard._record_raised(err)
            raise err
        return out

    return validated


def make_train_step(cfg: ArchConfig, mesh=None,
                    opt_cfg: Optional[AdamWConfig] = None,
                    grad_accum: int = 1,
                    loss_fn: Optional[Callable] = None,
                    validate: bool = False,
                    trap_retries: int = 1):
    opt_cfg = opt_cfg or AdamWConfig(state_bits=cfg.opt_bits)

    def loss_of(params, batch):
        if loss_fn is not None:
            return loss_fn(params, batch)
        return M.loss_fn(cfg, params, batch, mesh=mesh)

    def train_step(params, opt_state, batch):
        if grad_accum > 1:
            b = jax.tree.leaves(batch)[0].shape[0]  # custom losses may
            mb = b // grad_accum                    # not carry "tokens"
            micro = jax.tree.map(
                lambda x: x.reshape((grad_accum, mb) + x.shape[1:]), batch)

            def acc(carry, mbatch):
                g_acc, l_acc, p_acc = carry
                (loss, parts), g = jax.value_and_grad(loss_of, has_aux=True)(
                    params, mbatch)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                p_acc = jax.tree.map(jnp.add, p_acc, parts)
                return (g_acc, l_acc + loss, p_acc), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            p_shape = jax.eval_shape(
                lambda p, mb_: loss_of(p, mb_)[1], params,
                jax.tree.map(lambda x: x[0], micro))
            p0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), p_shape)
            (grads, loss, parts), _ = jax.lax.scan(
                acc, (g0, jnp.zeros((), jnp.float32), p0), micro)
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
            loss = loss / grad_accum
            # same metric keys as grad_accum=1: parts averaged over
            # microbatches
            parts = jax.tree.map(lambda v: v / grad_accum, parts)
        else:
            (loss, parts), grads = jax.value_and_grad(loss_of, has_aux=True)(
                params, batch)
        new_params, new_state = adamw_update(params, grads, opt_state, opt_cfg)
        metrics = {"loss": loss, **parts,
                   "grad_norm": jnp.sqrt(sum(
                       jnp.sum(jnp.square(g.astype(jnp.float32)))
                       for g in jax.tree.leaves(grads)))}
        return new_params, new_state, metrics

    step = _instrument_step(train_step)
    if validate:
        step = _guard_step(step, trap_retries=trap_retries)
    return step, opt_cfg


def init_opt(cfg: ArchConfig, params, opt_cfg: Optional[AdamWConfig] = None):
    opt_cfg = opt_cfg or AdamWConfig(state_bits=cfg.opt_bits)
    return adamw_init(params, opt_cfg)


def opt_state_shapes(cfg: ArchConfig, param_shapes,
                     opt_cfg: Optional[AdamWConfig] = None):
    opt_cfg = opt_cfg or AdamWConfig(state_bits=cfg.opt_bits)
    return state_shapes(param_shapes, opt_cfg)
