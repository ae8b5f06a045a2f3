"""Serving driver: batched prefill + greedy decode with a KV cache.

Usage::

    python -m repro.launch.serve --arch mistral-nemo-12b --tokens 32

``--telemetry`` enables :mod:`repro.obs`: per-request (= per decode
step) latency histograms labeled warm/cold — the first decode call pays
the jit compile, and lumping it in with steady-state latency hid every
warm-path regression — plus the executor's dispatch counters, rendered
with ``obs.report()`` at exit. ``--trace OUT.json`` additionally writes
the Chrome trace.

``--validate`` turns on :mod:`repro.guard` for the whole run (ring 1
always-on validation plus ring-2 guarded dispatch, DESIGN.md §14).
Guard resolution is per request: after each prefill/decode step the
accumulated trap/fallback counters are checked and recovered
degradations are reported.

Failure handling is the resilience layer's request lifecycle
(DESIGN.md §16), not process abort: every prefill/decode step runs
under :func:`repro.resilience.run_with_policy` — retryable
:class:`~repro.guard.GuardError`\\ s get ``--retries`` bounded retries
with deterministic backoff inside the optional ``--deadline-ms``
budget, and an exhausted/terminal failure becomes a **structured
per-request error result** (printed, counted) while the process keeps
draining. At drain the full summary always prints (decode report +
guard/store/resilience counters) and ``--error-budget`` decides the
exit code: more request errors than the budget exits 1. SIGTERM is
graceful drain — the loop finishes its in-flight decode step, reports
``drained:``, and still prints the complete summary with exit 0.

``--store PATH`` points the process at a durable plan store
(DESIGN.md §15): compiled permutation plans load from disk instead of
re-planning on boot, every loaded plan re-audits through ring 1, and
per-request ``store.hit/miss/quarantined`` deltas print next to the
guard resolution report. ``examples/serve_batch.py`` drives the cold
vs disk-warm first-request comparison end to end.
"""
from __future__ import annotations

import argparse
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import guard, obs, resilience, store as _store
from ..configs import get_config, reduce_for_smoke
from ..models import model as M
from ..models.layers import init_params
from .compile_cache import setup_compile_cache


def _guard_resolve(where: str, base: dict) -> dict:
    """Per-request guard resolution: report counter deltas since
    ``base`` (recovered degradations stay a warning; the raising path
    never reaches here — the typed error aborts in ``main``). Returns
    the new baseline."""
    now = guard.stats()
    trapped = (sum(now["traps"].values())
               - sum(base["traps"].values()))
    recovered = now["recovered"] - base["recovered"]
    if trapped:
        print(f"guard[{where}]: {trapped} trap(s), "
              f"{recovered} recovered via engine fallback")
    return now


def _store_resolve(where: str, base: dict) -> dict:
    """Per-request plan-store resolution, printed next to the guard
    report: hit/miss/quarantined deltas since ``base``. A quarantine
    is never silent — the corrupt entry was refused, replanned past,
    and left in ``quarantine/`` for post-mortem."""
    now = _store.stats()
    hit = now["hit"] - base["hit"]
    miss = now["miss"] - base["miss"]
    quarantined = now["quarantined"] - base["quarantined"]
    if hit or miss or quarantined:
        extra = (f", {quarantined} QUARANTINED (corrupt entry refused, "
                 f"replanned)" if quarantined else "")
        print(f"store[{where}]: {hit} hit / {miss} miss{extra}")
    return now


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mistral-nemo-12b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry", action="store_true",
                    help="record+print repro.obs latency/dispatch report")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write a chrome://tracing span export (implies "
                         "--telemetry)")
    ap.add_argument("--validate", action="store_true",
                    help="guarded execution (repro.guard): validate "
                         "plans, trap faults in-program, degrade "
                         "pallas->ref; exit nonzero on an unrecovered "
                         "trap")
    ap.add_argument("--error-budget", type=int, default=0, metavar="N",
                    help="max per-request structured errors tolerated "
                         "before the drain exit code goes nonzero "
                         "(default 0: any unrecovered request error "
                         "fails the run — but only after draining and "
                         "printing the full summary)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    metavar="MS",
                    help="per-request deadline budget (attempts + "
                         "retry backoff); an exhausted budget is a "
                         "structured 'deadline' request error")
    ap.add_argument("--retries", type=int, default=1, metavar="N",
                    help="bounded retries of retryable GuardErrors per "
                         "request (deterministic seeded backoff; "
                         "default 1)")
    ap.add_argument("--store", default=None, metavar="PATH",
                    help="durable plan store root (DESIGN.md §15): load "
                         "compiled permutation plans from disk, report "
                         "per-request hit/miss/quarantine deltas")
    ap.add_argument("--head-shuffle", default=None, metavar="ENGINE",
                    choices=("ref", "pallas"),
                    help="enable the BMMC kv-head shuffle through ENGINE "
                         "(needs power-of-two n_kv_heads >= 2); with "
                         "'pallas' the serving path exercises compiled "
                         "permutation plans, so --store traffic is real")
    ap.add_argument("--kv-heads", type=int, default=None, metavar="N",
                    help="override n_kv_heads (power of two; n_heads is "
                         "raised to match if needed) — the smoke configs "
                         "reduce to 2 kv heads, whose 1-bit shuffle is "
                         "identity, so --head-shuffle demos want >= 4")
    args = ap.parse_args(argv)
    setup_compile_cache()
    if args.telemetry or args.trace:
        obs.enable(sync=True)
    if args.validate:
        guard.enable()
    if args.store:
        _store.configure(args.store)
        _store.reset_stats()

    cfg = reduce_for_smoke(get_config(args.arch))
    if args.kv_heads or args.head_shuffle:
        import dataclasses
        repl = {}
        if args.kv_heads:
            repl["n_kv_heads"] = args.kv_heads
            repl["n_heads"] = max(cfg.n_heads, args.kv_heads)
        if args.head_shuffle:
            repl["head_shuffle"] = args.head_shuffle
        cfg = dataclasses.replace(cfg, **repl)
    key = jax.random.PRNGKey(args.seed)
    params = M.init(cfg, key)
    total = args.prompt_len + args.tokens

    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    batch = {"tokens": prompts}
    if cfg.is_encdec or cfg.family == "vlm":
        batch["src"] = jax.random.normal(key, (args.batch, cfg.src_len,
                                               cfg.d_model), cfg.dtype)

    gbase = guard.stats() if args.validate else None
    sbase = _store.stats() if args.store else None

    policy = resilience.RetryPolicy(max_retries=max(0, args.retries),
                                    seed=args.seed)
    deadline_s = args.deadline_ms / 1e3 if args.deadline_ms else None
    errors = []

    def _request(where, fn, request_id):
        """One policied request: bounded retries + deadline; a failure
        becomes a structured, printed result — never a process abort."""
        res = resilience.run_with_policy(fn, policy=policy,
                                         deadline_s=deadline_s,
                                         request_id=request_id)
        if not res.ok:
            errors.append((where, res))
            print(f"request[{where}]: {res.describe()}")
        elif res.retries:
            print(f"request[{where}]: recovered after "
                  f"{res.retries} retry(ies)")
        return res

    # SIGTERM = graceful drain: finish the in-flight decode step, then
    # fall through to the summary with the tokens served so far
    drain = {"sigterm": False}
    try:
        prev_term = signal.signal(
            signal.SIGTERM, lambda *_: drain.update(sigterm=True))
    except ValueError:          # not the main thread (e.g. under tests)
        prev_term = None

    try:
        t0 = time.time()
        with obs.span("serve.prefill", batch=args.batch,
                      prompt_len=args.prompt_len):
            res = _request("prefill",
                           lambda: M.prefill(cfg, params, batch), 0)
            if res.ok and obs.sync_enabled():
                jax.block_until_ready(res.value[0])
        if args.validate:
            gbase = _guard_resolve("prefill", gbase)
        if args.store:
            sbase = _store_resolve("prefill", sbase)
        prefill_s = time.time() - t0
        if not res.ok:
            _summary(args, cfg, None, prefill_s, 0.0, 0, errors)
            raise SystemExit(1)   # nothing decodable without a prefill
        logits, caches = res.value
        # grow caches to the full decode horizon
        caches = M.grow_caches(caches, args.prompt_len, total)
        if obs.enabled():
            obs.observe("serve.request_us", prefill_s * 1e6,
                        phase="prefill", cache="cold")

        decode = jax.jit(
            lambda p, c, t, pos: M.decode_step(cfg, p, c, t, pos),
            donate_argnums=(1,))

        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out_tokens = [tok]
        print(f"serving: decode starting (tokens={args.tokens})",
              flush=True)
        t1 = time.time()
        warm_steps = 0
        for i in range(args.tokens - 1):
            if drain["sigterm"]:
                print(f"drained: SIGTERM after {len(out_tokens)}/"
                      f"{args.tokens} tokens", flush=True)
                break
            with obs.span("serve.decode_step", step=i,
                          cache="cold" if i == 0 else "warm"):
                tr = time.perf_counter_ns()
                res = _request(
                    f"decode step {i}",
                    lambda: decode(params, caches, tok,
                                   jnp.int32(args.prompt_len + i)),
                    i + 1)
                if res.ok and obs.sync_enabled():
                    jax.block_until_ready(res.value[0])
                if res.ok and obs.enabled():
                    # the first decode call carries the jit trace+
                    # compile; label it cold so warm-path latency stays
                    # readable
                    obs.observe("serve.request_us",
                                (time.perf_counter_ns() - tr) / 1e3,
                                phase="decode",
                                cache="cold" if i == 0 else "warm")
            if args.validate:
                gbase = _guard_resolve(f"decode step {i}", gbase)
            if args.store:
                sbase = _store_resolve(f"decode step {i}", sbase)
            if not res.ok:
                # the step's KV cache buffers were donated to the failed
                # attempt — later steps would read freed state, so drain
                # with the tokens served so far; the budget decides the
                # exit code
                break
            logits, caches = res.value
            if i > 0:
                warm_steps += 1
            tok = jnp.argmax(logits[:, -1],
                             axis=-1)[:, None].astype(jnp.int32)
            out_tokens.append(tok)
        decode_s = time.time() - t1
    finally:
        if prev_term is not None:
            signal.signal(signal.SIGTERM, prev_term)

    gen = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)
    _summary(args, cfg, gen, prefill_s, decode_s, warm_steps, errors)
    if len(errors) > args.error_budget:
        raise SystemExit(1)
    return gen


def _summary(args, cfg, gen, prefill_s, decode_s, warm_steps, errors):
    """The drain-time report: always printed in full — on success, on
    drained SIGTERM, and on over-budget failure alike."""
    served = 0 if gen is None else gen.shape[1]
    print(f"arch={cfg.name} batch={args.batch}")
    print(f"prefill: {args.prompt_len} tokens in {prefill_s:.2f}s")
    if warm_steps > 0:
        rate = f"{args.batch * served / max(decode_s, 1e-9):.1f} tok/s"
    else:
        # --tokens 1 (or a first-step failure) times zero warm decode
        # steps; a rate derived from max(decode_s, 1e-9) is nonsense
        rate = "n/a tok/s — no warm decode step timed"
    print(f"decode:  {served}/{args.tokens} tokens in {decode_s:.2f}s "
          f"({rate})")
    if gen is not None:
        print("generated ids (first row):", gen[0][:16])
    if args.validate:
        gs = guard.stats()
        print(f"guard: traps={sum(gs['traps'].values())} "
              f"fallbacks={sum(gs['fallbacks'].values())} "
              f"recovered={gs['recovered']} (all requests validated)")
    if args.store:
        ss = _store.stats()
        st = _store.active()
        print(f"store: hits={ss['hit']} misses={ss['miss']} "
              f"plans_built={ss['plan_built']} "
              f"quarantined={ss['quarantined']} "
              f"({st.entry_count()} entries on disk at {st.root})")
    rs = resilience.stats()
    print(f"resilience: requests={rs['requests']} "
          f"retries={rs['retries']} "
          f"deadline_exceeded={rs['deadline_exceeded']} "
          f"errors={len(errors)} (budget {args.error_budget}) "
          f"breaker={rs['breaker']}")
    if args.trace:
        print(f"trace written to {obs.export_trace(args.trace)}")
    if obs.enabled():
        print(obs.report())


if __name__ == "__main__":
    main()
