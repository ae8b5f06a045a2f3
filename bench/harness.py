"""One run of one cell of ``BENCHMARK.json``: the benchmark's general
driver, behind ``run.py``.

A cell names a configuration (``configs/<config>.json``: sizes, dtype,
chips) and a traffic mix (``traffic/<mix>.json``: the library entry
point, the calls, how many seeded inputs, the value distribution, the
sampling of outputs and the limits of the comparison). The mix's entry
point is adapted to the library by ``entries/<entry>.py`` and checked
against the plain numpy reference ``reference/<entry>.py``, which
imports nothing of the library. Each per-layer metric is read by
``metrics/<metric>.py``. All of them are found by name, so a new cell,
mix or metric is new files and new entries in ``BENCHMARK.json``.

A run:

1. makes its inputs on the device from the seed, in one jitted call;
2. warms up the cell's own programs (one call each, then one more),
   with the library's telemetry on, to read the kernel classes it
   dispatched, and times the yardstick copies;
3. runs a closed loop of synchronous library calls for ``seconds``,
   round-robin over the mix's calls and cycling over the inputs, and
   ends at the first whole round past ``seconds``. With ``trace`` a
   profiler trace covers a steady sub-window of it;
4. keeps the outputs of a sample of the window's calls, drawn from the
   seed, and compares them with the reference once the window has
   closed and the memory peak has been read.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import re
import shutil
import statistics
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_AFTER_S = 1.0          # the traced sub-window starts this far in
COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE = "/jax/core/compile/jaxpr_trace_duration"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, imported by its path."""
    path = BENCH / kind / f"{name}.py"
    mod_name = f"bench_{kind}_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(workload: str) -> dict:
    """Everything ``BENCHMARK.json`` and the cell's files say about it."""
    spec = load_json(ROOT / "BENCHMARK.json")
    found = [w for w in spec["workloads"] if w["name"] == workload]
    if not found:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = found[0]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])

    def here(metrics):
        return [m for m in metrics if workload in m.get("workloads", [workload])]

    return {"workload": w, "cfg": load_json(ROOT / conf["file"]),
            "mix": load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
            "end_to_end": here(spec["end_to_end"]),
            "per_layer": here(spec["per_layer"])}


class CompileLog:
    """JAX's own compile and trace spans (wall clock), from its
    monitoring events: backend compiles, persistent-cache reads
    included, and jaxpr traces."""

    def __init__(self):
        self.spans = []

    def __call__(self, event, start, end, **_):
        if event in (COMPILE, TRACE):
            self.spans.append((event, start, end))

    def compile_s(self, lo: float, hi: float) -> float:
        """Length of the union of backend compiles within [lo, hi]."""
        total, reach = 0.0, lo
        for _, a, b in sorted(s for s in self.spans if s[0] == COMPILE):
            a, b = max(a, reach), min(b, hi)
            if b > a:
                total += b - a
                reach = b
        return total

    def count(self, lo: float, hi: float) -> int:
        return sum(lo <= a < hi for _, a, _ in self.spans)


def seed_key(seed: int):
    """A threefry key from any non-negative seed, wider than 32 bits
    included."""
    import jax
    import jax.numpy as jnp
    data = np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(data), impl="threefry2x32")


def array_shape(cfg: dict, mix: dict) -> tuple:
    channels = mix.get("channels", 1)
    return (1 << cfg["n"],) + ((channels,) if channels > 1 else ())


def make_inputs(cfg: dict, mix: dict, seed: int, sharding) -> list:
    """The mix's ``inputs`` arrays, made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp
    shape, dtype = array_shape(cfg, mix), jnp.dtype(cfg["dtype"])
    count, values = mix["inputs"], mix["values"]

    def gen(key):
        out = []
        for k in jax.random.split(key, count):
            if values == "bits":
                bits = jax.random.bits(k, shape, jnp.dtype(f"uint{8 * dtype.itemsize}"))
                out.append(jax.lax.bitcast_convert_type(bits, dtype))
            elif values == "normal":
                out.append(jax.random.normal(k, shape, dtype))
            else:
                raise ValueError(f"unknown value distribution {values!r}")
        return out

    shardings = None if sharding is None else [sharding] * count
    return jax.block_until_ready(
        jax.jit(gen, out_shardings=shardings)(seed_key(seed)))


def copy_rates(x, chips: int) -> dict:
    """GB/s of the paper's yardsticks on one input: a plain XLA
    elementwise pass (one read, one write) and, on one chip, the
    library's ``bmmc_copy`` kernel. Each is 20 calls enqueued back to
    back and waited for once."""
    import jax
    from repro.kernels.bmmc_permute import copy_through_vmem
    fns = {"xla_copy": jax.jit(lambda v: v + v.dtype.type(1))}
    if chips == 1:
        fns["bmmc_copy"] = jax.jit(copy_through_vmem)
    out, reps = {}, 20
    for name, fn in fns.items():
        jax.block_until_ready(fn(x))
        t0 = time.perf_counter()
        for _ in range(reps):
            y = fn(x)
        jax.block_until_ready(y)
        out[name] = 2 * x.nbytes * reps / (time.perf_counter() - t0) / 1e9
    return out


def warm_up(calls, x) -> dict:
    """First and second call of each program, with the library's
    telemetry on while it traces (its ``dispatch.kernel`` counters are
    taken once per compiled program)."""
    import jax
    from repro import obs
    obs.reset()
    obs.enable(sync=False)
    first = second = 0.0
    try:
        for _, fn in calls:
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x))
            t1 = time.perf_counter()
            jax.block_until_ready(fn(x))
            first += t1 - t0
            second += time.perf_counter() - t1
        kernels = {k: int(v) for k, v in obs.kernel_counts().items()}
        fallbacks = int(obs.counter_total("dispatch.fused_fallback"))
    finally:
        obs.disable()
        obs.reset()
    return {"first_s": first, "second_s": second, "kernels": kernels,
            "fused_fallbacks": fallbacks}


class Window:
    """The closed loop. Call k runs op ``k % ops`` on input
    ``(k // ops) % inputs``. For each (op, input) slot the output of
    one occurrence, drawn from the seed among the first
    ``sample_depth``, is kept for the comparison (the last one where
    the window ends before it)."""

    def __init__(self, calls, xs, mix, seed):
        self.calls, self.xs = calls, xs
        rng = np.random.default_rng([seed, 1])
        slots = len(calls) * len(xs)
        self.pick = rng.integers(0, mix["sample_depth"], size=slots)
        self.kept = {}
        self.enqueue_s, self.latency_s, self.start_s = [], [], []
        self.traced = (0, 0)

    def run(self, seconds: float, trace_dir=None, trace_s=0.0,
            trace_calls=0):
        import jax
        ops, inputs = len(self.calls), len(self.xs)
        seen = np.zeros(ops * inputs, dtype=np.int64)
        # the traced sub-window: (first call, start time) while on
        state = "wanted" if trace_dir else "none"
        on = (0, 0.0)
        ann = jax.profiler.TraceAnnotation if trace_dir else contextlib.nullcontext
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the harness's spans, not every call
        gc_s = [0.0, 0.0]

        def gc_clock(phase, info):
            gc_s[phase == "stop"] += time.perf_counter()

        gc.callbacks.append(gc_clock)
        start = time.perf_counter()
        self.wall_start = time.time()
        k = 0
        while True:
            op, inp = k % ops, (k // ops) % inputs
            slot = op * inputs + inp
            with ann("bench.call"):
                t0 = time.perf_counter()
                with ann("bench.enqueue"):
                    y = self.calls[op][1](self.xs[inp])
                t1 = time.perf_counter()
                with ann("bench.wait"):
                    jax.block_until_ready(y)
                t2 = time.perf_counter()
            self.enqueue_s.append(t1 - t0)
            self.latency_s.append(t2 - t0)
            self.start_s.append(t0 - start)
            if seen[slot] <= self.pick[slot]:
                self.kept[slot] = y
            seen[slot] += 1
            del y
            k += 1
            if k % ops:
                continue
            now = time.perf_counter() - start
            if state == "wanted" and now >= TRACE_AFTER_S:
                jax.profiler.start_trace(str(trace_dir), profiler_options=options)
                state, on = "on", (k, now)
            elif state == "on" and (now - on[1] >= trace_s
                                    and k - on[0] >= trace_calls):
                jax.profiler.stop_trace()
                state, self.traced = "done", (on[0], k)
            if now >= seconds and state in ("none", "done"):
                break
        self.seconds = time.perf_counter() - start
        self.wall_end = time.time()
        gc.callbacks.remove(gc_clock)
        self.gc_s = gc_s[1] - gc_s[0]
        self.count = k
        return self


def host_shards(y) -> list:
    """(index, device id, numpy data) of every addressable shard."""
    return [(s.index, s.device.id, np.asarray(s.data))
            for s in y.addressable_shards]


def compare(ref, mix, xs_host, kept, chips, control=False):
    """(worst number of each kind, outputs that failed a limit) over
    the kept outputs. With ``control`` the reference's lower-precision
    twin stands in for the program's outputs."""
    inputs = len(xs_host)
    worst, failed = {}, 0
    for slot, shards in sorted(kept.items()):
        op = mix["ops"][slot // inputs]
        x = xs_host[slot % inputs]
        want = ref.expected(op, x)
        if control:
            shards = [((slice(None),), -1, ref.control(op, x))]
        nums = ref.compare(shards, want, 1 if control else chips)
        if any(v > mix["limits"][k] for k, v in nums.items()):
            failed += 1
        for key, v in nums.items():
            worst[key] = max(worst.get(key, v), v)
    return worst, failed


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float | None = None, require_chip: bool = True,
             overrides: dict | None = None, wrap=None, trace_out=None,
             log=print) -> dict:
    """One run; returns the result line's object. For tests only:
    ``overrides`` replaces keys of the cell's ``cfg`` and ``mix``, and
    ``wrap`` wraps each library call, to break it on purpose."""
    t_start = time.perf_counter() if t_start is None else t_start
    c = cell(workload)
    cfg, mix = dict(c["cfg"]), dict(c["mix"])
    for key, value in (overrides or {}).items():
        (cfg if key in cfg else mix)[key] = value
    chips = c["workload"]["chips"]

    import jax
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    kind = devices[0].device_kind
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if require_chip and kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    used = devices[:chips]

    clog = CompileLog()
    jax.monitoring.register_event_time_span_listener(clog)
    try:
        wall0 = time.time() - (time.perf_counter() - t_start)
        entry = load_module("entries", mix["entry"])
        calls, sharding = entry.build(cfg, mix, used)
        if wrap is not None:
            calls = [(name, wrap(fn)) for name, fn in calls]
        t0 = time.perf_counter()
        xs = make_inputs(cfg, mix, seed, sharding)
        inputs_s = time.perf_counter() - t0
        warm_wall = time.time()
        warm = warm_up(calls, xs[0])
        warm_compile_s = clog.compile_s(warm_wall, time.time())
        copies = copy_rates(xs[0], chips)
        win = Window(calls, xs, mix, seed)
        # what set-up left on the heap (traced programs, plans) is kept
        # out of the window's garbage collections
        gc.collect()
        gc.freeze()
        # a first run writes the compile cache: flush it now, not in the window
        os.sync()
        setup_s = time.perf_counter() - t_start
        compile_s = clog.compile_s(wall0, time.time())
        # what the first calls spent beyond a warm call, less compiling:
        # tracing, the library's planning and its audits
        trace_plan_s = warm["first_s"] - warm["second_s"] - warm_compile_s
        log("bench " + json.dumps({
            "workload": workload, "seed": seed, "shape": list(array_shape(cfg, mix)),
            "dtype": cfg["dtype"], "kernels": warm["kernels"],
            "fused_fallbacks": warm["fused_fallbacks"],
            "setup_s": setup_s, "inputs_s": inputs_s, "compile_s": compile_s,
            "trace_plan_s": trace_plan_s, "yardstick_gbps": copies}), flush=True)

        trace_dir = Path(tempfile.mkdtemp(prefix="bench_trace_")) if trace else None
        try:
            win.run(seconds, trace_dir, mix["trace_seconds"], mix["trace_min_calls"])
            compiles = clog.count(win.wall_start, win.wall_end)
            peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in used)
            reduced = None
            if trace_dir:
                import xplane
                path = next(trace_dir.rglob("*.xplane.pb"))
                reduced = xplane.extract(str(path))
                if trace_out:
                    Path(trace_out).mkdir(parents=True, exist_ok=True)
                    with open(Path(trace_out) / "inventory.json", "w") as f:
                        json.dump(xplane.inventory(str(path)), f, indent=1)
        finally:
            gc.unfreeze()
            if trace_dir:
                shutil.rmtree(trace_dir, ignore_errors=True)
    finally:
        jax.monitoring.unregister_event_time_span_listener(clog)

    lat = sorted(win.latency_s)
    # where a run lost time: its slowest call, and its longest pause
    # between two calls, each with where it began in the window
    starts, spans = np.asarray(win.start_s), np.asarray(win.latency_s)
    ends = starts + spans
    pauses = np.append(starts[1:] - ends[:-1], 0.0)
    slow, pause = int(spans.argmax()), int(pauses.argmax())
    log("bench " + json.dumps({
        "calls": win.count, "window_s": win.seconds, "compiles_in_window": compiles,
        "latency_ms": {"p50": 1e3 * lat[len(lat) // 2],
                       "p95": 1e3 * lat[min(len(lat) - 1, int(0.95 * len(lat)))]},
        "enqueue_us_mean": 1e6 * statistics.fmean(win.enqueue_s),
        "in_calls_s": sum(win.latency_s), "gc_s": win.gc_s,
        "slowest_call": {"ms": 1e3 * spans[slow], "at_s": starts[slow]},
        "longest_pause": {"ms": 1e3 * pauses[pause], "at_s": ends[pause]},
        "kept_outputs": len(win.kept)}), flush=True)

    import work
    least = work.least_bytes(cfg, mix)
    e2e = {"perm_gbps": least * win.count / win.seconds / 1e9,
           "peak_hbm_gib": peak / 2**30,
           "setup_s": setup_s}
    metrics = {}
    breakdown = None
    if not trace:
        for m in c["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        lo, hi = win.traced
        run = {"trace": reduced, "cfg": cfg, "mix": mix, "peak": peaks.get(kind, {}),
               "setup": {"compile_s": compile_s, "trace_plan_s": trace_plan_s},
               "enqueue_s": win.enqueue_s[lo:hi]}
        for m in c["per_layer"]:
            value = load_module("metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if trace_out:
            with open(Path(trace_out) / "run.json", "w") as f:
                json.dump({"workload": workload, "source": f"{kind}, seed {seed}",
                           **{k: run[k] for k in ("peak", "setup", "enqueue_s", "trace")},
                           "metrics": metrics}, f)
        if reduced and reduced["devices"]:
            import xplane
            breakdown = xplane.breakdown(reduced)
            lo_ns, hi_ns = xplane.window(reduced)
            busy = statistics.fmean(xplane.busy_ns(d["ops"])
                                    for d in reduced["devices"])
            window_s, busy_s = (hi_ns - lo_ns) / 1e9, busy / 1e9
        else:
            window_s = busy_s = 0.0

    # the comparison, once the window has closed and the peak is read
    kept = {slot: host_shards(y) for slot, y in win.kept.items()}
    attempted = win.count
    win.kept.clear()
    xs_host = [np.asarray(x) for x in xs]
    del xs, win, calls
    ref = load_module("reference", mix["entry"])
    t0 = time.perf_counter()
    worst, failed = compare(ref, mix, xs_host, kept, chips)
    log("bench " + json.dumps({"compared_outputs": len(kept), "failed_outputs": failed,
                               "compare_s": time.perf_counter() - t0}), flush=True)
    checks = {k: {"value": v, "limit": mix["limits"][k]} for k, v in worst.items()}
    correct = bool(kept) and failed == 0 and set(worst) == set(mix["limits"])

    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    if trace:
        device.update(busy_s=busy_s, window_s=window_s)
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
