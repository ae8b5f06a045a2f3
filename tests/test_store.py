"""Durable plan store (DESIGN.md §15).

Covers the crash-safety contract end to end: entry codec round trips
(class + fused payloads, reserved measured-cost slot), the corruption
matrix (truncated / bit-flipped / version-skewed / torn entries →
quarantine-or-skew-miss + replan, bitwise parity with fresh planning,
zero silent wrong outputs), a two-process persistence round trip
(phase B compiles zero plans), concurrent reader/writer fuzz, the
quarantine race resolving exactly once, and the bounded identity
memos' eviction + ``cache_stats`` surfacing.
"""
from __future__ import annotations

import os
import subprocess
import sys
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro import guard, store
from repro.combinators.execute import (cache_stats, clear_caches,
                                       compile_expr)
from repro.combinators import vocab as V
from repro.combinators.sort import sort_expr
from repro.core.bmmc import Bmmc
from repro.guard import inject
from repro.guard.validate import IdentityMemo, plan_fingerprint
from repro.kernels import ops, ref
from repro.store import codec


@pytest.fixture()
def tmp_store(tmp_path):
    """A configured throwaway store; restores the prior configuration
    (env-default or none) afterwards so tests are hermetic."""
    prev = store.active()
    st = store.configure(str(tmp_path / "planstore"))
    store.reset_stats()
    clear_caches()
    yield st
    clear_caches()
    store.configure(prev.root if prev is not None else None)


def _plan_key(n: int) -> tuple:
    b = Bmmc.bit_reverse(n)
    t = ops.choose_tile(n, 4)
    return b, t, store.class_key(b.rows, b.c, t)


# ---------------------------------------------------------------------------
# codec round trips
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_entry_roundtrip_class_plan(tmp_store):
    n = 8
    b, t, key = _plan_key(n)
    kernel, payload = ops._build_class_plan(b.rows, b.c, t)
    meta, arrays = codec.encode_class_payload(kernel, payload)
    assert tmp_store.put(key, "class", meta, arrays)
    header, loaded = tmp_store.get(key)
    k2, p2 = codec.decode_class_payload(header["meta"], loaded)
    assert k2 == kernel
    assert plan_fingerprint(k2, p2) == plan_fingerprint(kernel, payload)
    # the reserved autotune slot exists, is empty, and survives rewrite
    assert header["measured_cost"] is None
    assert tmp_store.annotate_cost(key, {"us": 12.5, "t": t})
    header2, _ = tmp_store.get(key)
    assert header2["measured_cost"] == {"us": 12.5, "t": t}


@pytest.mark.tier1
def test_loaded_arrays_are_writable_copies(tmp_store):
    n = 8
    b, t, key = _plan_key(n)
    kernel, payload = ops._build_class_plan(b.rows, b.c, t)
    meta, arrays = codec.encode_class_payload(kernel, payload)
    tmp_store.put(key, "class", meta, arrays)
    _, loaded = tmp_store.get(key)
    for arr in loaded.values():
        arr.flat[0] = arr.flat[0]  # would raise on a read-only view


@pytest.mark.tier1
def test_store_backed_plans_bitwise_equal_fresh(tmp_store):
    """A plan decoded from disk is bitwise the plan a fresh planner
    builds — the parity that makes warm-start behavior-preserving."""
    n = 8
    b, t, _ = _plan_key(n)
    fresh = ops._build_class_plan(b.rows, b.c, t)
    ops._class_plan_cached(b.rows, b.c, t)      # build + write
    ops._class_plan_cached.cache_clear()
    loaded = ops._class_plan_cached(b.rows, b.c, t)  # disk hit
    assert store.stats()["hit"] >= 1
    assert loaded[0] == fresh[0]
    assert plan_fingerprint(*loaded) == plan_fingerprint(*fresh)


# ---------------------------------------------------------------------------
# warm boot: zero plans compiled, end-to-end parity
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_warm_boot_compiles_zero_plans(tmp_store):
    n = 8
    x = jnp.asarray(np.random.default_rng(0).standard_normal(1 << n),
                    dtype=jnp.float32)
    y0 = np.asarray(compile_expr(sort_expr(n))(x))
    cold = store.stats()
    assert cold["plan_built"] > 0 and cold["write"] == cold["plan_built"]
    clear_caches()  # fresh process modulo the disk
    y1 = np.asarray(compile_expr(sort_expr(n))(x))
    warm = store.stats()
    assert np.array_equal(y0, y1)
    assert warm["plan_built"] == 0, "warm boot replanned"
    assert warm["miss"] == 0 and warm["hit"] == cold["plan_built"]


# ---------------------------------------------------------------------------
# corruption matrix
# ---------------------------------------------------------------------------

@pytest.mark.tier1
@pytest.mark.parametrize("kind,mode", [
    ("disk_truncate", "truncate"), ("disk_bitflip", "bitflip"),
    ("disk_version_skew", "skew"), ("disk_torn_write", "torn")])
def test_corruption_matrix(tmp_store, kind, mode):
    n = 6
    b, t, key = _plan_key(n)
    x = jnp.arange(1 << n, dtype=jnp.float32)
    oracle = np.asarray(ref.bmmc_ref(x, b))
    ce = compile_expr(V.bit_reverse(n), optimize=False)
    ce(x)  # populate
    base = store.stats()
    gbase = guard.stats()
    with inject.corrupt_store_entry(tmp_store, key, mode):
        inject._clear_replan_path()
        y = ce(x)
    assert np.array_equal(np.asarray(y), oracle), "SILENT WRONG OUTPUT"
    now = store.stats()
    if mode == "skew":
        assert now["version_skew"] > base["version_skew"]
        assert now["quarantined"] == base["quarantined"]
    else:
        assert now["corrupt"] > base["corrupt"]
        assert now["quarantined"] == base["quarantined"] + 1
        # quarantine mirrors into the guard report
        gnow = guard.stats()
        assert (sum(gnow["store_quarantined"].values())
                == sum(gbase["store_quarantined"].values()) + 1)
        assert tmp_store.quarantined_count() >= 1
    assert now["plan_built"] > base["plan_built"], "no replan happened"


@pytest.mark.tier1
def test_full_disk_fault_matrix():
    r = inject.run_disk_fault_matrix()
    assert r["caught"] == r["injected"] == len(inject.STORE_FAULT_KINDS), \
        r["cases"]


@pytest.mark.tier1
def test_quarantine_race_resolves_once(tmp_store):
    n = 6
    b, t, key = _plan_key(n)
    ops._class_plan_cached(b.rows, b.c, t)
    fresh = ops._build_class_plan(b.rows, b.c, t)
    base = store.stats()
    with inject.corrupt_store_entry(tmp_store, key, "bitflip"):
        results, errs = [], []

        def reader():
            try:
                results.append(store.class_plan_through(
                    b.rows, b.c, t,
                    lambda: ops._build_class_plan(b.rows, b.c, t)))
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=reader) for _ in range(6)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    assert not errs
    now = store.stats()
    assert now["quarantined"] - base["quarantined"] == 1
    want = plan_fingerprint(*fresh)
    assert all(plan_fingerprint(*r) == want for r in results)


# ---------------------------------------------------------------------------
# wrong-key / cross-matrix defense
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_valid_plan_under_wrong_key_is_refused(tmp_store):
    """A bitwise-intact entry copied under another key (hash collision /
    tampering) must not pass: the header key check + ring-1 audit tie
    the payload to the key's matrix."""
    n = 8
    b, t, key = _plan_key(n)
    ops._class_plan_cached(b.rows, b.c, t)
    other = Bmmc.reverse_array(n)
    other_key = store.class_key(other.rows, other.c, t)
    data = tmp_store.read_bytes(key)
    tmp_store.write_bytes(other_key, data)
    base = store.stats()
    got = store.class_plan_through(
        other.rows, other.c, t,
        lambda: ops._build_class_plan(other.rows, other.c, t))
    now = store.stats()
    assert now["quarantined"] > base["quarantined"]
    assert plan_fingerprint(*got) == plan_fingerprint(
        *ops._build_class_plan(other.rows, other.c, t))


# ---------------------------------------------------------------------------
# concurrency fuzz
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_concurrent_reader_writer_fuzz(tmp_store):
    """Readers racing one writer over the same key never see a torn
    entry: every get() is either a miss or a complete, checksummed
    entry (rename atomicity)."""
    n = 8
    b, t, key = _plan_key(n)
    kernel, payload = ops._build_class_plan(b.rows, b.c, t)
    meta, arrays = codec.encode_class_payload(kernel, payload)
    stop = threading.Event()
    bad: list = []

    def writer():
        while not stop.is_set():
            assert tmp_store.put(key, "class", meta, arrays)

    def reader():
        while not stop.is_set():
            try:
                got = tmp_store.get(key)
            except (codec.EntryCorrupt, codec.EntrySkew) as e:
                bad.append(e)
                return
            if got is not None:
                k2, p2 = codec.decode_class_payload(got[0]["meta"], got[1])
                if plan_fingerprint(k2, p2) != plan_fingerprint(
                        kernel, payload):
                    bad.append("fingerprint drift")
                    return

    threads = [threading.Thread(target=writer)] + [
        threading.Thread(target=reader) for _ in range(4)]
    for th in threads:
        th.start()
    import time
    time.sleep(1.0)
    stop.set()
    for th in threads:
        th.join()
    assert not bad, bad


# ---------------------------------------------------------------------------
# two-process persistence round trip
# ---------------------------------------------------------------------------

_PHASE_SCRIPT = r"""
import sys, numpy as np
import jax.numpy as jnp
from repro import store
from repro.combinators.execute import compile_expr
from repro.combinators.sort import sort_expr

store.configure(sys.argv[1])
n = 8
x = jnp.asarray(np.random.default_rng(0).standard_normal(1 << n),
                dtype=jnp.float32)
y = np.asarray(compile_expr(sort_expr(n))(x))
np.save(sys.argv[3], y)
s = store.stats()
if sys.argv[2] == "B":
    assert s["plan_built"] == 0, f"phase B compiled plans: {s}"
    assert s["miss"] == 0 and s["hit"] > 0, f"phase B not 100% disk-hit: {s}"
else:
    assert s["plan_built"] > 0 and s["write"] > 0, s
print("OK", s["hit"], s["plan_built"])
"""


@pytest.mark.slow
def test_two_process_persistence_roundtrip(tmp_path):
    root = str(tmp_path / "planstore")
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
           # JAX_PLATFORMS=cpu: without it a scrubbed env lets jax
           # probe real accelerator backends (PR 8: baked-in libtpu
           # stalls ~8 min) and the probe alone blows the timeout
           "JAX_PLATFORMS": "cpu"}
    outs = []
    for phase in ("A", "B"):
        out_npy = str(tmp_path / f"y_{phase}.npy")
        r = subprocess.run(
            [sys.executable, "-c", _PHASE_SCRIPT, root, phase, out_npy],
            capture_output=True, text=True, env=env, timeout=500,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert r.returncode == 0, (phase, r.stderr[-3000:])
        assert "OK" in r.stdout
        outs.append(np.load(out_npy))
    assert np.array_equal(outs[0], outs[1]), \
        "disk-warm process diverged from cold process"


# ---------------------------------------------------------------------------
# bounded identity memos (satellite: no unbounded growth in serving)
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_identity_memo_eviction():
    memo = IdentityMemo(maxsize=4)
    owners = [(i,) for i in range(10)]
    for i, o in enumerate(owners):
        memo.store((id(o), i), o, i)
    assert len(memo) == 4
    # the four youngest survive, oldest evicted
    assert memo.lookup((id(owners[9]), 9), owners[9]) == 9
    assert memo.lookup((id(owners[0]), 0), owners[0]) is None
    hits, misses, maxsize, currsize = memo.cache_info()
    assert maxsize == 4 and currsize == 4


@pytest.mark.tier1
def test_memos_surface_in_cache_stats_and_reset():
    n = 6
    x = jnp.arange(1 << n, dtype=jnp.float32)
    with guard.guarded():
        compile_expr(V.bit_reverse(n))(x)
    stats = cache_stats()
    for key in ("guard_validate_fast", "guard_exec_memo", "store"):
        assert key in stats, key
    assert stats["guard_validate_fast"].currsize >= 1
    assert stats["guard_exec_memo"].currsize >= 1
    clear_caches()
    stats = cache_stats()
    assert stats["guard_validate_fast"].currsize == 0
    assert stats["guard_exec_memo"].currsize == 0
    assert store.stats()["plan_built"] == 0  # session counters reset


@pytest.mark.tier1
@pytest.mark.parametrize("code", [None, "plan-v2"])
def test_version_skew_is_miss_then_heals(tmp_store, code):
    """An entry of another schema, or of the planner generation before
    box sides took ascending slot order (``plan-v2``), is a skew miss
    that the rebuild overwrites."""
    n = 6
    b, t, key = _plan_key(n)
    ops._class_plan_cached(b.rows, b.c, t)
    data = tmp_store.read_bytes(key)
    tmp_store.write_bytes(key, inject._skewed_entry(data, code))
    base = store.stats()
    store.class_plan_through(
        b.rows, b.c, t, lambda: ops._build_class_plan(b.rows, b.c, t))
    now = store.stats()
    assert now["version_skew"] == base["version_skew"] + 1
    assert now["quarantined"] == base["quarantined"]
    assert now["write"] == base["write"] + 1  # rebuilt + overwrote
    # healed: the rewritten entry is current-version and hits
    store.class_plan_through(
        b.rows, b.c, t, lambda: ops._build_class_plan(b.rows, b.c, t))
    assert store.stats()["hit"] == now["hit"] + 1


@pytest.mark.tier1
def test_fused_negative_entry_cached(tmp_store):
    """Unplannable clusters persist as negative entries: a warm boot
    skips the failing planning attempt too (plan_built stays 0)."""
    from repro.combinators import execute as _ex

    n = 8
    x = jnp.asarray(np.random.default_rng(1).standard_normal(1 << n),
                    dtype=jnp.float32)
    ce = compile_expr(sort_expr(n))
    ce(x)
    prog, t = ce._resolve(x, False)
    fused = [s for s in prog if getattr(s, "computes", ())]
    assert fused
    # an off-nominal tile parameter the megakernel may reject
    _ex._fused_plan_cached.cache_clear()
    got_a = _ex._fused_plan_cached(fused[0], t)
    base = store.stats()
    _ex._fused_plan_cached.cache_clear()
    got_b = _ex._fused_plan_cached(fused[0], t)
    now = store.stats()
    assert now["hit"] == base["hit"] + 1 and now["plan_built"] == \
        base["plan_built"]
    assert (got_a is None) == (got_b is None)
