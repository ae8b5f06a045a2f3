"""Collectives layer of the four-chip reshard: the least bytes a call
must move between chips at the chips' interconnect peak, over the
device time of the call's collective ops (all-to-alls and collective
permutes). Device trace.

The trace names the all-to-all ``all_to_all`` and ``xplane.kind`` files
it as an ``op``, so the collective ops are chosen here by name as well
as by kind.

The least bytes are those of the elements whose shard changes under
``A i ^ c``, counted from each matrix of the mix, so the yardstick is
the same whatever plan implements the call. Each chip can send at its
peak at once, so a call's least time is its least bytes over the
chips' summed peak; its spent time is the collective ops' device time
per call, averaged over the chips."""
import re

import numpy as np

import harness
import xplane

# Interchip interconnect bandwidth per chip, by device_kind. Source:
# Google Cloud documentation, "TPU v5e": 1,600 Gbps per chip.
ICI_BYTES_PER_S = {"TPU v5 lite": 1600e9 / 8}

# Collective ops by name, in either spelling the trace uses.
_COLLECTIVE = re.compile(
    r"^(all[-_]to[-_]all|collective[-_]permute|all[-_]gather|all[-_]reduce"
    r"|reduce[-_]scatter)")


def is_collective(op) -> bool:
    """Whether a trace op ``[name, start, duration, kind]`` moves data
    between chips."""
    return op[3] == "collective" or bool(_COLLECTIVE.match(op[0]))


def moved_elements(rows, c: int, n: int, chips: int) -> int:
    """Indices i < 2^n whose shard (the top log2(chips) bits) differs
    between i and ``A i ^ c``. The difference is affine in i, so it is
    the XOR of its images on the high and low halves of i, counted
    from the two halves' histograms."""
    s = chips.bit_length() - 1
    nl, h = n - s, n // 2

    def diff(shift: int, bits: int) -> np.ndarray:
        v = np.arange(1 << bits, dtype=np.int64) << shift
        out = v >> nl
        for k in range(s):
            out ^= (np.bitwise_count(v & rows[nl + k]) & 1).astype(np.int64) << k
        return np.bincount(out, minlength=chips)

    hi, lo = diff(h, n - h), diff(0, h)
    stay = sum(int(hi[a]) * int(lo[a ^ (c >> nl)]) for a in range(chips))
    return (1 << n) - stay


def least_bytes_per_call(cfg: dict, mix: dict) -> float:
    """Mean over the mix's calls of the bytes that must change chips."""
    itemsize = np.dtype(cfg["dtype"]).itemsize * mix.get("channels", 1)
    return itemsize * float(np.mean([
        moved_elements(op["rows"], op["c"], cfg["n"], cfg["chips"])
        for op in mix["ops"]]))


def device_kind(peak: dict):
    """The device kind whose row of ``peaks.json`` the run used."""
    table = harness.load_json(harness.BENCH / "peaks.json")["devices"]
    return next((k for k, v in table.items() if v == peak), None)


def read(run):
    trace = run["trace"]
    ici = ICI_BYTES_PER_S.get(device_kind(run["peak"]))
    if not trace or not trace["devices"] or ici is None:
        return None
    spent_ns = sum(o[2] for d in trace["devices"] for o in d["ops"]
                   if is_collective(o))
    if not spent_ns:
        return None
    cfg, calls, chips = run["cfg"], len(xplane.calls(trace)), len(trace["devices"])
    least_s = least_bytes_per_call(cfg, run["mix"]) / (cfg["chips"] * ici)
    return 100 * least_s / (spent_ns / 1e9 / calls / chips)
