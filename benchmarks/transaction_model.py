"""Memory-transaction model shared by the paper-table benchmarks.

This model gives the paper's *effective bandwidth* tables from exact
transaction counting, valid on any backend (times come only from runs
on the chip, DESIGN.md §7.4) — the quantity the paper's coalescing
argument is about (§2.2): an uncoalesced access touches a full segment
per element, so

    time(variant) = touched_bytes(variant) / BW
    effective_bw  = copy_time / variant_time

``touched_bytes`` counts, per pass, read-side and write-side segment bytes:
fully-coalesced sides touch exactly the useful bytes; the naive kernel's
scattered side touches ``waste`` segments per warp/segment-width run
(measured exactly per matrix by ``naive_write_runs``). This reproduces the
paper's worst-case bound; hardware caches make measured GPU numbers a bit
kinder (paper: 11x for naive bit-reverse vs our 16.5x bound — same regime).

Two constant sets: the paper's GPU segment model (128 B segments, int32
elements) and the TPU-adapted model (512 B minimum efficient DMA run).
"""
from __future__ import annotations

import dataclasses

from repro.core.bmmc import Bmmc
from repro.core.tiling import naive_write_runs, stats_bmmc


@dataclasses.dataclass(frozen=True)
class HwModel:
    name: str
    seg_bytes: int
    bw: float                 # bytes/s
    itemsize: int = 4

    @property
    def seg_elems(self) -> int:
        return self.seg_bytes // self.itemsize


GPU_RTX4090 = HwModel("rtx4090-paper", seg_bytes=128, bw=1008e9)
TPU_V5E = HwModel("tpu-v5e", seg_bytes=512, bw=819e9)


def copy_time(n: int, hw: HwModel) -> float:
    nbytes = (1 << n) * hw.itemsize
    return 2 * nbytes / hw.bw  # read + write


def naive_time(bmmc: Bmmc, hw: HwModel, sample: int = 256) -> float:
    """Naive kernel: coalesced read, scattered write (paper §4 pre-tiling)."""
    nbytes = (1 << bmmc.n) * hw.itemsize
    waste = naive_write_runs(bmmc, hw.seg_elems, sample_tiles=sample)
    return (nbytes + nbytes * waste) / hw.bw


def tiled_time(bmmc: Bmmc, hw: HwModel, t: int) -> float:
    """Tiled kernel(s): both sides fully coalesced; 2 passes if general."""
    plans = stats_bmmc(bmmc, t)
    nbytes = (1 << bmmc.n) * hw.itemsize
    total = 0.0
    for p in plans:
        # rows are whole segments when 2^t * itemsize >= seg_bytes
        row_bytes = p.row_len * hw.itemsize
        waste = max(1.0, hw.seg_bytes / row_bytes)
        total += 2 * nbytes * waste / hw.bw
    return total


def descriptor_counts(bmmc: Bmmc, t: int) -> dict:
    plans = stats_bmmc(bmmc, t)
    return {
        "passes": len(plans),
        "descriptors": sum(p.dma_descriptors() for p in plans),
        "descriptors_unmerged": sum(
            p.n_tiles * 2 * p.rows_per_tile for p in plans),
    }
