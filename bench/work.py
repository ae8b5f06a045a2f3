"""The least work of a call, from its shapes: a permutation, a sort or
an FFT of the array has to read every element once and write it once,
so a call moves at least twice the array's bytes through HBM. One pass
of a Pallas kernel over the array moves the same."""
import numpy as np


def array_bytes(cfg: dict, mix: dict) -> int:
    return ((1 << cfg["n"]) * mix.get("channels", 1)
            * np.dtype(cfg["dtype"]).itemsize)


def least_bytes(cfg: dict, mix: dict) -> int:
    """Bytes one call has to move: one read and one write of the array."""
    return 2 * array_bytes(cfg, mix)


def least_bytes_per_chip(cfg: dict, mix: dict) -> float:
    """The same for one chip's shard."""
    return least_bytes(cfg, mix) / cfg["chips"]
