"""Plain reference of ``out[A i ^ c] = x[i]`` in numpy for a sharded
output, independent of the library: the matrix comes from the mix file
as bit rows (bit k of ``A i`` is the parity of ``i & rows[k]``). Each
shard is compared with its slice of the whole reference, and elements
no shard holds count as mismatches."""
import numpy as np


def dest_index(rows, c: int, n: int) -> np.ndarray:
    """``A i ^ c`` for every index i < 2^n. ``A`` is linear over F2, so
    the image of ``i = hi || lo`` is the XOR of the images of its high
    and low halves: two small tables and one outer XOR."""
    h = n // 2

    def image(shift: int, bits: int) -> np.ndarray:
        v = np.arange(1 << bits, dtype=np.int64) << shift
        out = np.zeros_like(v)
        for k, row in enumerate(rows):
            out |= (np.bitwise_count(v & row) & 1).astype(np.int64) << k
        return out

    return ((image(h, n - h)[:, None] ^ image(0, h)[None, :]) ^ c).reshape(-1)


def permute(x: np.ndarray, rows, c: int) -> np.ndarray:
    out = np.empty_like(x)
    out[dest_index(rows, c, x.shape[0].bit_length() - 1)] = x
    return out


def expected(op, x):
    return permute(x, op["rows"], op["c"])


def control(op, x):
    """The same permutation of the values held in 16 bits: the next
    narrower integer a later change could be tempted to move."""
    return permute(x.astype(np.int16).astype(x.dtype), op["rows"], op["c"])


def compare(shards, want, chips):
    """Elements that differ from the reference, over every shard, plus
    the elements that no shard holds."""
    seen = np.zeros(want.shape[0], dtype=bool)
    bad = 0
    for index, _, data in shards:
        bad += int(np.count_nonzero(data != want[index]))
        seen[index[0]] = True
    return {"mismatches": bad + int(np.count_nonzero(~seen))}
