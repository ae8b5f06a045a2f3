"""Plain reference of the sort: ``np.sort``."""
import ml_dtypes
import numpy as np


def expected(op, x):
    return np.sort(x)


def control(op, x):
    """The sort of the values rounded to bfloat16, the next precision
    below the configuration's float32."""
    return np.sort(x.astype(ml_dtypes.bfloat16)).astype(x.dtype)


def compare(shards, want, chips):
    bad = sum(int(np.count_nonzero(data != want[index]))
              for index, _, data in shards)
    return {"mismatches": bad}
