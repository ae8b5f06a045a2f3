"""Device layer: the share of the traced window in which no op ran on
the chip, averaged over the cell's chips. Device trace."""
import statistics

import xplane


def read(run):
    trace = run["trace"]
    if not trace or not trace["devices"]:
        return None
    lo, hi = xplane.window(trace)
    return statistics.fmean(100 * (1 - xplane.busy_ns(d["ops"]) / (hi - lo))
                            for d in trace["devices"])
