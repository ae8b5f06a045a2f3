"""Device layer: the whole call against the HBM roofline. The least
bytes of a call on one chip (``work.least_bytes_per_chip``) at the
chip's peak HBM bandwidth, over the chip's busy time per call, averaged
over the cell's chips. It counts the same work whatever implements the
call. Device trace."""
import statistics

import work
import xplane


def read(run):
    trace = run["trace"]
    if not trace or not trace["devices"]:
        return None
    calls = len(xplane.calls(trace))
    least_s = (work.least_bytes_per_chip(run["cfg"], run["mix"])
               / run["peak"]["hbm_bytes_per_s"])
    shares = []
    for dev in trace["devices"]:
        busy_s = xplane.busy_ns(dev["ops"]) / 1e9
        if not busy_s:
            return None
        shares.append(100 * least_s / (busy_s / calls))
    return statistics.fmean(shares)
