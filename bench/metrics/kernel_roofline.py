"""Kernel layer: the library's Pallas kernels against the HBM roofline.
Each kernel event is one pass over the chip's share of the array (one
read and one write, ``work.least_bytes_per_chip``); the share is the
time those passes would take at the chip's peak HBM bandwidth over the
summed device time of the events. Device trace."""
import work
import xplane


def read(run):
    trace = run["trace"]
    if not trace or not trace["devices"]:
        return None
    events = [o for d in trace["devices"] for o in d["ops"] if o[3] == "pallas"]
    spent_s = sum(o[2] for o in events) / 1e9
    if not spent_s:
        return None
    least_s = (len(events) * work.least_bytes_per_chip(run["cfg"], run["mix"])
               / run["peak"]["hbm_bytes_per_s"])
    return 100 * least_s / spent_s
