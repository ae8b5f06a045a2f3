"""Program layer: device ops in the traced window per call, averaged
over the cell's chips. Device trace."""
import statistics

import xplane


def read(run):
    trace = run["trace"]
    if not trace or not trace["devices"]:
        return None
    calls = len(xplane.calls(trace))
    return statistics.fmean(len(d["ops"]) / calls for d in trace["devices"])
