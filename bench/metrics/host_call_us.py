"""Entry layer: mean host time of a library call up to its return,
before the wait (the enqueue), over the traced calls. Host clock."""
import statistics


def read(run):
    if not run["enqueue_s"]:
        return None
    return 1e6 * statistics.fmean(run["enqueue_s"])
