"""Kernel layer of the four-chip reshard: ``kernel_roofline``'s reading
under the reshard's own name. Every Pallas kernel event of its local
rounds (``bmmc_tile`` and ``bmmc_block`` in its mix) counts as one pass
over the chip's shard at the HBM roofline, over the events' summed
device time. Device trace. It stands apart until ``kernel_roofline``
lists the reshard's cell."""
import harness


def read(run):
    return harness.load_module("metrics", "kernel_roofline").read(run)
