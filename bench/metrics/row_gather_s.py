"""row_gather_s (s/sweep): device time, per sweep, of the XLA work that
builds the row-gathering rungs' kernel operand -- the ops under the
program's ``row_gather`` scope (``mttkrp_device_step``: the factor-row
takes, their product with the values, the rank padding and the
block alignment), inside every such mode's ``mttkrp`` -- on the device
that spent most. Nothing where no mode takes such a rung."""
import phases


def read(ctx):
    return phases.slowest_per_sweep(ctx, "row_gather")
