"""layout_s (s/sweep): device time of the kernel layout build per sweep --
the ops under the program's ``layout`` scope (``build_block_layout``:
its ``searchsorted`` loops and sorts), inside every mode's ``mttkrp`` --
on the device that spent most."""
import phases


def read(ctx):
    return phases.slowest_per_sweep(ctx, phases.LAYOUT)
