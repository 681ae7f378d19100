"""mttkrp_s (s/sweep): device time of the MTTKRP per sweep -- the ops
under the program's ``mttkrp`` scope in every mode: the layout build, the
kernel and the rung's XLA row gathers -- on the device that spent most."""
import phases


def read(ctx):
    return phases.slowest_per_sweep(ctx, "mttkrp")
