"""solve_s (s/sweep): device time of the least-squares solve and factor
update per sweep -- the ops under the program's ``solve`` scope in every
mode: the solve, the column norms, the normalisation, the factor's
all_gather and its gram -- on the device that spent most."""
import phases


def read(ctx):
    return phases.slowest_per_sweep(ctx, "solve")
