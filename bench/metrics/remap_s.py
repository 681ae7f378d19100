"""remap_s (s/sweep): device time of the remaps per sweep -- the ops under
the program's ``remap`` scope in every mode: bucketing by destination, the
all_to_all and the compaction -- on the device that spent most."""
import phases


def read(ctx):
    return phases.slowest_per_sweep(ctx, "remap")
