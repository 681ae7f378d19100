"""sort_s (s/sweep): device time of XLA sort operations per sweep -- the
kernel layout build and the remap's compaction -- on the device that
spent most."""
import devtrace


def read(ctx):
    per = devtrace.per_device_seconds(ctx.trace, devtrace.is_sort)
    worst = max(per.values(), default=0.0)
    return worst / ctx.sweeps if worst > 0 else None
