"""collective_s (s/sweep): device time of the remap's all-to-all and the
factor update's all-gather per sweep, on the device that spent most."""
import devtrace


def read(ctx):
    per = devtrace.per_device_seconds(ctx.trace, devtrace.is_collective)
    worst = max(per.values(), default=0.0)
    return worst / ctx.sweeps if worst > 0 else None
