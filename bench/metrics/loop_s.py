"""loop_s (s/sweep): device time inside XLA while loops per sweep, on the
device that spent most. Today that is ``jnp.searchsorted``'s binary search
over the nonzero stream in the kernel layout build (one loop per search,
each gathering from the whole stream at every step), and the solve's small
SVD loops."""
import devtrace


def read(ctx):
    per = devtrace.per_device_seconds(ctx.trace, devtrace.is_loop)
    worst = max(per.values(), default=0.0)
    return worst / ctx.sweeps if worst > 0 else None
