"""idle_share (%): the share of the traced window in which no operation
ran on the device, averaged over the cell's chips."""
import devtrace


def read(ctx):
    if not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - devtrace.busy_s(ctx.trace) / ctx.trace.window_s)
