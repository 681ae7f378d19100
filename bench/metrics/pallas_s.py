"""pallas_s (s/sweep): device time of the Pallas kernels (XLA's
``tpu_custom_call``) per sweep, on the device that spent most."""
import devtrace


def read(ctx):
    per = devtrace.per_device_seconds(ctx.trace, devtrace.is_pallas)
    worst = max(per.values(), default=0.0)
    return worst / ctx.sweeps if worst > 0 else None
