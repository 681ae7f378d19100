"""sweep_roofline (%): the least time one sweep's compulsory work could
take on the cell's chips (``work.sweep_least_seconds``, from nnz, shape
and R at the published peaks), over the traced run's time per sweep."""
import work


def read(ctx):
    least = work.sweep_least_seconds(ctx.nnz, ctx.shape, ctx.rank,
                                     ctx.device_kind, ctx.chips)
    return 100.0 * least / (ctx.window_s / ctx.sweeps)
