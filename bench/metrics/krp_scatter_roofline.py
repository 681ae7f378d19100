"""krp_scatter_roofline (%): the least time the ``krp_scatter`` calls in
the traced window could take (``krp_work.least_seconds`` of each call,
from nnz, R and the call's output rows, at the published HBM peak), over
the device time they took; the lowest share over the chips. Nothing where
no mode takes a row-gathering rung."""
import devtrace
import krp_work


def read(ctx):
    shares = []
    for ops in ctx.trace.devices.values():
        least = spent = 0.0
        for o in ops:
            rows = krp_work.output_rows(o.name)
            if rows is None or not devtrace.is_pallas(o):
                continue
            least += krp_work.least_seconds(rows, ctx.nnz // ctx.chips,
                                            ctx.rank, ctx.device_kind)
            spent += o.dur * 1e-9
        if spent > 0:
            shares.append(100.0 * least / spent)
    return min(shares) if shares else None
