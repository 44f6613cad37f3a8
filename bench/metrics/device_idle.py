"""`device_idle.<fit|batch>` (layer: device; unit %; source: device_trace):
100 x (1 - union of device-operation intervals / traced window), averaged
over the cell's chips.  Moves the cell's end-to-end metric (fit_s or
trials_per_s)."""
from bench import trace_reduce


def read(ctx, metric):
    width = ctx.hi - ctx.lo
    if width <= 0 or not ctx.devices:
        return None
    idle = [1.0 - trace_reduce.busy_ns(d, ctx.lo, ctx.hi) / width
            for d in ctx.devices]
    return 100.0 * sum(idle) / len(idle)
