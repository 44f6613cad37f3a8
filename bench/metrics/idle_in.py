"""`idle_in.<span>` (source: device_trace; unit %): the device's idle time
inside the program's host span `<span>`, as a share of the traced window,
averaged over the cell's chips.

Inside is the union of the host events named `<span>` in the window; idle
is that union less its overlap with the union of the device's operations.
`idle_in.batch_fit.launch`, `.wait`, `.fetch` and `.assemble` read the four
phases of `api.batch_fit`, which follow one another, so they add up to the
device idle inside `api.batch_fit`, and `device_idle.batch` less their sum
is the idle outside the program's call.  None where the span does not occur
in the window or the trace has no device plane, so a renamed span shows as
missing rather than as 0.  Moves trials_per_s."""
from bench import trace_reduce


def overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(ctx, metric):
    span = metric["name"].split(".", 1)[1]
    width = ctx.hi - ctx.lo
    if width <= 0 or not ctx.devices:
        return None
    inside = trace_reduce.merge(
        [e for e in ctx.trace["host"] if e[0] == span], ctx.lo, ctx.hi)
    if not inside:
        return None
    length = sum(e - s for s, e in inside)
    idle = [length - overlap_ns(inside,
                                trace_reduce.merge(d["ops"], ctx.lo, ctx.hi))
            for d in ctx.devices]
    return 100.0 * sum(idle) / len(idle) / width
