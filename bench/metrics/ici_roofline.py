"""`ici_roofline.mesh` (layer: agent-mesh collectives; unit %; source:
device_trace): the least time each chip needs to receive what the agent
mesh's sweeps must deliver to it over the interconnect, over the time its
collectives took, averaged over the cell's chips.

Per sweep of D parties on N instances in float32, each chip receives the
other D-1 parties' residual rows in the sweep's gather ((D-1) N 4 bytes)
and the D-1 candidate rows that the other parties broadcast in their
updates' psums ((D-1) N 4 bytes).  Times calls x trials x sweeps of the
window (`ctx.work`), over the ICI peak (`ici_bits_per_s` / 8 of
bench/peaks.json), over the union of the chip's collectives (told from
the ops that read their results as `collective_exposed.mesh` tells them;
async halves merge).  The bytes are a lower bound (the record's gather and
every psum's other elements are left out), so the share cannot pass 100 %
while the trace holds every collective.  None where no peaks are known or no chip shows a
collective.  Moves trials_per_s."""
from bench import trace_reduce
from bench.metrics.collective_exposed import is_collective


def least_receive_s(d: int, n: int, peaks: dict) -> float:
    """Least seconds one chip needs to receive one sweep's rows."""
    return 2.0 * (d - 1) * n * 4 / (peaks["ici_bits_per_s"] / 8.0)


def read(ctx, metric):
    if ctx.peaks is None or not ctx.devices:
        return None
    data = ctx.cell.config["data"]
    least = least_receive_s(len(data["groups"]), data["n_train"], ctx.peaks)
    sweeps = ctx.work["calls"] * ctx.work["trials"] * ctx.work["sweeps"]
    shares = []
    for dev in ctx.devices:
        busy = sum(e - s for s, e in trace_reduce.merge(
            [op for op in dev["ops"] if is_collective(op[0])],
            ctx.lo, ctx.hi)) * 1e-9
        if busy > 0:
            shares.append(sweeps * least / busy)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
