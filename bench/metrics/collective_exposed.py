"""`collective_exposed.mesh` (layer: agent-mesh collectives; unit %;
source: device_trace): the time the chip spends in collectives that no
other operation overlaps, as a share of the traced window, averaged over
the cell's chips.

A collective is an op whose own opcode `trace_reduce.COLLECTIVE` matches:
in the TPU's HLO text (`%psum.9 = f32[..] all-reduce(..)`) the opcode
before its operand list, so an op that merely reads a collective's result
(`.. fusion(%all-reduce.31)`) is not one; a name without HLO text is
matched whole.  The start and done halves of an async collective merge
into one interval.  Exposed is the union of the collectives' intervals
less its overlap with the union of every other op on the same chip, but
for the control-flow ops (`while`, `conditional`, `call`), whose
intervals span the ops of their bodies.  None where no chip shows a
collective in the window, so a mesh program that stops running
collectives shows as missing rather than as 0.  Moves trials_per_s."""
import re

from bench import trace_reduce
from bench.metrics.idle_in import overlap_ns

OPCODE = re.compile(r"(?<![%\w.-])(?:" + trace_reduce.COLLECTIVE.pattern
                    + r")(?:-start|-done)?\(")
CONTROL_FLOW = re.compile(r"(while|conditional|call)(\.\d+)?$")


def is_collective(name: str) -> bool:
    if " = " in name:
        return bool(OPCODE.search(name.split(" = ", 1)[1]))
    return bool(trace_reduce.COLLECTIVE.search(name.lower()))


def exposed_ns(dev: dict, lo: float, hi: float) -> float:
    coll, other = [], []
    for e in dev["ops"]:
        if is_collective(e[0]):
            coll.append(e)
        elif not CONTROL_FLOW.match(trace_reduce.base_name(e[0])):
            other.append(e)
    c = trace_reduce.merge(coll, lo, hi)
    return sum(e - s for s, e in c) - overlap_ns(
        c, trace_reduce.merge(other, lo, hi))


def read(ctx, metric):
    width = ctx.hi - ctx.lo
    if width <= 0 or not ctx.devices:
        return None
    if not any(is_collective(e[0]) and e[1] < ctx.hi
               and e[1] + e[2] > ctx.lo
               for d in ctx.devices for e in d["ops"]):
        return None
    shares = [exposed_ns(d, ctx.lo, ctx.hi) / width for d in ctx.devices]
    return 100.0 * sum(shares) / len(shares)
