"""`sweep_roofline.batch` (layer: sweep kernels; unit %; source:
device_trace): the least time the chip needs for the sweeps the trace
holds, over the device time of the program that ran them.

One sweep's algorithmic work, from the cell's D parties and N instances in
float32: the residual matrix R (D x N) read once and its rows written once
(8 D N bytes), and 4 D^2 N flops.  The least time is the larger of bytes
over HBM bandwidth and flops over peak; the f32 work is held against the
bf16 peak, so the compute bound is a lower bound.  The program is the one
with the most device time in the window (the compiled Monte-Carlo batch),
run `trials` x `sweeps` sweeps per call, so its denominator also holds the
batch's data generation and records.  Moves trials_per_s."""
import sys

from bench import trace_reduce


def least_sweep_s(d: int, n: int, peaks: dict) -> float:
    t_mem = 8.0 * d * n / peaks["hbm_bytes_per_s"]
    t_flop = 4.0 * d * d * n / peaks["bf16_flops_per_s"]
    print(f"bench: sweep_roofline bound by "
          f"{'HBM bandwidth' if t_mem >= t_flop else 'compute'} "
          f"({t_mem:.3e} s vs {t_flop:.3e} s per sweep)", file=sys.stderr)
    return max(t_mem, t_flop)


def read(ctx, metric):
    if ctx.peaks is None or not ctx.devices:
        return None
    cfg = ctx.cell.config
    d = len(cfg["data"]["groups"]) if isinstance(
        cfg["data"]["groups"], list) else cfg["data"]["n_attrs"]
    least = least_sweep_s(d, cfg["data"]["n_train"], ctx.peaks)
    mods = [e for e in ctx.devices[0]["modules"] if ctx.lo <= e[1] < ctx.hi]
    by_name = trace_reduce.time_by_name(mods, ctx.lo, ctx.hi)
    if not by_name:
        return None
    top = max(by_name, key=by_name.get)
    runs = [e for e in mods if trace_reduce.base_name(e[0]) == top]
    busy = sum(e[2] for e in runs) * 1e-9
    if busy <= 0:
        return None
    sweeps = len(runs) * ctx.work["trials"] * ctx.work["sweeps"]
    return 100.0 * sweeps * least / busy
