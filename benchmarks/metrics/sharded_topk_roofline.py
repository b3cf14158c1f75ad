"""The exact scan's share of its roofline. HBM bandwidth bounds it: one
launch must read its shard's rows (rows x dim x 2 bytes; the benchmark's
`scan_bytes_per_dispatch`), so the least time is those bytes over the
chip's peak bytes per second; the time taken is the device time of the
scan's XLA module per launch, from the trace."""
from benchmarks import flops, trace_reduce


def read(ctx):
    red = ctx.get("reduced")
    name = ctx.get("trace_modules", {}).get("scan")
    if ctx.get("job") != "serve" or not red or not name:
        return None
    mod = trace_reduce.find_module(red, name)
    if not mod or not mod["launches"] or not mod["seconds"]:
        return None
    bw = flops.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    least = ctx["scan_bytes_per_launch"] / bw
    return 100.0 * least / (mod["seconds"] / mod["launches"])
