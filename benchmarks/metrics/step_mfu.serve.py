"""The whole serving path's share of the chip's bf16 peak: the query
tower's forward FLOPs plus 2 x rows x dim of the scan, per query answered,
over the traced window and the peak."""
from benchmarks import flops


def read(ctx):
    if ctx.get("job") != "serve" or not ctx.get("answered"):
        return None
    peak = flops.peaks_for(ctx["device_kind"])["bf16_flops"]
    rate = ctx["answered"] / ctx["window_s"]
    return 100.0 * rate * ctx["flops_per_query"] / (peak * ctx["chips"])
