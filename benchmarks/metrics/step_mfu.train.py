"""The whole step's share of the chip's bf16 peak: pages per second of the
traced window, times the benchmark's own matmul FLOPs per pair, over the
peak of this device kind times the chips. Recomputed work is not counted."""
from benchmarks import flops


def read(ctx):
    if ctx.get("job") != "train" or not ctx.get("steps"):
        return None
    peak = flops.peaks_for(ctx["device_kind"])["bf16_flops"]
    rate = ctx["steps"] * ctx["batch"] / ctx["window_s"]
    return 100.0 * rate * ctx["flops_per_pair"] / (peak * ctx["chips"])
