"""The routed experts' grouped products' share of their roofline in serving:
per encode call and layer the larger of the products' FLOPs (the call's
assignments on the experts held, `encode.moe_assignments_held`, times
6 x hidden x width) over the chip's bf16 peak and the held experts' three
stacked kernels' bytes over the HBM bandwidth (a call reads them whatever it
routes), summed over the window's encode calls and layers, against the device
time of the operations under the name scope `moe.experts` (three grouped
products and the SwiGLU's elementwise pass between them). At one query a call
the kernels' bytes bound it, at four the MXU."""
from benchmarks import flops


def read(ctx):
    ss = ctx.get("scope_seconds") or {}
    seconds = ss.get("scopes", {}).get("moe.experts")
    calls = ss.get("encode_launches")
    held = (ctx.get("encode_counters") or {}).get("moe_assignments_held")
    if ctx.get("job") != "serve" or not seconds or not calls or not held:
        return None
    peaks = flops.peaks_for(ctx["device_kind"])
    layers = ctx["expert_layers"]
    per_call = max(
        ctx["expert_flops_per_assignment"] * held / calls / layers
        / peaks["bf16_flops"],
        ctx["expert_kernel_bytes_per_call"] / peaks["hbm_bytes_per_s"])
    return 100.0 * per_call * calls * layers / seconds
