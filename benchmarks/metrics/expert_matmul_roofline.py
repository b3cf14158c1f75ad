"""The routed experts' grouped products' share of the chip's bf16 peak:
their FLOPs a step (forward + backward at the expected load of the experts
held; `flops_glm4_moe_lite.expert_matmul_flops_per_step`) over the peak,
against the device time a step of the operations under the name scope
`moe.experts` (three grouped products forward, six backward, the SwiGLU's
elementwise pass between them; recomputed forwards are in the time and not
in the FLOPs). The MXU bounds it."""
from benchmarks import flops


def read(ctx):
    scopes = (ctx.get("scope_seconds") or {}).get("scopes", {})
    seconds = scopes.get("moe.experts")
    if ctx.get("job") != "train" or not seconds or not ctx.get("steps"):
        return None
    peak = flops.peaks_for(ctx["device_kind"])["bf16_flops"]
    least = ctx["expert_flops_per_step"] / peak
    return 100.0 * least / (seconds / ctx["steps"])
