"""Causal flash attention's share of the chip's bf16 peak: its FLOPs a step
(forward + backward, visible pairs counted once;
`flops_glm4_moe_lite.flash_flops_per_step`) over the peak, against the
device time a step of the kernels `flash_fwd`, `flash_dq` and `flash_dkv`
(recomputed forwards are in the time and not in the FLOPs). The MXU bounds
it."""
from benchmarks import flops

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def read(ctx):
    kernels = (ctx.get("scope_seconds") or {}).get("kernels", {})
    seconds = sum(kernels.get(k, 0.0) for k in KERNELS)
    if ctx.get("job") != "train" or not seconds or not ctx.get("steps"):
        return None
    peak = flops.peaks_for(ctx["device_kind"])["bf16_flops"]
    least = ctx["flash_flops_per_step"] / peak
    return 100.0 * least / (seconds / ctx["steps"])
