"""The dense SwiGLU's share of the chip's bf16 peak in serving: its three
products' FLOPs a token (6 x hidden x width, `flops_h1.mlp_flops_per_token`)
times the tokens encoded in the window (the `encode.tokens` counter) and the
layers, over the peak, against the device time of the operations under the
name scope `mlp` (the products, the gate's elementwise pass and the two
multipliers). The MXU bounds it at a whole page a call."""
from benchmarks import flops


def read(ctx):
    scopes = (ctx.get("scope_seconds") or {}).get("scopes", {})
    seconds = scopes.get("mlp")
    tokens = (ctx.get("encode_counters") or {}).get("tokens")
    if ctx.get("job") != "serve" or not seconds or not tokens \
            or "mlp_flops_per_token" not in ctx:
        return None
    peak = flops.peaks_for(ctx["device_kind"])["bf16_flops"]
    work = ctx["mlp_flops_per_token"] * tokens * ctx["mlp_layers"]
    return 100.0 * work / peak / seconds
