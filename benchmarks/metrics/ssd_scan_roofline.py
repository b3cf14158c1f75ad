"""The state-space scan's share of its roofline: the larger of its FLOPs over
the chip's bf16 peak and its bytes over the HBM bandwidth (one sequence, one
Mamba-2 layer: `flops_granitemoehybrid.scan_flops_per_query` /
`scan_bytes_per_query`: within-chunk products over the visible pairs once,
the chunks' states, the carried state's part of the output; reads of X, B, C
and delta, write of Y), times the layers and the queries encoded in the
window (the `encode.tokens` counter over the query's length), against the
device time of the operations under the name scope `mamba.ssd`. Rows of
padding in a bucket are in the time and not in the work."""
from benchmarks import flops


def read(ctx):
    scopes = (ctx.get("scope_seconds") or {}).get("scopes", {})
    seconds = scopes.get("mamba.ssd")
    tokens = (ctx.get("encode_counters") or {}).get("tokens")
    if ctx.get("job") != "serve" or not seconds or not tokens:
        return None
    peaks = flops.peaks_for(ctx["device_kind"])
    least = max(ctx["ssd_flops_per_query"] / peaks["bf16_flops"],
                ctx["ssd_bytes_per_query"] / peaks["hbm_bytes_per_s"])
    work = ctx["mamba_layers"] * tokens / ctx["query_tokens"]
    return 100.0 * least * work / seconds
