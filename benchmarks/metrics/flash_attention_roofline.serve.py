"""The causal flash forward's share of its roofline in serving: the larger of
its FLOPs over the chip's bf16 peak (q k^T and p v over the visible pairs
once, every query head) and its bytes over the HBM bandwidth (q read and o
written for every query head, k and v for every key/value head once), one
layer and one sequence (`flops_h1.flash_flops_per_layer` /
`flash_bytes_per_layer`), times the layers and the queries encoded in the
window (the `encode.tokens` counter over the query's length), against the
device time of the operations under the name scope `attn.flash` (the kernel
and whatever relayout XLA left inside the scope). Key/value heads repeated
to the query heads' count are in the time and not in the bytes."""
from benchmarks import flops


def read(ctx):
    scopes = (ctx.get("scope_seconds") or {}).get("scopes", {})
    seconds = scopes.get("attn.flash")
    tokens = (ctx.get("encode_counters") or {}).get("tokens")
    if ctx.get("job") != "serve" or not seconds or not tokens \
            or "flash_flops_per_layer" not in ctx:
        return None
    peaks = flops.peaks_for(ctx["device_kind"])
    least = max(ctx["flash_flops_per_layer"] / peaks["bf16_flops"],
                ctx["flash_bytes_per_layer"] / peaks["hbm_bytes_per_s"])
    work = ctx["attn_layers"] * tokens / ctx["query_tokens"]
    return 100.0 * least * work / seconds
