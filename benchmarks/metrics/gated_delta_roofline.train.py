"""The chunked gated delta rule's share of its roofline: the larger of its
FLOPs over the chip's bf16 peak and its bytes over the HBM bandwidth
(forward + backward a token and layer, `flops_qwen3_next.
gated_delta_flops_per_token_fb` / `gated_delta_bytes_per_token`), times the
tokens through the recurrence in the window (the step's `gdn/tokens`
counter, every Gated DeltaNet layer, read once the window has closed),
against the device time of the operations under the name scope `gdn.delta`
(beta, the decays, the normalisation of q and k, the rule; recomputed
forwards are in the time and not in the work)."""
from benchmarks import flops


def read(ctx):
    scopes = (ctx.get("scope_seconds") or {}).get("scopes", {})
    seconds = scopes.get("gdn.delta")
    tokens = ctx.get("gdn_tokens")
    if ctx.get("job") != "train" or not seconds or not tokens:
        return None
    peaks = flops.peaks_for(ctx["device_kind"])
    least = max(ctx["gated_delta_flops_per_token"] / peaks["bf16_flops"],
                ctx["gated_delta_bytes_per_token"]
                / peaks["hbm_bytes_per_s"])
    return 100.0 * least * tokens / seconds
