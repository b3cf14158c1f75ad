"""Device time under the name scope `mlp` (the dense SwiGLU: its three
products, the gate and the two multipliers) as a share of the query encode
programs' device time in the traced window."""


def read(ctx):
    ss = ctx.get("scope_seconds") or {}
    seconds = ss.get("scopes", {}).get("mlp")
    whole = ss.get("encode_module_seconds")
    if ctx.get("job") != "serve" or not seconds or not whole:
        return None
    return 100.0 * seconds / whole
