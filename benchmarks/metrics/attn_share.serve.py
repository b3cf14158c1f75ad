"""Device time under the name scope `attn` (grouped-query attention: its four
projections, rotary, the flash kernel and the relayouts around it) as a share
of the query encode programs' device time in the traced window."""


def read(ctx):
    ss = ctx.get("scope_seconds") or {}
    seconds = ss.get("scopes", {}).get("attn")
    whole = ss.get("encode_module_seconds")
    if ctx.get("job") != "serve" or not seconds or not whole:
        return None
    return 100.0 * seconds / whole
