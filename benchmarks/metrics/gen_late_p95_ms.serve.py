"""How late the load generator ran: 95th percentile of the instant a
request was handed to a client thread minus the instant it was due."""


def read(ctx):
    if ctx.get("job") != "serve":
        return None
    return ctx.get("gen_late_p95_ms")
