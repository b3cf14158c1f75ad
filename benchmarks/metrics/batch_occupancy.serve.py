"""Mean coalesced batch over `serve.max_batch`."""


def read(ctx):
    if ctx.get("job") != "serve" or not ctx.get("mean_batch"):
        return None
    return 100.0 * ctx["mean_batch"] / ctx["max_batch"]
