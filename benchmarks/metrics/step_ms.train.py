"""Host time per train step: the window over the steps it completed."""


def read(ctx):
    if ctx.get("job") != "train" or not ctx.get("steps"):
        return None
    return 1000.0 * ctx["window_s"] / ctx["steps"]
