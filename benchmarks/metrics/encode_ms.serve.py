"""Host time of tokenize plus the compiled query-tower dispatch (with its
pull to the host), per encode call: PipelineProfiler `tokenize` + `encode`."""


def read(ctx):
    if ctx.get("job") != "serve":
        return None
    n = ctx["stage_counts"].get("encode", 0)
    if not n:
        return None
    s = ctx["stage_seconds"]
    return 1000.0 * (s.get("tokenize", 0.0) + s.get("encode", 0.0)) / n
