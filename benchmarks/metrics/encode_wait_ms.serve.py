"""Host time blocked on the query tower, per encode call: PipelineProfiler
`encode_wait` (the pull of the vectors inside `encode`, which waits for the
tower's program and copies its output) over `encode` calls. Nothing where
the program does not split `encode`."""


def read(ctx):
    if ctx.get("job") != "serve":
        return None
    n = ctx["stage_counts"].get("encode", 0)
    wait = ctx["stage_seconds"].get("encode_wait")
    if not n or wait is None:
        return None
    return 1000.0 * wait / n
