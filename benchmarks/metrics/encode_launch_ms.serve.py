"""Host time to put the ids up and launch the query tower, per encode call:
PipelineProfiler `encode_launch` (inside `encode`, before the pull) over
`encode` calls. Nothing where the program does not split `encode`."""


def read(ctx):
    if ctx.get("job") != "serve":
        return None
    n = ctx["stage_counts"].get("encode", 0)
    launch = ctx["stage_seconds"].get("encode_launch")
    if not n or launch is None:
        return None
    return 1000.0 * launch / n
