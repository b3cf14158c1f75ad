"""Host time of the per-shard scan dispatches plus the merge's one pull to
the host, per compiled bucket: PipelineProfiler `topk` + `merge`."""


def read(ctx):
    if ctx.get("job") != "serve":
        return None
    n = ctx["stage_counts"].get("topk", 0)
    if not n:
        return None
    s = ctx["stage_seconds"]
    return 1000.0 * (s.get("topk", 0.0) + s.get("merge", 0.0)) / n
