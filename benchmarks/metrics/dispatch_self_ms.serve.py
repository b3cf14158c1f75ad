"""Host time per batch that no stage names: PipelineProfiler `dispatch`
seconds less the seconds of its children `tokenize`, `encode`, `topk`,
`merge` and `format` (those present), per `dispatch`. What is left is the
query block's copy to the device, the grouping by key, the trace grafting
and the calls that wake the client threads."""

CHILDREN = ("tokenize", "encode", "topk", "merge", "format")


def read(ctx):
    n = ctx.get("stage_counts", {}).get("dispatch", 0)
    if ctx.get("job") != "serve" or not n:
        return None
    s = ctx["stage_seconds"]
    named = sum(s.get(c, 0.0) for c in CHILDREN)
    return 1000.0 * (s["dispatch"] - named) / n
