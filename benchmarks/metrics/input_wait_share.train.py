"""Share of the window the train loop stood waiting for a host batch:
PipelineProfiler `produce_wait` seconds (consumer side) over the window."""


def read(ctx):
    if ctx.get("job") != "train" or not ctx.get("window_s"):
        return None
    return 100.0 * ctx["stage_seconds"].get("produce_wait", 0.0) \
        / ctx["window_s"]
