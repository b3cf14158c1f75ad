"""Share of the window in which the serve front's one dispatcher thread was
answering a batch: PipelineProfiler `dispatch` seconds (the `serve.dispatch`
span, opened on the `serve-batcher` thread for every batch that passed the
door) over the window. The seconds are read once every answer is in, so the
batches that finish just past the close are counted too."""


def read(ctx):
    busy = ctx.get("stage_seconds", {}).get("dispatch")
    if ctx.get("job") != "serve" or busy is None or not ctx.get("window_s"):
        return None
    return 100.0 * busy / ctx["window_s"]
