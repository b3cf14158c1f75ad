"""95th percentile of the time a request sat in the micro-batcher's queue:
the service's own `serve.queue_wait_ms` histogram over its rolling window
(`obs.window_s`, 5 s in the cell), read at the window's close."""


def read(ctx):
    if ctx.get("job") != "serve":
        return None
    return ctx.get("queue_wait_p95_ms")
