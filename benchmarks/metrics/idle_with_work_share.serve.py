"""Share of the traced window in which the device was idle although the
dispatcher thread had a batch in hand: the trace's idle share less the idle
seconds laid to `serve.batcher_idle` (blocked on an empty queue) or
`serve.batch_window` (waiting out the coalescing window), over the window.
This is the idle that a program change can remove; the rest is idle for
lack of work.

`reduced["idle_gaps"]` lists only the ten largest names: a name that is not
listed counts as 0 s. A program without these spans cannot tell the two
kinds of idle apart, so there is nothing to read."""

WAITING = ("serve.batcher_idle", "serve.batch_window")


def read(ctx):
    red = ctx.get("reduced")
    if ctx.get("job") != "serve" or not red or red["idle_share"] is None \
            or "batcher_idle" not in ctx.get("stage_seconds", {}):
        return None
    waiting_s = sum(s for name, s in red["idle_gaps"] if name in WAITING)
    return 100.0 * (red["idle_share"] - waiting_s / red["window_s"])
