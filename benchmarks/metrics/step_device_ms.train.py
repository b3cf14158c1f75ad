"""Device time of the train step's XLA module per launch, from the trace."""
from benchmarks import trace_reduce


def read(ctx):
    red = ctx.get("reduced")
    name = ctx.get("trace_modules", {}).get("step")
    if ctx.get("job") != "train" or not red or not name:
        return None
    mod = trace_reduce.find_module(red, name)
    if not mod or not mod["launches"]:
        return None
    return 1000.0 * mod["seconds"] / mod["launches"]
