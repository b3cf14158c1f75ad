"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    red = ctx.get("reduced")
    if ctx.get("job") != "train" or not red or red["idle_share"] is None:
        return None
    return 100.0 * red["idle_share"]
