"""How full the grouped product's row tiles are in serving: assignments on
the experts held over the rows of the tiles in use (tiles x 256), over the
window's encode calls and layers; the service's `encode.moe_assignments_held`
and `encode.moe_tiles_used` counters. A tile that holds one row costs the
kernel what a full one costs."""


def read(ctx):
    c = ctx.get("encode_counters") or {}
    if ctx.get("job") != "serve" or not c.get("moe_tiles_used"):
        return None
    return 100.0 * c["moe_assignments_held"] / (
        c["moe_tiles_used"] * ctx["expert_tile_rows"])
