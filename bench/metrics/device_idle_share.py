"""Share of the traced window in which no op ran on a device, the mean
over the cell's devices."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"


def read(ctx):
    red = ctx.trace
    if not red or not red.get("devices"):
        return None
    w = red["window_s"]
    idle = [1.0 - d["busy_s"] / w for d in red["devices"].values()]
    return 100.0 * sum(idle) / len(idle)
