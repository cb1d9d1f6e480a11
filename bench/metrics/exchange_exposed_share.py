"""Time in collective ops (collective-permute, all-gather, all-reduce)
while no other op runs on the same device, over the window; the mean
over the cell's devices."""
LAYER = "phase executor exchange (core/pipeline_runtime.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"


def read(ctx):
    red = ctx.trace
    if not red or not red.get("devices") or len(ctx.devices) < 2:
        return None
    w = red["window_s"]
    ex = [d["exposed_collective_s"] / w for d in red["devices"].values()]
    return 100.0 * sum(ex) / len(ex)
