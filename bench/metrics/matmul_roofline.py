"""The matmuls' least time over their device time.

The executed matmul FLOPs and bytes of a step come from its shapes
(``bench/flops.executed_matmul``: projections, head and the sequence
mixer's batched matmuls, the replayed chunk included).  Each group's
least time is the larger of FLOPs over the bf16 peak and bytes over the
HBM bandwidth; their sum over the traced steps, divided by the summed
device time of the ops the trace classes as matmuls on all chips."""
import sys

LAYER = "compute backend (models/backend.py, kernels=xla)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"


def read(ctx):
    red = ctx.trace
    if not red or not red.get("devices"):
        return None
    t = sum(d["class_s"].get("matmul", 0.0) for d in red["devices"].values())
    if t <= 0:
        return None
    pk = ctx.peaks
    least, bounds = 0.0, {}
    for name, (f, b) in ctx.flops.executed_matmul(ctx.model,
                                                  ctx.traffic).items():
        tf, tb = f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"]
        least += max(tf, tb) * ctx.steps
        bounds[name] = "compute" if tf >= tb else "memory"
    print(f"matmul_roofline: least {least:.6f} s over matmul device time "
          f"{t:.6f} s; bound by {bounds}", file=sys.stderr)
    return 100.0 * least / t
