"""Device time in the phase executor's bookkeeping scopes (``ring``:
tick-indexed reads and writes of the rings, the chunk's parameters and
the microbatch; ``grad_accum``: the gradient accumulators; ``wire``:
payload packing and route selection) over the traced window; the mean
over the cell's devices (``bench/scopes.py``)."""
from bench import scopes

LAYER = "phase executor tick bookkeeping (core/pipeline_runtime.py)"
UNIT = "%"
SOURCE = "program_span"
MOVES = "train_tokens_per_s"


def read(ctx):
    if not getattr(ctx, "scopes", None) or not ctx.trace:
        return None
    return scopes.share(ctx.scopes, ctx.trace["window_s"],
                        "tick_bookkeeping_share")
