"""Device time in the program's ``optimizer`` scope (the gradient mean,
AdamW and the cast back to the model dtype) over the traced window; the
mean over the cell's devices (``bench/scopes.py``)."""
from bench import scopes

LAYER = "optimizer state (optim/adamw.py)"
UNIT = "%"
SOURCE = "program_span"
MOVES = "train_tokens_per_s"


def read(ctx):
    if not getattr(ctx, "scopes", None) or not ctx.trace:
        return None
    return scopes.share(ctx.scopes, ctx.trace["window_s"],
                        "optimizer_share")
