"""Device time in the program's ``replay`` scope (the chunk forward that
a backward tick re-runs from the stored boundary inside its
``jax.vjp``, and ``jax.checkpoint``'s recompute inside the pullback)
over the traced window; the mean over the cell's devices
(``bench/scopes.py``)."""
from bench import scopes

LAYER = "phase executor activation residency (schedule and recompute)"
UNIT = "%"
SOURCE = "program_span"
MOVES = "train_tokens_per_s"


def read(ctx):
    if not getattr(ctx, "scopes", None) or not ctx.trace:
        return None
    return scopes.share(ctx.scopes, ctx.trace["window_s"], "replay_share")
