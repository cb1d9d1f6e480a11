"""Model FLOPs per token (``bench/flops.py``, recomputation not counted)
times the traced run's tokens per second (the host's clock), over chips
times the bf16 peak."""
LAYER = "model step (launch/steps.make_pipeline_train_step)"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "train_tokens_per_s"


def read(ctx):
    per_tok = ctx.flops.model_flops_per_token(ctx.model,
                                              ctx.traffic["seq_len"])
    peak = ctx.peaks["bf16_flops"] * len(ctx.devices)
    return 100.0 * per_tok * ctx.train_tokens_per_s / peak
