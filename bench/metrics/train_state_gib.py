"""Arguments of the step compiled for the chip
(``memory_analysis().argument_size_in_bytes``): bf16 weights, fp32
master, mu and nu, and the batch."""
LAYER = "optimizer state (optim/adamw.py)"
UNIT = "GiB"
SOURCE = "program_counter"
MOVES = "hbm_peak_gib"


def read(ctx):
    return ctx.memory.argument_size_in_bytes / 2 ** 30
