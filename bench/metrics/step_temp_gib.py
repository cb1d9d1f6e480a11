"""Temporaries of the step compiled for the chip
(``memory_analysis().temp_size_in_bytes``): the activations the schedule
and the recompute policy keep live."""
LAYER = "phase executor activation residency (schedule and recompute)"
UNIT = "GiB"
SOURCE = "program_counter"
MOVES = "hbm_peak_gib"


def read(ctx):
    return ctx.memory.temp_size_in_bytes / 2 ** 30
