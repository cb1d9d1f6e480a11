"""Idle (stage, tick) entries of the cell's task table (op code 0) over
all entries: a count of the schedule's bubble, not a time."""
LAYER = "schedule and task table (core/schedules.py, core/tasktable.py)"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "train_tokens_per_s"


def read(ctx):
    tab = getattr(ctx.spec, "table", None)
    if tab is None or tab.op.size == 0:
        return None
    return 100.0 * float((tab.op == 0).sum()) / float(tab.op.size)
