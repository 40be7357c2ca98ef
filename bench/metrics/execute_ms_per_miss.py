"""Executor: wall time of the pipeline's ``execute`` stage, counted once per
submit (a shared scan charges its whole time to every request it serves),
summed over the window and divided by the misses, in ms."""


def read(ctx):
    misses = sum(s["misses"] for s in ctx.submits)
    if not misses:
        return None
    return sum(s["execute_ms"] for s in ctx.submits) / misses
