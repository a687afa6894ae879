"""Scheduler: mean share of the ``max_batch`` slots that decode in each
engine step of the window (the harness's record of the engine's events)."""


def read(ctx):
    steps = ctx.record.window_steps()
    if not steps:
        return None
    rows = sum(len(s.decode_pos) for s in steps)
    return 100.0 * rows / (len(steps) * ctx.record.max_batch)
