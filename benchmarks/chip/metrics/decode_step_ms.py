"""Model step: mean device time of one execution of the decode-step program
in the traced slice (device trace)."""


def read(ctx):
    if ctx.trace is None:
        return None
    calls = ctx.trace.module_calls(r"serve_step")
    return 1e3 * sum(c.dur for c in calls) / len(calls) if calls else None
