"""Every token emitted inside the window over the window's length (host
clock)."""
from chipbench import window


def read(ctx):
    return window.tokens_in_window(ctx.record) / ctx.record.seconds
