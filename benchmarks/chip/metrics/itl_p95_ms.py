"""95th percentile over every gap between successive tokens of a request
inside the window, over every request (host clock; see
chipbench.window.itl_samples)."""
from chipbench import window


def read(ctx):
    s = window.itl_samples(ctx.record)
    return 1e3 * window.nearest_rank(s, 95) if s else None
