"""Whole step: model FLOPs of every token the window's engine steps
processed (decode rows) over the summed wall time of
those ``step()`` calls times the chip's peak bf16 FLOP/s (host clock)."""
from chipbench import work


def read(ctx):
    steps = ctx.record.window_steps()
    wall = sum(s.t1 - s.t0 for s in steps)
    if not steps or wall <= 0.0:
        return None
    flops = 0.0
    for s in steps:
        flops += work.model_flops(ctx.cfgj, len(s.decode_pos), s.decode_pos)
    return 100.0 * flops / (wall * ctx.peaks["bf16_flops_per_s"])
