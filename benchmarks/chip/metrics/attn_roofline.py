"""Kernels: least time of the paged-attention work of the traced steps at
the chip's peaks over the device time of its ops, the combine epilogue
included (job ``paged_attention``); None where no op could be assigned."""
from chipbench import jobs


def read(ctx):
    return jobs.roofline_pct(ctx, "paged_attention")
