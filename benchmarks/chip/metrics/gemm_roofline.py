"""Kernels: least time of the W4A16 GEMM work of the traced steps at the
chip's peaks over the device time of every op doing that work (job
``gemm_w4a16``); None where no op could be assigned to it."""
from chipbench import jobs


def read(ctx):
    return jobs.roofline_pct(ctx, "gemm_w4a16")
