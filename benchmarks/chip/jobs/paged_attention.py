"""Kernel job: paged attention over the KV pool at decode.

Device ops: the fused paged-attention Pallas kernel, the one TPU custom call
of the step programs that is not a W4A16 GEMM (its instruction carries no
name of its own). The combine epilogue is XLA ops with no name that ties
them to attention, so it is not counted in the device time (PERF.md, open
questions). Work:
:func:`chipbench.work.attn_decode`.
"""
from chipbench import work

MATCH = r'custom_call_target="tpu_custom_call"'
EXCLUDE = r"^%(w4a16_fused|w4a16_decoupled|w8a16_fused|w4a8_fused)[.\d]* ="


def least_time(cfgj, steps, peaks) -> float:
    t = 0.0
    for s in steps:
        if s.decode_pos:
            t += work.least_time(*work.attn_decode(cfgj, s.decode_pos),
                                 peaks)
    return t
