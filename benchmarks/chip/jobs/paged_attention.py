"""Kernel job: paged attention over the KV pool at decode.

Device ops: the fused paged-attention Pallas kernel, by the name it is
given (``pallas_call(..., name="paged_attention")``), which the TPU trace
carries as the custom call's instruction (``%paged_attention.3 = ...
custom-call(...)``). A custom call of any other name, such as a grouped
expert GEMM, is not attention's. The combine epilogue is XLA ops with no
name that ties them to attention, so it is not counted in the device time
(PERF.md, open questions). Work: :func:`chipbench.work.attn_decode`.
"""
from chipbench import work

# searched in each device op's name and metadata
MATCH = r"^%paged_attention[.\d]* ="


def least_time(cfgj, steps, peaks) -> float:
    t = 0.0
    for s in steps:
        if s.decode_pos:
            t += work.least_time(*work.attn_decode(cfgj, s.decode_pos),
                                 peaks)
    return t
