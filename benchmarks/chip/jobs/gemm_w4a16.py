"""Kernel job: the W4A16 GEMMs of every layer (q, k, v, o and the MLP).

Device ops: the Pallas W4A16 kernels. On the TPU each is one custom call
whose HLO instruction is named after the kernel's jitted function
(``%w4a16_fused.57 = ... custom-call(...)``); the trace gives an op's HLO
text as its name. The configuration's plans use no Split-K, so no
partial reduction or ``finalize`` op belongs to the job; a plan with Split-K
would add ops this pattern does not see (PERF.md, open questions). Work:
:func:`chipbench.work.gemm_call` per model pass.
"""
from chipbench import work

# searched in each device op's name and metadata
MATCH = r"^%(w4a16_fused|w4a16_decoupled|w8a16_fused|w4a8_fused)[.\d]* ="


def least_time(cfgj, steps, peaks) -> float:
    t = 0.0
    for s in steps:
        if s.decode_pos:
            t += work.least_time(*work.gemm_call(cfgj, len(s.decode_pos)),
                                 peaks)
    return t
