"""Segment-GEMM kernels (``kernels/packed_matmul.py`` through the
``packed_matmul`` driver of ``backend/base.py``): every packed linear of
every layer, one kernel call per non-empty [K4|K2|K1] segment.

Work of one step: 2 * tokens * K * N FLOPs per linear, and the bytes of
the packed weights (read once per step), the activations in (bfloat16)
and the float32 outputs of the fed tokens. Only the linears on this
kernel's path count: not the router, nor the stacked expert banks, which
``packed_segment_matmul_grouped`` runs. The least time of a step is
the larger of FLOPs over the peak and bytes over the bandwidth: decode
steps are bound by the bytes, prefill-width steps by the FLOPs."""
from benchlib import flops

# Device op names of the segment GEMM in a profiler trace (regular
# expressions, matched in full against the HLO instruction's name, which
# is the kernel wrapper's: ``%packed_segment_matmul.187 = ...``).
PATTERNS = (r"(packed_segment_matmul|fused_act_segment_matmul"
            r"|fused_act_selfscale_matmul)(\.\d+)?",)


def step_work(m, step):
    """(FLOPs, bytes) the step's fed tokens need of the segment GEMMs."""
    tokens = sum(w for _, w in step.fed)
    f = b = 0.0
    for x in flops.linears(m):
        if x.kernel != "segment":
            continue
        rows = tokens * x.share
        f += 2.0 * rows * x.k * x.n * x.copies
        b += (flops.packed_weight_bytes(x.k, x.n, m["mix"])
              + rows * x.k * 2 + rows * x.n * 4) * x.copies
    return f, b
