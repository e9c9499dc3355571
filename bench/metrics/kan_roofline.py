"""Share of the fused KAN kernel's device time that its roofline allows:
``Σ least time / Σ time`` over its calls in the traced round.  The trace
names each call by the jitted function around the ``pallas_call``,
``kan_fused_gemm_pallas``.  A call's least time is the larger of its operations over the
chip's bf16 peak and its bytes over the HBM bandwidth.

Per call, from the shapes it ran (rows ``B`` and the padded ``K``, ``N``):
``2·B·K·(M + 1)·N`` operations for the ``M`` basis planes and the ReLU
base term, and each operand read once and the result written once:
``x (B, K)``, coefficients ``(K, M, N)``, base weights ``(K, N)``, result
``(B, N)``, in the dtypes the engine holds them.
"""

from harness import hlo_shapes

KERNEL = "kan_fused_gemm_pallas"


def least_time(arrays, peaks):
    if len(arrays) < 3:
        return None
    out, x, coeff, *rest = arrays
    if len(x[1]) != 2 or len(coeff[1]) != 3:
        return None
    B, K = x[1]
    _, M, N = coeff[1]
    planes = M + (1 if rest else 0)
    ops = 2.0 * B * K * planes * N
    nbytes = sum(hlo_shapes.nbytes(a) for a in arrays)
    return max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def read(run):
    if run.trace is None or not run.peaks:
        return None
    calls = run.trace.ops(lambda e: e.op.split(".")[0] == KERNEL)
    return hlo_shapes.roofline_share(calls, lambda a: least_time(a, run.peaks))
