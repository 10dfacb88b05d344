"""The fused W4A8 GEMM's share of its roofline (``kernels/w4a8_fused.py``):
over the traced steps, the least time the chip could take for every GEMM
call (the larger of its operations over the bf16 peak and its bytes over
the HBM bandwidth, per call, from ``chipbench/costs.py``), over the summed
device time of the kernel's events in the trace."""

from chipbench import costs, trace


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["trace_steps"]:
        return None
    spent = trace.kernel_s(tr, "gemm")
    if spent <= 0:
        return None
    least = sum(costs.step_gemm_least_s(st, ctx["arch"], ctx["quant"],
                                        ctx["peaks"])
                for st in ctx["trace_steps"])
    return 100.0 * least / spent
