"""The paged FP8 decode kernel's share of its roofline
(``kernels/decode_attn.py``): over the traced steps, the time the pages
that the rows' true lengths cover take to read at the HBM bandwidth (or
their operations at the bf16 peak, whichever is longer; from
``chipbench/costs.py``), over the summed device time of the kernel's
events in the trace."""

from chipbench import costs, trace


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["trace_steps"]:
        return None
    spent = trace.kernel_s(tr, "decode_attn")
    if spent <= 0:
        return None
    pk = ctx["peaks"]
    least = 0.0
    for st in ctx["trace_steps"]:
        f, b = costs.step_decode_attn(st, ctx["arch"],
                                      ctx["serve"]["page_size"])
        least += max(f / pk["flops"], b / pk["hbm_bytes_per_s"])
    return 100.0 * least / spent
