"""The whole engine step's share of the chip's bf16 peak: the operations
the model needs for the true tokens the window's steps carried (every
quantized linear with its LoRC path, causal attention over the true
lengths, the tied head for each row whose logits are used; pad rows and
idle slots are not counted), over the host-clock seconds of the window
before the trace started, times the peak (``chipbench/costs.py``)."""

from chipbench import costs


def read(ctx):
    steps = ctx["host_steps"]
    if not steps or ctx["host_window_s"] <= 0:
        return None
    flops = sum(costs.model_flops(s, ctx["arch"], ctx["quant"]) for s in steps)
    return 100.0 * flops / (ctx["host_window_s"] * ctx["peaks"]["flops"])
