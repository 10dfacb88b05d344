"""The device's idle share of the traced window: one minus the union of
the intervals in which an operation ran on the chip, over the window's
length (``chipbench/trace.py``)."""

from chipbench import trace


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.device:
        return None
    return 100.0 * (1.0 - trace.busy_s(tr) / trace.window_s(tr))
