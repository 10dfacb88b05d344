"""Engine step time: the host-clock seconds of the window before the trace
started, over the ``Server.step`` calls in them (``step_ms.chat`` moves
``itl_p95_ms``, ``step_ms.prefill`` moves ``tok_s``)."""


def read(ctx):
    steps = ctx["host_steps"]
    if not steps or ctx["host_window_s"] <= 0:
        return None
    return 1e3 * ctx["host_window_s"] / len(steps)
