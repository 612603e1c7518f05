"""Median over the window's ``step`` spans that decoded (``busy`` > 0) of
the span less its ``wait_s`` (ms): the host's own time in one
``ContinuousBatcher.step``, outside the syncs on the device's tokens,
when the device has nothing of this step to run."""


def read(ctx):
    d = [sp["dur"] - sp["attrs"]["wait_s"] for sp in ctx.spans
         if sp["name"] == "step" and sp["attrs"]["busy"] > 0
         and ctx.t0 <= sp["ts"] <= ctx.t1]
    v = ctx.quantile(d, 50)
    return None if v is None else v * 1e3
