"""95th percentile over the requests due in the window of their
``queue`` span, from the due time to the slot (seconds); a request never
given a slot counts as infinitely late.

Between the window's close and the first ``step`` after it the loop
stands still while the harness stops the profiler (a minute and more in
a traced run); that stretch is taken out of the waits that hold it.
None without the program's ``step`` spans."""


def read(ctx):
    if not ctx.open_loop:
        return None
    steps = [sp["ts"] for sp in ctx.spans if sp["name"] == "step"]
    if not steps:
        return None
    resumed = min((ts for ts in steps if ts >= ctx.t1), default=ctx.t1)
    pause = resumed - ctx.t1
    waited = {sp["trace_id"]: sp["dur"] - (pause if sp["ts"] + sp["dur"]
                                           >= resumed else 0.0)
              for sp in ctx.spans if sp["name"] == "queue"}
    return ctx.quantile([waited.get(s.plan.rid, float("inf"))
                         for s in ctx.due_in_window()], 95)
