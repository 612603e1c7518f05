"""Share of the window in which no operation ran on the device while the
batcher's ``step`` annotation was open (%): the idle that host code
causes, apart from the loop waiting for arrivals.

The trace names each idle gap by the innermost host event open at its
middle.  Inside the window loop, the harness's own phases (``bench.*``)
and nothing at all (the loop asleep until the next arrival) lie outside
``step``; every other name is the program's phases or the JAX calls they
make, inside it.  None without a device trace or without the program's
``step`` spans."""

OUTSIDE = "none"
HARNESS = "bench."


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.busy_s <= 0:
        return None
    if not any(sp["name"] == "step" for sp in ctx.spans):
        return None
    idle = sum(s for label, s in tr.gaps
               if label != OUTSIDE and not label.startswith(HARNESS))
    return 100.0 * idle / tr.chips / tr.window_s
