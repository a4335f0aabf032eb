"""95th percentile of every gap between consecutive tokens of a request,
at the client, whose later token arrived inside the window.  Tokens of
one SSE event share its arrival time, so the tail is the gap between
events that users feel."""
import numpy as np


def read(ctx):
    t0, t1 = ctx.window
    gaps = []
    for r in ctx.records:
        ts = r.token_times
        gaps.extend(b - a for a, b in zip(ts, ts[1:]) if t0 <= b < t1)
    return 1e3 * float(np.percentile(gaps, 95)) if gaps else None
