"""Output tokens that reached the clients inside the window, over the
window's length."""


def read(ctx):
    t0, t1 = ctx.window
    n = sum(1 for r in ctx.records for t in r.token_times if t0 <= t < t1)
    return n / (t1 - t0) if n else None
