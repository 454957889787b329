"""Output tokens that reached the host inside the window, over the
window's length: the sweep's completed work.  (Requests completed per
second would move in steps of a whole 32-request cohort, which ends
together in this closed loop.)"""


def read(ctx):
    w = ctx.window
    n = sum(1 for o in w.outcomes for t in o.tokens_at
            if w.t0 <= t <= w.t_stop)
    return n / (w.t_stop - w.t0)
