"""Requests answered correctly within the window, per second of the
window (host clock, client side)."""


def read(run):
    t_end = run.t0 + run.window_s
    n = sum(1 for r in run.records if r.ok and r.done <= t_end)
    return n / run.window_s
