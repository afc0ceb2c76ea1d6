"""Reference-tester flops of every call served correctly in the window,
over the window's length (first input made to last solution on the
host)."""


def read(run):
    return sum(c.flops for c in run.calls if c.ok) / run.window_s / 1e9
