"""Requests (searches and mores) answered before the window closed, over
the window's seconds."""


def read(run):
    n = sum(1 for r in run.loop.requests if r.done is not None and r.done <= run.loop.t_end)
    return n / run.seconds
