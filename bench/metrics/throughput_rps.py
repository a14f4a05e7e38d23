"""Requests completed inside the window, per second of the window."""


def read(ctx):
    done = sum(1 for r in ctx.requests if r.ok and r.done <= ctx.window_end)
    return done / ctx.seconds
