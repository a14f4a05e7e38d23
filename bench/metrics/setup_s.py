"""Process start to the window's start: JAX and the chip, the input pool,
the Session, and the warm-up requests with their compiles."""


def read(ctx):
    return ctx.setup_s
