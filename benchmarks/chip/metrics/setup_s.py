"""Process start to the start of the window: imports, traffic, state,
compiles and the checked steps."""


def read(ctx):
    return ctx.setup_s
