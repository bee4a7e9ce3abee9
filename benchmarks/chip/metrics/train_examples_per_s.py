"""Examples of every step completed in the window, summed over the cell's
chips, over the time from the window's start until the last step's state
was ready (the last step may run past ``--seconds``)."""


def read(ctx):
    return ctx.examples / ctx.window_s if ctx.steps else None
