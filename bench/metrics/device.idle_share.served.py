"""The devices' idle share of the traced window, in percent: one minus the
union of the intervals in which an operation ran, over the window."""


def read(ctx):
    if ctx.trace.window_s <= 0:
        return None
    return 100.0 * ctx.trace.idle_share
