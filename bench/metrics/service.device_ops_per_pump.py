"""Device operations per pump: the ``XLA Ops`` events that start in the
window, per device, over the window's ``service.pump`` spans."""

from bench import spans


def read(ctx):
    win = spans.window(ctx)
    pumps = 0 if win is None else len(win.named("service.pump"))
    if not pumps:
        return None
    return win.device_ops / pumps
