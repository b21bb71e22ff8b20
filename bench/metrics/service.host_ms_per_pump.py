"""Host milliseconds per pump: the median, over the window's
``service.pump`` spans, of a pump's length less that of its
``service.sync`` child (the host's wait for the chunk's device work)."""

from bench import spans


def read(ctx):
    win = spans.window(ctx)
    if win is None:
        return None
    return spans.median(
        pump.ms - sum(s.ms for s in win.children(pump, "service.sync"))
        for pump in win.named("service.pump")
    )
