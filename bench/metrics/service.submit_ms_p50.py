"""Median length of the window's ``service.submit`` spans, in milliseconds:
admitting one request, its resolution included."""

from bench import spans


def read(ctx):
    win = spans.window(ctx)
    if win is None:
        return None
    return spans.median(s.ms for s in win.named("service.submit"))
