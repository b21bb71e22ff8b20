"""Chunk programs the service compiled inside the window
(``ServiceMetrics.compiles``, counted from a fresh metrics object at the
window's start). Set-up warms every bucket width, so it should read 0."""


def read(ctx):
    return ctx.counts.get("compiles")
