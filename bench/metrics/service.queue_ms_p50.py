"""Median queue wait, in milliseconds: the ``wait_us`` (admission to the
first bucket join, on the program's clock) of the ``service.join`` spans
of the requests whose ``service.submit`` lies in the window."""

from bench import spans


def read(ctx):
    win = spans.window(ctx)
    if win is None:
        return None
    submitted = {s.stats.get("request") for s in win.named("service.submit")}
    waits = {}
    for join in win.named("service.join"):
        request = join.stats.get("request")
        if request in submitted and request not in waits:
            waits[request] = join.stats["wait_us"] * 1e-3
    return spans.median(waits.values())
