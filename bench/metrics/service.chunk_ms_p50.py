"""Median chunk latency of the window, in milliseconds, as the service's own
``ServiceMetrics.latency_us(50)`` has it (host clock, fenced per chunk)."""

import math


def read(ctx):
    value = ctx.counts.get("chunk_ms_p50")
    if value is None or not math.isfinite(value):
        return None
    return value
