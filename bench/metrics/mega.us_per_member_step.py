"""Device microseconds of the megakernel per member-step: the summed device
time of its events on every device, over the member-steps of the window."""

from bench.metrics import is_megakernel


def read(ctx):
    seconds = ctx.trace.op_seconds(is_megakernel)
    steps = ctx.counts.get("member_steps")
    if not seconds or not steps:
        return None
    return seconds * 1e6 / steps
