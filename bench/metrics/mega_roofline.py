"""The megakernel's share of its roofline, in percent: the least time the
chip could take for the window's work (the larger of its float32 operations
over the peak FLOP/s and its least HBM bytes over the peak bandwidth, both
from ``bench/work``) over the kernel's summed device time."""

from bench.metrics import is_megakernel


def read(ctx):
    seconds = ctx.trace.op_seconds(is_megakernel)
    horizons = ctx.counts.get("member_horizons")
    if not seconds or not horizons:
        return None
    flops = horizons * ctx.work.flops(ctx.config)
    nbytes = horizons * ctx.work.hbm_bytes(ctx.config)
    least = max(flops / ctx.peaks["flops_bf16"], nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
