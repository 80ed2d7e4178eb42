"""The 2-D solver's share of its roofline: the least time the chip needs for
the algorithmic work of the `model.chunk` spans completed in the traced
window (bench/work2d.py, peaks from bench/peaks.py), over the device's busy
time in that window. Every chunk of the cells that report it is a 2-D
solve."""
from bench import program_spans as ps
from bench.work import least_time_s
from bench.work2d import chunk_work


def read(ctx):
    flops, nbytes = chunk_work(ps.records() or [])
    if flops <= 0 or ctx.mean_busy_s <= 0:
        return None
    chips = len(ctx.busy_s)
    return 100.0 * least_time_s(flops / chips, nbytes / chips, ctx.peak) / ctx.mean_busy_s
