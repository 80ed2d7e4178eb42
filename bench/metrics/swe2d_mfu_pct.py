"""Algorithmic FLOPs of the 2-D `model.chunk` spans completed in the traced
window (bench/work2d.py), over the window's whole length times the peak
FLOP/s of the cell's chips."""
from bench import program_spans as ps
from bench.work2d import chunk_work


def read(ctx):
    flops, _ = chunk_work(ps.records() or [])
    if flops <= 0 or ctx.window_s <= 0:
        return None
    return 100.0 * flops / (ctx.window_s * len(ctx.busy_s) * ctx.peak["flops_per_s"])
