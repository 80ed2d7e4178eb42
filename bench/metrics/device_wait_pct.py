"""Share of the traced window in which some host thread waited for a device
result: the union, over threads, of the program's `device.wait` spans."""
from bench import program_spans as ps


def read(ctx):
    waits = ps.named(ps.records() or [], "device.wait")
    if not waits or ctx.window_s <= 0:
        return None
    return 100.0 * ps.union_ns(waits) * 1e-9 / ctx.window_s
