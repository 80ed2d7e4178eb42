"""Share of the traced window inside the program's `gp.predict` spans (their
union): the GP level's own time, its device waits included."""
from bench import program_spans as ps


def read(ctx):
    recs = ps.records()
    gp = ps.named(recs or [], "gp.predict")
    if not gp or ctx.window_s <= 0:
        return None
    return 100.0 * ps.union_ns(gp) * 1e-9 / ctx.window_s
