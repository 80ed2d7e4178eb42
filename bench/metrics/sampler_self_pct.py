"""Share of the traced window inside the program's `sampler.mlda_step` spans
that no span they caused covers: the host sampler's own logic, outside the
GP level, fabric calls and compiles."""
from bench import program_spans as ps


def read(ctx):
    recs = ps.records() or []
    steps = ps.named(recs, "sampler.mlda_step")
    if not steps or ctx.window_s <= 0:
        return None
    kids = ps.children(recs)
    own = sum(ps.self_ns(st, kids.get(st.id, [])) for st in steps)
    return 100.0 * own * 1e-9 / ctx.window_s
