"""Share of the lanes the model dispatched in the traced window that were
padding: sum of `padded` over sum of `lanes` of the program's `model.chunk`
spans. A count: it repeats exactly for a given set of wave widths."""
from bench import program_spans as ps


def read(ctx):
    chunks = ps.named(ps.records() or [], "model.chunk")
    lanes = sum(c.attrs["lanes"] for c in chunks)
    if not lanes:
        return None
    return 100.0 * sum(c.attrs["padded"] for c in chunks) / lanes
