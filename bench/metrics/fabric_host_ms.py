"""Mean over the program's `fabric.*` spans of the span minus its
`backend.dispatch` spans, in ms: the fabric's own time inside the program
(keys, cache, futures, the training tap)."""
from bench import program_spans as ps


def read(ctx):
    recs = ps.records() or []
    fabric = [r for r in recs if r.name.startswith("fabric.")]
    if not fabric:
        return None
    kids = ps.children(recs)
    own = [ps.self_ns(f, ps.named(kids.get(f.id, []), "backend.dispatch")) * 1e-6
           for f in fabric]
    return sum(own) / len(own)
