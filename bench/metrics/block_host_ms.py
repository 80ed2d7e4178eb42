"""Host time per fused block, in ms: mean over the program's `sampler.fused`
spans of the span minus the `device.wait` spans it caused, over its
`blocks`. The entry (carry, memo keys, jit wrappers), the starting
log-posteriors' dispatch and the host copy of the samples."""
from bench import program_spans as ps


def read(ctx):
    recs = ps.records() or []
    calls = [c for c in ps.named(recs, "sampler.fused") if c.attrs.get("blocks")]
    if not calls:
        return None
    kids = ps.children(recs)
    per_block = [ps.self_ns(c, ps.named(ps.descendants(kids, c), "device.wait")) * 1e-6
                 / c.attrs["blocks"] for c in calls]
    return sum(per_block) / len(per_block)
