"""The program's own spans (`repro.core.tracing`) in a traced run, and the
interval arithmetic the readers of `bench/metrics/` do on them.

The program records its spans while a profiler trace collects host events,
which in a run of the benchmark is the traced window alone. A checkout whose
program records no spans gives None, and its readers report nothing.
"""
from __future__ import annotations

from bench.trace import merge


def records() -> list | None:
    """Every span the program recorded in this process, or None where the
    program has no recorder."""
    try:
        from repro.core import tracing
    except ImportError:
        return None
    return tracing.records()


def named(recs, name: str) -> list:
    return [r for r in recs if r.name == name]


def union_ns(recs, lo: float = float("-inf"), hi: float = float("inf")) -> float:
    """Length of the union of the spans' intervals, clipped to [lo, hi]."""
    return sum(e - s for s, e in merge([(r.name, r.start_ns, r.end_ns) for r in recs], lo, hi))


def children(recs) -> dict:
    """Parent id -> the spans it caused."""
    out: dict = {}
    for r in recs:
        out.setdefault(r.parent, []).append(r)
    return out


def descendants(kids: dict, rec) -> list:
    """Every span `rec` caused, directly or not, on any thread."""
    out, todo = [], list(kids.get(rec.id, ()))
    while todo:
        r = todo.pop()
        out.append(r)
        todo.extend(kids.get(r.id, ()))
    return out


def self_ns(rec, covered) -> float:
    """`rec`'s duration minus the part of it that the spans in `covered`
    cover."""
    return rec.end_ns - rec.start_ns - union_ns(covered, rec.start_ns, rec.end_ns)
