"""The program's own host spans (``kvf.*``, see docs/architecture.md,
"Tracing") in the traced window, for the per-layer readers.

`chipbench.trace.load` keeps a host span's name, start and duration,
and its arguments as strings (``Event.tags``), which a new reader may
take a new span's shapes from. The readers here take what they need
beyond names and times from the harness's own records on the same
window: the kernel calls in ``ctx.restore_calls`` (one
``kvf.cache.restore`` span and one ``kvf.restore.h2d`` span each, in the
same order) and the first tokens in ``ctx.clients.token_log`` (one
``kvf.prefill.suffix`` or ``kvf.prefill.full`` span each, in the same
order). Every reader returns None where the trace holds no such span,
as a program without them gives.
"""
from __future__ import annotations

import bisect
from typing import Iterable, List, Optional, Sequence

from chipbench import trace as trace_mod
from chipbench.trace import Event


def named(ctx, name: str) -> List[Event]:
    """The host spans called ``name`` that lie wholly inside the traced
    window, in order of start; none without a trace or a window."""
    if ctx.trace is None or ctx.window_ns is None:
        return []
    lo, hi = ctx.window_ns
    return sorted((e for e in ctx.trace.host if e.name == name
                   and lo <= e.start_ns and e.end_ns <= hi),
                  key=lambda e: e.start_ns)


def inside(children: Sequence[Event],
           parents: Sequence[Event]) -> List[Event]:
    """The ``children`` that lie wholly inside one of ``parents``. Spans
    of one thread nest or are disjoint, so a child's parent can only be
    the last one to start at or before it."""
    parents = sorted(parents, key=lambda e: e.start_ns)
    starts = [p.start_ns for p in parents]
    out = []
    for c in children:
        i = bisect.bisect_right(starts, c.start_ns) - 1
        if i >= 0 and c.end_ns <= parents[i].end_ns:
            out.append(c)
    return out


def self_ns(span: Event, children: Iterable[Event]) -> float:
    """``span``'s duration less the part of it that ``children`` cover."""
    lo, hi = span.start_ns, span.end_ns
    covered = trace_mod.union_ns(
        Event(c.name, max(c.start_ns, lo), min(c.end_ns, hi)
              - max(c.start_ns, lo))
        for c in children if c.end_ns > lo and c.start_ns < hi)
    return span.dur_ns - covered


def restores(ctx) -> Optional[List[tuple]]:
    """The window's ``kv_restore`` calls as the harness recorded them,
    or None unless each has its ``kvf.cache.restore`` span inside a
    ``kvf.restore.chunk`` span."""
    calls = ctx.restore_calls
    dispatched = inside(named(ctx, "kvf.cache.restore"),
                        named(ctx, "kvf.restore.chunk"))
    if not calls or len(dispatched) != len(calls):
        return None
    return calls
