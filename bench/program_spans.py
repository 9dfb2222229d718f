"""The program's own host spans (``repro.telemetry``) of the window's
``StreamingSummarizer.ingest`` call, for the per-layer readers.

``devtrace.extract`` keeps only the benchmark's ``bench.`` spans, so these
readers take the program's spans from its in-memory record instead. They
put them on the trace's clock by the benchmark's ``bench.ingest`` span,
which encloses the call: the offset is the difference of the two starts,
and the two ends have to agree with it. A program that keeps no such record
gives None, and so does a record that lost part of the call.
"""
from __future__ import annotations

import collections
from typing import Dict, List, NamedTuple, Optional

#: the program's spans of one ingest call
CALL = "repro.ingest"
STAGE = "repro.ingest.stage"
UPDATE = "repro.ingest.update"
#: the benchmark's span around the call
ANCHOR = "bench.ingest"
#: how far apart the offsets at the anchor's two ends may lie
ANCHOR_TOLERANCE_NS = 1e6


class Call(NamedTuple):
    span: tuple                     # the call's telemetry.Occurrence
    children: Dict[str, List[tuple]]  # span name -> occurrences inside it


def last_call() -> Optional[Call]:
    """The last recorded ``CALL`` span and the spans directly inside it;
    None where the program records no spans, recorded no such call, or
    its ring overwrote any span of the call."""
    try:
        from repro import telemetry
    except ImportError:
        return None
    occ = telemetry.occurrences()
    idx = next((i for i in range(len(occ) - 1, -1, -1)
                if occ[i].name == CALL), None)
    if idx is None:
        return None
    children = collections.defaultdict(list)
    for o in occ[:idx]:             # a span closes before its parent
        if o.parent == idx:
            children[o.name].append(o)
    if sum(map(len, children.values())) != occ[idx].children:
        return None
    return Call(occ[idx], dict(children))


def offset_ns(trace, call: Call) -> Optional[float]:
    """Trace clock minus the program's clock, from the last ``ANCHOR`` span
    of the trace; None without one, or where its two ends disagree by more
    than ``ANCHOR_TOLERANCE_NS``."""
    spans = [s for s in trace.spans if s[0] == ANCHOR]
    if not spans:
        return None
    _, start, dur = spans[-1]
    head = start - call.span.start_ns
    tail = start + dur - call.span.end_ns
    if abs(tail - head) > ANCHOR_TOLERANCE_NS:
        return None
    return head


def overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    ``(start, end)`` intervals, in one pass over both (a window holds
    tens of thousands of idle gaps and thousands of launches)."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total
