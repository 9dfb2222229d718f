"""Host spans of the program, on the profiler's clock and in memory.

    from repro import telemetry

    with telemetry.span("repro.ingest.update"):
        state = summ.update(state, A_chunk, B_chunk, off)

A span opens a ``jax.profiler.TraceAnnotation`` of its name, so that it
lands in any profiler trace next to the device's ops, on the profiler's
clock. It also records one occurrence: its start and end
(``time.perf_counter_ns``), the thread's CPU time over it
(``time.thread_time_ns``), and the span that encloses it on the same
thread. Occurrences go into one ring of ``CAPACITY`` entries, in the order
they close; when the ring is full the oldest is overwritten and counted.
Per-name totals (count, wall ns, CPU ns, longest wall ns) are kept besides.

A named count (``telemetry.count("repro.sparse.shared_pass")``) records how
often a path was taken where a span would cost too much or say nothing: an
integer per name, with no annotation and no occurrence in the ring.

The record is always on. An occurrence costs a few microseconds of host
time, most of it reading the thread's CPU clock, which on some hosts is a
system call; with no profiler running the annotation costs under one.
Where that clock ticks coarsely (every 10 ms on some virtual machines), a
single occurrence's CPU time is 0 or a whole tick, and only sums over
many occurrences are meaningful. Span names start with ``repro.``.

Read side, as plain values: ``snapshot()`` gives the per-name totals,
``occurrences()`` the ring oldest first, ``overwritten()`` how many
occurrences the ring lost, and ``counts()`` the named counts.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax

#: occurrences the process-wide ring holds (a few MB): a 40 s window of
#: ingest at 7.85 ms a chunk closes about 15,300
CAPACITY = 65_536


class Occurrence(NamedTuple):
    """One closed span. ``parent`` is the index, in the same list, of the
    span that enclosed it (None at the top of a thread, or where the ring
    no longer holds the parent); ``children`` counts the spans that closed
    directly inside it, held by the ring or not."""

    name: str
    start_ns: int
    end_ns: int
    cpu_ns: int
    parent: Optional[int]
    children: int


class _Span:
    """One use of ``Recorder.span``; nests through the thread's stack."""

    __slots__ = ("_rec", "_name", "_annotation", "_stack", "_seq",
                 "_parent", "_children", "_start", "_cpu")

    def __init__(self, rec: "Recorder", name: str):
        self._rec = rec
        self._name = name

    def __enter__(self) -> "_Span":
        self._stack = stack = self._rec._stack()
        self._parent = stack[-1] if stack else None
        self._seq = next(self._rec._seqs)
        self._children = 0
        stack.append(self)
        self._annotation = jax.profiler.TraceAnnotation(self._name)
        self._annotation.__enter__()
        self._cpu = time.thread_time_ns()
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        cpu = time.thread_time_ns() - self._cpu
        self._annotation.__exit__(*exc)
        self._stack.pop()
        parent = self._parent
        if parent is not None:
            parent._children += 1
        self._rec._record(self._name, self._start, end, cpu, self._seq,
                          None if parent is None else parent._seq,
                          self._children)


class Recorder:
    """A fixed-capacity ring of span occurrences and per-name totals, safe
    to write from several threads."""

    def __init__(self, capacity: int = CAPACITY):
        if capacity < 1:
            raise ValueError(f"capacity must be positive, got {capacity!r}")
        self.capacity = capacity
        self._lock = threading.Lock()
        self._local = threading.local()
        self._seqs = itertools.count()
        self._ring: List[Optional[tuple]] = [None] * capacity
        self._written = 0
        self._totals: Dict[str, List[int]] = {}
        self._counts: Dict[str, int] = {}

    def span(self, name: str) -> _Span:
        """A context manager: a profiler annotation and one occurrence."""
        return _Span(self, name)

    def count(self, name: str) -> None:
        """Add one to the named count."""
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + 1

    def counts(self) -> Dict[str, int]:
        """Every named count since the record began."""
        with self._lock:
            return dict(self._counts)

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record(self, name, start, end, cpu, seq, parent_seq, children):
        wall = end - start
        with self._lock:
            self._ring[self._written % self.capacity] = (
                name, start, end, cpu, seq, parent_seq, children)
            self._written += 1
            totals = self._totals.get(name)
            if totals is None:
                self._totals[name] = [1, wall, cpu, wall]
            else:
                totals[0] += 1
                totals[1] += wall
                totals[2] += cpu
                if wall > totals[3]:
                    totals[3] = wall

    def snapshot(self) -> Dict[str, Dict[str, int]]:
        """Per span name: ``count``, ``wall_ns``, ``cpu_ns``,
        ``max_wall_ns`` over every occurrence since the process began."""
        with self._lock:
            return {name: dict(zip(("count", "wall_ns", "cpu_ns",
                                    "max_wall_ns"), totals))
                    for name, totals in self._totals.items()}

    def overwritten(self) -> int:
        """Occurrences the ring has lost to newer ones."""
        with self._lock:
            return max(0, self._written - self.capacity)

    def occurrences(self) -> List[Occurrence]:
        """The ring's occurrences, oldest first (in the order they closed,
        so a parent follows its children)."""
        with self._lock:
            n = min(self._written, self.capacity)
            first = self._written - n
            raw = [self._ring[(first + i) % self.capacity] for i in range(n)]
        index = {rec[4]: i for i, rec in enumerate(raw)}
        return [Occurrence(name, start, end, cpu, index.get(parent_seq),
                           children)
                for name, start, end, cpu, _, parent_seq, children in raw]


#: the process-wide record the program's spans write to
RECORDER = Recorder()
span = RECORDER.span
snapshot = RECORDER.snapshot
occurrences = RECORDER.occurrences
overwritten = RECORDER.overwritten
count = RECORDER.count
counts = RECORDER.counts
