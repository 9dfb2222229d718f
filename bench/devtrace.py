"""The device trace of a window, and its reduction to plain numbers.

``capture(dir)`` runs the JAX profiler around the window; ``extract``
reads the ``.xplane.pb`` it wrote into a ``Trace`` of plain tuples (device
ops and programs per chip, the benchmark's own host spans); the functions
below reduce a ``Trace`` to busy time, idle share, time per op or program,
and the longest idle gaps labelled by what the host was doing. Per-layer
metric readers (``bench/metrics``) read only a ``Trace`` and counters.

Host and device events share the profiler's clock; on a v5e their
alignment was seen to be off by about a millisecond, which bounds how
exactly an idle gap can be pinned to a host span.
"""
from __future__ import annotations

import collections
import contextlib
import glob
import os
import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

#: host spans the benchmark writes start with this
SPAN_PREFIX = "bench."

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)


class Trace(NamedTuple):
    ops: Dict[str, List[Event]]           # device plane -> XLA ops
    programs: Dict[str, List[Event]]      # device plane -> XLA modules
    spans: List[Event]                    # the benchmark's host spans


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace device ops and host annotations, without the Python tracer
    (which records every Python call and makes a window's trace huge)."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(log_dir, profiler_options=options):
        yield


#: op names of Pallas kernels start with this
KERNEL = "kernel:"


def op_name(hlo_text: str) -> str:
    """``%add.3 = f32[...] add(...)`` -> ``add``; a Pallas kernel,
    ``%sketch_fused.1 = (...) custom-call(...),
    custom_call_target="tpu_custom_call"`` -> ``kernel:sketch_fused``;
    a module ``jit_pipeline_fn(1234)`` -> ``jit_pipeline_fn``."""
    head = hlo_text.split(" = ", 1)[0].strip().lstrip("%")
    head = re.sub(r"\(\d+\)$", "", head)
    head = re.sub(r"(\.\d+)+$", "", head)
    if 'custom_call_target="tpu_custom_call"' in hlo_text:
        return KERNEL + head
    return head


def extract(log_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``log_dir`` as a ``Trace``."""
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    ops, programs, spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [(op_name(e.name), e.start_ns,
                                        e.duration_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    programs[plane.name] = [(op_name(e.name), e.start_ns,
                                             e.duration_ns)
                                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops, programs, sorted(spans, key=lambda s: s[1]))


def window_of(trace: Trace, span: str = "bench.window"
              ) -> Tuple[float, float]:
    """(start_ns, end_ns) of the named host span (the measured window)."""
    for name, start, dur in trace.spans:
        if name == span:
            return start, start + dur
    raise KeyError(f"the trace has no {span!r} span")


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to [lo, hi]; those outside it dropped."""
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Merged (start, end) intervals covered by any event."""
    merged: List[List[float]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Length of the union of the events inside [lo, hi]."""
    return sum(e - s for s, e in union(clip(events, lo, hi)))


def device_busy_s(trace: Trace, lo: float, hi: float) -> float:
    """Busy seconds in [lo, hi], averaged over the chips in the trace."""
    planes = list(trace.ops)
    if not planes:
        return 0.0
    return sum(busy_ns(trace.ops[p], lo, hi) for p in planes) / len(planes) \
        / 1e9


def idle_percent(trace: Trace, lo: float, hi: float) -> Optional[float]:
    """Share of [lo, hi] in which no op ran on the device, in percent;
    None when the trace holds no device."""
    if first_plane(trace) is None:
        return None
    return 100.0 * (1.0 - device_busy_s(trace, lo, hi) / ((hi - lo) / 1e9))


def time_by_name(events: Sequence[Event], lo: float, hi: float
                 ) -> Dict[str, float]:
    """Seconds per event name inside [lo, hi]."""
    out: Dict[str, float] = collections.defaultdict(float)
    for name, _, dur in clip(events, lo, hi):
        out[name] += dur / 1e9
    return dict(out)


def self_time_by_name(events: Sequence[Event], lo: float, hi: float
                      ) -> Dict[str, float]:
    """Seconds per event name inside [lo, hi], less the time of the events
    nested in it (a ``while`` op holds its body's ops on the same line)."""
    out: Dict[str, float] = collections.defaultdict(float)
    stack: List[List] = []                # [name, end, self_ns]
    for name, start, dur in sorted(clip(events, lo, hi),
                                   key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out[done[0]] += done[2] / 1e9
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    for name, _, self_ns in stack:
        out[name] += self_ns / 1e9
    return dict(out)


def idle_gaps(events: Sequence[Event], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """(start, end) of every stretch of [lo, hi] with no event running."""
    gaps, cursor = [], lo
    for s, e in union(clip(events, lo, hi)):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    return gaps


def host_label(spans: Sequence[Event], start: float, end: float,
               skip: str = "bench.window") -> str:
    """The innermost benchmark span covering most of [start, end]."""
    best, best_key = "host: outside any bench span", (0.0, 0.0)
    for name, s, d in spans:
        if name == skip:
            continue
        cover = min(end, s + d) - max(start, s)
        if cover <= 0:
            continue
        key = (cover, -d)                 # most cover, then innermost
        if key > best_key:
            best, best_key = name, key
    return best


def breakdown(trace: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """The device ops that took most time (self time) and the longest idle
    gaps, each gap named by the host span it fell in (first chip)."""
    plane = first_plane(trace)
    if plane is None:
        return {"device_ops": [], "idle_gaps": []}
    by_op = sorted(self_time_by_name(trace.ops[plane], lo, hi).items(),
                   key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace.ops[plane], lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[name, secs] for name, secs in by_op],
            "idle_gaps": [[host_label(trace.spans, s, e), (e - s) / 1e9]
                          for s, e in gaps]}


def first_plane(trace: Trace) -> Optional[str]:
    return sorted(trace.ops)[0] if trace.ops else None
