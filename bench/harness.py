"""One run of one cell: devices, set-up, the measured window, the trace,
the check against the reference, and the result line.

The traffic driver of the cell's kind (``bench/traffic/<kind>.py``) does
the work; it provides

    setup(cell, seed)                 -> session     (data, warm-up)
    window(session, seconds, span)    -> Outcome     (the measured window)
    check(session, outcome)           -> [Check]     (after the window)
    control(session, outcome)         -> {name: reading}  (bench/control.py)

and this module times set-up, counts compilations and full garbage
collections in the window, traces the window when asked, reads the
per-layer metrics (``bench/metrics/<metric>.py``) and prints everything as
the contract in BENCHMARK.json's cells asks.
"""
from __future__ import annotations

import contextlib
import gc
import json
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, NamedTuple

import devtrace
import loader


class NoChip(RuntimeError):
    """The cell needs chips this process cannot see."""


class Check(NamedTuple):
    """One number compared with the reference, and its limit (the number
    may not exceed it)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


class Outcome(NamedTuple):
    """What a window did: its end-to-end numbers (by metric name), the
    requests attempted and failed, and counters for the per-layer
    readers."""

    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    counters: Dict[str, Any]


class MetricContext(NamedTuple):
    """What a per-layer reader may read."""

    cell: loader.Cell
    counters: Dict[str, Any]
    trace: Any                   # devtrace.Trace
    window: tuple                # (start_ns, end_ns) of the traced window
    peaks: dict


class CompileWatch:
    """JAX's own compile and cache events, counted from a mark."""

    def __init__(self):
        import jax
        self.counts: Dict[str, int] = {"compiles": 0, "cache_hits": 0,
                                       "cache_misses": 0}
        self.compile_s = 0.0
        self._mark: Dict[str, int] = dict(self.counts)
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_time)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.counts["cache_misses"] += 1

    def _on_time(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.counts["compiles"] += 1
        if event.startswith("/jax/core/compile/"):
            self.compile_s += secs

    def mark(self) -> None:
        self._mark = dict(self.counts)

    def since_mark(self) -> Dict[str, int]:
        return {k: v - self._mark[k] for k, v in self.counts.items()}


class GcWatch:
    """Python's full (generation 2) collections in this process, and the
    seconds they took."""

    def __init__(self):
        self.full = 0
        self.full_s = 0.0
        self._start = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._start = time.perf_counter()
        else:
            self.full += 1
            self.full_s += time.perf_counter() - self._start

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


def say(**fields) -> None:
    """An earlier line of the run's standard output."""
    print(json.dumps(fields, default=float), flush=True)


def find_devices(chips: int) -> dict:
    """The accelerator this run measures; raises NoChip without a TPU or
    with fewer chips than the cell needs. Never falls back to the CPU."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {platform!r}); the "
                     f"benchmark measures only on the chip")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest chip the cell used."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def enable_compile_cache() -> str:
    """The program's persistent cache inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), with every program kept, so that
    only a checkout's first run compiles."""
    import jax
    from repro.compile_cache import enable_compile_cache as enable
    where = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def read_per_layer(cell: loader.Cell, ctx: MetricContext) -> Dict[str, dict]:
    """Each per-layer metric whose reader found something to read."""
    out = {}
    for metric in cell.per_layer:
        value = loader.load_module("metrics", metric["name"]).read(ctx)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: str = loader.ROOT, find=find_devices) -> int:
    """One run; prints the result line last and returns the exit code."""
    cell = loader.load_cell(name, root)
    driver = loader.load_module("traffic", cell.traffic["kind"])
    try:
        device = find(cell.chips)
        peaks = loader.load_peaks(device["kind"], root)
    except (NoChip, KeyError) as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    watch = CompileWatch()
    say(cell=name, seed=seed, seconds=seconds, trace=int(trace),
        device=device, compile_cache=cache_dir)

    t0 = time.perf_counter()
    session = driver.setup(cell, seed)
    # what set-up and JAX's import leave lives to the end of the run;
    # frozen, a full collection in the window does not walk it
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t0
    say(phase="setup", setup_s=setup_s, compile_events_s=watch.compile_s,
        **watch.counts)

    watch.mark()
    gcs = GcWatch()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        with (contextlib.nullcontext() if trace_dir is None else
              devtrace.capture(trace_dir)):
            outcome = driver.window(session, seconds, span)
        gc.unfreeze()
        gcs.close()
        in_window = watch.since_mark()
        say(phase="window", **outcome.end_to_end,
            attempted=outcome.attempted, failed=outcome.failed,
            compiles_in_window=in_window["compiles"],
            cache_hits_in_window=in_window["cache_hits"],
            cache_misses_in_window=in_window["cache_misses"],
            full_gcs_in_window=gcs.full, full_gc_s_in_window=gcs.full_s,
            **{k: v for k, v in outcome.counters.items()
               if isinstance(v, (int, float))})
        result_device = dict(device,
                             memory_peak_bytes=memory_peak_bytes(cell.chips))
        if trace_dir is None:
            metrics = {m["name"]: {"value": (setup_s if m["name"] == "setup_s"
                                             else outcome.end_to_end[m["name"]]),
                                   "unit": m["unit"]}
                       for m in cell.end_to_end}
            extra = {}
        else:
            metrics, extra = _reduce_trace(cell, outcome, trace_dir, peaks,
                                           result_device)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    checks = driver.check(session, outcome)
    del session
    correct = all(c.ok for c in checks)
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    result = {"correct": correct, "attempted": outcome.attempted,
              "failed": outcome.failed, "metrics": metrics,
              "device": result_device, **extra,
              "checks": {c.name: {"value": c.value, "limit": c.limit}
                         for c in checks}}
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def _reduce_trace(cell, outcome, trace_dir, peaks, result_device):
    tr = devtrace.extract(trace_dir)
    lo, hi = devtrace.window_of(tr)
    busy = devtrace.device_busy_s(tr, lo, hi)
    result_device["busy_s"] = busy
    result_device["window_s"] = (hi - lo) / 1e9
    ctx = MetricContext(cell, outcome.counters, tr, (lo, hi), peaks)
    return (read_per_layer(cell, ctx),
            {"breakdown": devtrace.breakdown(tr, lo, hi)})
