"""Continuously-batched serving loop — the scheduler/dispatcher split.

The paper's sketch->estimate->error recipe is a cheap, fixed-shape
computation a high-traffic service wants to run millions of times, and the
compile-once ``PipelineEngine`` makes every warm request one cache lookup
plus one fused dispatch. This module puts a production front-end on that
warm path:

* ``Scheduler`` — pure host-side queueing (no jax): an admission queue with
  **continuous batching** (a request joins its shape bucket's open batch
  slot the moment it arrives; the batch dispatches when full *or* when the
  oldest member's deadline budget forces it), earliest-deadline-first
  priority ordering, and bounded queues with **backpressure and
  load-shedding** (reject-with-reason when depth or wait-time limits are
  exceeded).
* ``Dispatcher`` — executes one ready batch as ONE fused dispatch through
  the shared ``PipelineEngine`` executable cache (stack keys/A/B, run the
  plan, unstack per request) and resolves the requests' futures.
* ``ServingLoop`` — composes the two behind a clock: ``submit`` admits a
  request and returns a ``ServeFuture`` immediately; ``poll`` sheds expired
  requests and dispatches every ready batch; ``drain`` force-dispatches
  everything queued (the synchronous ``SketchService.flush`` path);
  ``start``/``stop`` run ``poll`` on a background thread for fully async
  serving.

**Multi-tenant key namespacing**: a request submitted under ``tenant=``
has its key folded through the reserved two-level
``pipeline.tenant_key`` derivation *before* batching, so many tenants
share one warm executable cache (same plans, same shapes, same compiled
code) while two tenants submitting the *same* user key get bit-different
sketches. Tenancy never enters the batch signature — mixed-tenant traffic
batches together.

Everything is deterministic under an injected ``clock`` (tests drive a
virtual clock; production uses ``time.monotonic``):

>>> import jax
>>> from repro.core import pipeline
>>> from repro.serve.scheduler import LoopConfig, PipelineWork, ServingLoop
>>> key = jax.random.PRNGKey(0)
>>> A = jax.random.normal(key, (64, 6))
>>> B = jax.random.normal(jax.random.fold_in(key, 1), (64, 4))
>>> plan = pipeline.PipelinePlan(
...     sketch=pipeline.SketchSpec(k=8, backend="scan", block=32),
...     estimation=pipeline.EstimationSpec(m=64, T=2),
...     rank=pipeline.RankPolicy(r=2), key_layout="service")
>>> now = [0.0]
>>> loop = ServingLoop(config=LoopConfig(max_batch=2),
...                    clock=lambda: now[0])
>>> f1 = loop.submit(key, A, B, work=PipelineWork(plan))
>>> f2 = loop.submit(jax.random.fold_in(key, 7), A, B,
...                  work=PipelineWork(plan), tenant="acme")
>>> loop.poll()                    # batch full (2/2): ONE fused dispatch
1
>>> f1.done and f2.done
True
>>> f1.result().estimate.factors.U.shape
(6, 2)
>>> loop.stats.occupancy           # continuous batching: 2 requests/dispatch
2.0
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import math
import threading
import time
from typing import Callable, List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import pipeline
from repro.core.pipeline import PipelineResult
from repro.core.types import ErrorEstimate, LowRankFactors, SketchSummary
from repro.kernels.tuning import TuningSpec

#: Load-shed reasons (``Rejected.reason`` / ``LoopStats.shed`` keys).
SHED_QUEUE_FULL = "queue_full"        # admission: depth limit exceeded
SHED_WAIT_EXCEEDED = "wait_exceeded"  # scheduling: waited past max_wait

#: Dispatch triggers (``LoopStats.dispatched`` keys).
DISPATCH_FULL = "full"                # batch slot reached max_batch
DISPATCH_DEADLINE = "deadline"        # oldest member's budget forced it
DISPATCH_DRAIN = "drain"              # explicit drain()/flush


class Rejected(RuntimeError):
    """A request the service refused (admission) or shed (scheduling).

    ``reason`` is one of the SHED_* constants; the message carries the
    limit that was exceeded so callers can apply backpressure upstream.
    """

    def __init__(self, reason: str, message: str):
        super().__init__(message)
        self.reason = reason


class SummaryWork(NamedTuple):
    """Step-1-only work: the request resolves to a ``SketchSummary``.

    ``tuning`` optionally pins Pallas kernel configs (a hashable
    ``repro.kernels.tuning.TuningSpec``) exactly like
    ``PipelinePlan.tuning`` does for full-pipeline work; it is part of the
    work value, hence part of the batch signature and the executable cache
    key — warm repeat-shape traffic under a pinned tuning never re-traces.
    """

    spec: pipeline.SketchSpec
    tuning: Optional[TuningSpec] = None


class PipelineWork(NamedTuple):
    """Full-pipeline work: the request resolves to a ``PipelineResult``."""

    plan: pipeline.PipelinePlan


class LoopConfig(NamedTuple):
    """Scheduling policy knobs (all limits optional; None = unbounded).

    * ``max_batch`` — dispatch a bucket's open batch the moment it holds
      this many requests (None: only deadlines or ``drain`` dispatch).
    * ``max_queue`` — admission bound on total queued requests; past it
      ``submit`` raises ``Rejected(SHED_QUEUE_FULL)`` (backpressure).
    * ``max_wait`` — requests queued longer than this are shed at the next
      ``poll`` with ``Rejected(SHED_WAIT_EXCEEDED)``.
    * ``default_deadline`` — deadline budget (seconds from arrival) for
      requests submitted without one; None = no deadline.
    * ``dispatch_margin`` — dispatch a partial batch this many seconds
      *before* its most urgent deadline (headroom for service time).
    * ``pad`` — ``'none'``: dispatch batches at their exact size (every new
      size is a new executable signature); ``'pow2'``: right-pad each batch
      to the next power of two by replicating its last request, then slice
      the padding off — per-request results are bit-identical (vmapped
      lanes are independent) but variable-occupancy traffic compiles at
      most log2(max_batch)+1 executables per bucket instead of one per
      batch size.
    """

    max_batch: Optional[int] = None
    max_queue: Optional[int] = None
    max_wait: Optional[float] = None
    default_deadline: Optional[float] = None
    dispatch_margin: float = 0.0
    pad: str = "none"


@dataclasses.dataclass
class LoopStats:
    """Observable serving counters."""

    admitted: int = 0             # requests accepted into the queue
    completed: int = 0            # requests resolved with a result
    dispatches: int = 0           # fused device dispatches (batches)
    batched_requests: int = 0     # requests across all dispatches
    shed: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)        # reason -> count
    dispatched: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)        # trigger -> count

    @property
    def occupancy(self) -> float:
        """Mean requests per fused dispatch (continuous-batching win)."""
        return self.batched_requests / self.dispatches if self.dispatches \
            else 0.0

    @property
    def shed_total(self) -> int:
        """Requests refused or shed, over every reason."""
        return sum(self.shed.values())


class ServeFuture:
    """Handle for one in-flight request.

    ``done`` flips when the dispatcher resolves or the scheduler sheds the
    request; ``result()`` returns the work's value (``SketchSummary`` or
    ``PipelineResult``) or raises ``Rejected`` if the request was shed.
    ``result(timeout=...)`` blocks, so futures work identically whether
    the loop is polled inline or pumped by the background thread.
    """

    def __init__(self, seq: int):
        self.seq = seq
        self.dispatch_seq: Optional[int] = None   # which dispatch served it
        self.completed_at: Optional[float] = None
        self._event = threading.Event()
        self._value = None
        self._shed: Optional[Rejected] = None

    @property
    def done(self) -> bool:
        """True once resolved (with a result or a shed)."""
        return self._event.is_set()

    @property
    def shed_reason(self) -> Optional[str]:
        """The SHED_* reason if the request was shed, else None."""
        return None if self._shed is None else self._shed.reason

    def result(self, timeout: Optional[float] = None):
        """The served value; raises ``Rejected`` for shed requests."""
        if not self._event.wait(timeout):
            raise TimeoutError(f"request {self.seq} still pending")
        if self._shed is not None:
            raise self._shed
        return self._value

    def _resolve(self, value, dispatch_seq: int, now: float) -> None:
        self._value = value
        self.dispatch_seq = dispatch_seq
        self.completed_at = now
        self._event.set()

    def _reject(self, exc: Rejected, now: float) -> None:
        self._shed = exc
        self.completed_at = now
        self._event.set()


@dataclasses.dataclass
class _Request:
    """One admitted request: payload + scheduling state."""

    seq: int
    key: jax.Array                # tenant fold already applied
    A: jax.Array
    B: jax.Array
    work: Union[SummaryWork, PipelineWork]
    arrival: float
    deadline: Optional[float]     # absolute clock time, None = none
    future: ServeFuture

    @property
    def urgency(self) -> float:
        """EDF sort key (requests without a deadline sort last)."""
        return math.inf if self.deadline is None else self.deadline


class _Batch(NamedTuple):
    """A dispatch-ready group of same-signature requests."""

    requests: List[_Request]
    trigger: str                  # DISPATCH_FULL / _DEADLINE / _DRAIN

    @property
    def urgency(self) -> Tuple[float, int]:
        """Inter-batch EDF order: most urgent member, then oldest seq."""
        return (min(r.urgency for r in self.requests),
                min(r.seq for r in self.requests))


def _signature(req: _Request) -> tuple:
    """Batch bucket key: the work spec plus shapes AND dtypes (of A, B and
    the key) so stacking never promotes a request's arrays — results stay
    bit-identical to solo dispatches. Tenancy is deliberately absent."""
    return (req.work, req.A.shape, str(req.A.dtype), req.B.shape,
            str(req.B.dtype), req.key.shape, str(req.key.dtype))


class Scheduler:
    """Admission + continuous batching + EDF ordering (pure queueing).

    Requests live in per-signature buckets; each bucket IS its open batch
    slot — a request joins it on arrival and leaves when the batch
    dispatches (full / deadline-forced / drained) or when it is shed.
    No jax work happens here; the dispatcher owns the device.
    """

    def __init__(self, config: LoopConfig):
        if config.max_batch is not None and config.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {config.max_batch}")
        if config.max_queue is not None and config.max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {config.max_queue}")
        self.config = config
        self._buckets: "collections.OrderedDict[tuple, List[_Request]]" = \
            collections.OrderedDict()
        self._depth = 0

    @property
    def depth(self) -> int:
        """Total queued (not yet dispatched or shed) requests."""
        return self._depth

    def admit(self, req: _Request) -> None:
        """Queue a request into its bucket's open batch slot, or raise
        ``Rejected(SHED_QUEUE_FULL)`` when the depth bound is hit — the
        backpressure signal callers propagate upstream."""
        cfg = self.config
        if cfg.max_queue is not None and self._depth >= cfg.max_queue:
            raise Rejected(
                SHED_QUEUE_FULL,
                f"queue depth limit reached ({self._depth} >= "
                f"{cfg.max_queue} queued requests)")
        self._buckets.setdefault(_signature(req), []).append(req)
        self._depth += 1

    def shed_expired(self, now: float) -> List[_Request]:
        """Remove (and return) every request that has waited past
        ``max_wait`` — the wait-time load-shedding limit."""
        cfg = self.config
        if cfg.max_wait is None:
            return []
        expired: List[_Request] = []
        for sig in list(self._buckets):
            keep = []
            for req in self._buckets[sig]:
                if now - req.arrival > cfg.max_wait:
                    expired.append(req)
                else:
                    keep.append(req)
            self._prune(sig, keep)
        self._depth -= len(expired)
        return expired

    def ready(self, now: float) -> List[_Batch]:
        """Pop every dispatch-ready batch, most urgent first.

        A bucket's open batch is ready when it is **full** (``max_batch``
        members — repeatedly, so a backlog drains in max_batch-sized
        dispatches) or when its most urgent member's deadline budget
        **forces** it (``deadline - now <= dispatch_margin``), however
        few requests it holds. Members leave earliest-deadline-first, so
        an overfull bucket serves its most urgent requests in the first
        batch; batches are returned EDF-ordered across buckets, so a
        late-deadline pile-up in one bucket cannot starve an earlier
        deadline in another.
        """
        cfg = self.config
        batches: List[_Batch] = []
        for sig in list(self._buckets):
            pending = sorted(self._buckets[sig], key=lambda r:
                             (r.urgency, r.seq))
            while cfg.max_batch is not None and \
                    len(pending) >= cfg.max_batch:
                batches.append(_Batch(pending[:cfg.max_batch],
                                      DISPATCH_FULL))
                pending = pending[cfg.max_batch:]
            if pending and pending[0].deadline is not None and \
                    pending[0].deadline - now <= cfg.dispatch_margin:
                batches.append(_Batch(pending, DISPATCH_DEADLINE))
                pending = []
            self._prune(sig, pending)
        self._depth -= sum(len(b.requests) for b in batches)
        batches.sort(key=lambda b: b.urgency)
        return batches

    def force_all(self) -> List[_Batch]:
        """Pop EVERYTHING as one whole-bucket batch per signature (the
        ``drain``/flush path — batch sizes ignore ``max_batch`` so a
        manual flush stays one fused dispatch per shape bucket)."""
        batches = [_Batch(reqs, DISPATCH_DRAIN)
                   for reqs in self._buckets.values() if reqs]
        self._buckets.clear()
        self._depth = 0
        batches.sort(key=lambda b: b.urgency)
        return batches

    def _prune(self, sig: tuple, keep: List[_Request]) -> None:
        if keep:
            self._buckets[sig] = keep
        else:
            self._buckets.pop(sig, None)


class Dispatcher:
    """Executes one ready batch as ONE fused PipelineEngine dispatch.

    Stacks the batch's keys/A/B for the engine's batched/vmapped mode,
    runs the work's plan (or summary spec) through the shared executable
    cache, slices the batched result back out per request, and resolves
    the futures — bit-identical to dispatching each request alone.
    ``pad='pow2'`` replicates the last request up to the next power of two
    before stacking (and discards the padded lanes), bounding the number
    of batch-size executable signatures under variable occupancy;
    replicated lanes cannot move a quality gate because the gate takes a
    max over the batch and duplicates add no new values."""

    def __init__(self, engine: pipeline.PipelineEngine, pad: str = "none"):
        if pad not in ("none", "pow2"):
            raise ValueError(f"pad must be 'none' or 'pow2', got {pad!r}")
        self.engine = engine
        self.pad = pad

    def _padded(self, reqs: List[_Request]) -> List[_Request]:
        if self.pad == "none":
            return reqs
        width = 1 << (len(reqs) - 1).bit_length()
        return reqs + [reqs[-1]] * (width - len(reqs))

    def dispatch(self, batch: _Batch, dispatch_seq: int, now: float) -> None:
        """Run the batch and resolve every member's future."""
        reqs = batch.requests
        lanes = self._padded(reqs)
        keys = jnp.stack([r.key for r in lanes])
        A = jnp.stack([r.A for r in lanes])
        B = jnp.stack([r.B for r in lanes])
        work = reqs[0].work
        if isinstance(work, SummaryWork):
            out = self.engine.summarize(work.spec, keys, A, B, work.tuning)
        else:
            out = self.engine.run(work.plan, keys, A, B)
        for i, req in enumerate(reqs):
            sliced = jax.tree.map(lambda x, i=i: x[i], out)
            req.future._resolve(sliced, dispatch_seq, now)


class ServingLoop:
    """The serving stack: clock + Scheduler + Dispatcher + stats.

    ``submit`` is non-blocking admission (returns a ``ServeFuture`` or
    raises ``Rejected`` — the backpressure signal); ``poll`` advances the
    loop one step (shed expired, dispatch ready); ``drain`` synchronously
    force-flushes everything queued. ``start``/``stop`` run ``poll`` on a
    daemon thread for async serving — admission and result futures are
    thread-safe, and dispatches happen outside the queue lock so slow
    device work never blocks admission.
    """

    def __init__(self, *, engine: Optional[pipeline.PipelineEngine] = None,
                 config: LoopConfig = LoopConfig(),
                 clock: Callable[[], float] = time.monotonic):
        self.engine = engine if engine is not None else pipeline.get_engine()
        self.config = config
        self.clock = clock
        self.scheduler = Scheduler(config)
        self.dispatcher = Dispatcher(self.engine, pad=config.pad)
        self.stats = LoopStats()
        self._lock = threading.Lock()
        self._seq = itertools.count()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    @property
    def depth(self) -> int:
        """Currently queued requests (the backpressure observable)."""
        with self._lock:
            return self.scheduler.depth

    # -- admission ---------------------------------------------------------

    def submit(self, key: jax.Array, A: jax.Array, B: jax.Array, *,
               work: Union[SummaryWork, PipelineWork],
               tenant: Optional[Union[int, str]] = None,
               deadline: Optional[float] = None) -> ServeFuture:
        """Admit one request; returns its future immediately.

        ``tenant`` namespaces the request key through
        ``pipeline.tenant_key`` before batching (None leaves the key
        untouched — bit-compatible with pre-tenant behavior).
        ``deadline`` is the request's SLO budget in seconds from arrival
        (None uses ``config.default_deadline``); the scheduler
        force-dispatches a partial batch rather than let it lapse. Raises
        ``Rejected(SHED_QUEUE_FULL)`` when the queue bound is hit.
        """
        now = self.clock()
        if tenant is not None:
            key = pipeline.tenant_key(key, tenant)
        if deadline is None:
            deadline = self.config.default_deadline
        seq = next(self._seq)
        req = _Request(
            seq=seq, key=key, A=A, B=B, work=work, arrival=now,
            deadline=None if deadline is None else now + deadline,
            future=ServeFuture(seq))
        with self._lock:
            try:
                self.scheduler.admit(req)
            except Rejected as exc:
                self.stats.shed[exc.reason] += 1
                req.future._reject(exc, now)
                raise
            self.stats.admitted += 1
        return req.future

    # -- the loop body -----------------------------------------------------

    def poll(self) -> int:
        """One scheduling step: shed expired requests, then dispatch every
        ready batch (EDF order). Returns the number of dispatches."""
        now = self.clock()
        with self._lock:
            expired = self.scheduler.shed_expired(now)
            for req in expired:
                self.stats.shed[SHED_WAIT_EXCEEDED] += 1
            batches = self.scheduler.ready(now)
        for req in expired:
            req.future._reject(Rejected(
                SHED_WAIT_EXCEEDED,
                f"request {req.seq} waited past max_wait="
                f"{self.config.max_wait}s"), now)
        return self._dispatch_batches(batches)

    def drain(self) -> int:
        """Force-dispatch everything queued, one fused dispatch per shape
        bucket regardless of batch-size limits (the synchronous flush
        path). Returns the number of dispatches."""
        with self._lock:
            batches = self.scheduler.force_all()
        return self._dispatch_batches(batches)

    def _dispatch_batches(self, batches: List[_Batch]) -> int:
        for batch in batches:
            with self._lock:
                self.stats.dispatches += 1
                dispatch_seq = self.stats.dispatches
                self.stats.batched_requests += len(batch.requests)
                self.stats.dispatched[batch.trigger] += 1
            self.dispatcher.dispatch(batch, dispatch_seq, self.clock())
            with self._lock:
                self.stats.completed += len(batch.requests)
        return len(batches)

    # -- background pump ---------------------------------------------------

    def start(self, interval: float = 1e-3) -> None:
        """Pump ``poll`` on a daemon thread every ``interval`` seconds —
        async serving: callers just ``submit`` and wait on futures."""
        if self._thread is not None:
            raise RuntimeError("serving loop already started")
        self._stop.clear()

        def pump():
            while not self._stop.is_set():
                self.poll()
                self._stop.wait(interval)

        self._thread = threading.Thread(target=pump, daemon=True,
                                        name="serving-loop")
        self._thread.start()

    def stop(self, *, drain: bool = True) -> None:
        """Stop the background pump (optionally draining what's queued)."""
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join()
        self._thread = None
        if drain:
            self.drain()


class ServedEstimate(NamedTuple):
    """One serviced request: the step-1 summary, the step-2/3 factors, and
    (for probe-carrying services with ``with_error``/quality-gated modes)
    the a-posteriori ErrorEngine estimate the rank gate read."""

    summary: SketchSummary
    factors: LowRankFactors
    error: Optional[ErrorEstimate] = None


def as_served(result: PipelineResult) -> ServedEstimate:
    """Repackage a per-request ``PipelineResult`` slice as the
    ``ServedEstimate`` the SketchService API serves."""
    return ServedEstimate(result.summary, result.estimate.factors,
                          error=result.estimate.error)
