"""StreamingSummarizer — mergeable one-pass summaries over row chunks.

The paper's whole point is that the Step-1 summary of (A, B) can be built in
a *single pass*; this module makes that operational when the matrices never
fit in memory at once. It factors ``build_summary`` into the four-phase
contract of a mergeable sketch (Tropp et al., "Practical sketching
algorithms for low-rank matrix approximation"):

    init(key, shapes)                       -> StreamState   (empty monoid id)
    update(state, A_chunk, B_chunk, off)    -> StreamState   (absorb rows)
    merge(s1, s2)                           -> StreamState   (associative +)
    finalize(state)                         -> SketchSummary (sqrt the norms)

Because every accumulator field (sketches, *squared* column norms, the
optional held-out probe block ``(A^T B) @ Omega``, and the optional
refinement co-sketch pair ``(A^T B) @ Omega_c`` / ``Psi_c @ (A^T B)``) is
linear in the data rows, ``StreamState`` is a commutative monoid under
``merge``: chunked
ingestion, any merge order, and the one-shot ``build_summary`` backends all
produce the same summary. The randomness
contract is the SummaryEngine's: the projection column for global row ``i``
is a pure function of ``(key, i)`` (gaussian ``fold_in``; SRHT via the
popcount Hadamard identity from one ``srht_plan``), so a chunk's
contribution depends only on its rows' global indices — never on when, where,
or in what order the chunk was seen.

Exactness grades (tested in tests/core/test_streaming.py):

* sequential ingestion at a fixed chunk size ``c`` (rows 0..d in order) is
  **bit-identical** to ``build_summary(backend='scan', block=c)`` — the
  update performs the identical float ops as the scan body;
* merge is **bit-commutative** (float add commutes);
* reassociating the merge tree (different chunk sizes, shuffled arrival,
  distributed psum) agrees to float-reassociation tolerance, the same
  contract the engine's cross-backend parity tests already enforce.

``StreamState`` is a NamedTuple pytree: it jits, vmaps, psums (the
distributed tree-reduction in ``core/distributed.py`` merges per-device
partial states with one all-reduce), and checkpoints
(``ckpt.checkpoint.save_stream_state`` / ``restore_stream_state`` give
resumable passes).

Drifting streams (docs/streaming.md "Drifting streams"): two summary
variants forget old rows so ``stream_factors`` answers "top components
*now*" instead of "top components ever":

* ``StreamingSummarizer(decay=gamma)`` — exponential decay. Every logical
  tick multiplies all previously absorbed mass by ``gamma``. The decay op
  itself (``decay_state`` / ``Summarizer.advance``) only advances an
  *integer timestamp* riding the state; the scalar multiply per block is
  settled lazily at the next update/merge/finalize. Because both sides of
  ``decay(merge(s1, s2)) == merge(decay(s1), decay(s2))`` then perform the
  identical float ops, the law holds *bitwise* — the decayed states stay a
  commutative monoid (property-tested in
  tests/core/test_streaming_drift.py).
* ``WindowedSummarizer(k, n_buckets=b)`` — sliding window over epochs: a
  ring of ``b`` partial ``StreamState`` buckets; the window summary is the
  merge of the live buckets and ``slide`` retires the oldest in O(1) by
  re-initializing one ring slot. Each epoch's bucket derives its
  projection key from the reserved fold ``window_bucket_key(key, epoch)``
  so bucket-local row ids can repeat across epochs without randomness
  collisions (golden-tested in tests/core/test_key_contract.py).

``decay=1.0`` (the default) leaves the decay fields ``None`` — the pytree
structure and every float op are bit-identical to the pre-decay
``StreamState``, so all historical parity/golden suites run unchanged.

>>> import jax, jax.numpy as jnp
>>> key = jax.random.PRNGKey(0)
>>> A = jax.random.normal(key, (64, 6))
>>> B = jax.random.normal(jax.random.fold_in(key, 1), (64, 4))
>>> summ = StreamingSummarizer(k=8)
>>> state = summ.init(key, (64, 6, 4))
>>> state = summ.update(state, A[:32], B[:32], 0)     # rows arrive in chunks
>>> state = summ.update(state, A[32:], B[32:], 32)
>>> s = summ.finalize(state)
>>> (s.A_sketch.shape, s.B_sketch.shape, int(state.rows_seen))
((8, 6), (8, 4), 64)
>>> from repro.core.summary_engine import build_summary
>>> ref = build_summary(key, A, B, 8, backend="reference")
>>> bool(jnp.allclose(s.A_sketch, ref.A_sketch, atol=1e-5))
True
"""
from __future__ import annotations

import collections
import functools
import json
import struct
from typing import Iterable, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.core.summary_engine import (
    METHODS, _cast, _sketch_pair, projection_rows, sparse_summary_pass,
    srht_plan)
from repro.core.types import SketchSummary, SparseRows


class StreamState(NamedTuple):
    """Partial one-pass summary: the mergeable accumulator pytree.

    Norms are carried *squared* (``na2``/``nb2``) so ``merge`` is a plain sum
    on every field — the square root happens once, in ``finalize``.
    ``signs``/``srows`` hold the SRHT plan (None for gaussian); ``key`` is
    carried so a restored checkpoint can keep absorbing rows with the same
    randomness. ``rows_seen`` only tracks coverage for logging/manifests —
    the math never reads it.
    """

    key: Optional[jax.Array]       # base PRNG key (None for wrapped taps)
    A_acc: jax.Array               # (k, n1) running Pi @ A
    B_acc: jax.Array               # (k, n2) running Pi @ B
    na2: jax.Array                 # (n1,) running squared column norms of A
    nb2: jax.Array                 # (n2,) running squared column norms of B
    rows_seen: jax.Array           # () int32 total rows absorbed
    row_high: jax.Array            # () int32 high-water mark: 1 + max absorbed
                                   #    global row id (0 when empty) — what a
                                   #    resumed contiguous cursor starts from
    d_total: jax.Array             # () int32 global streamed dim (-1: unknown)
    signs: Optional[jax.Array]     # (d,) SRHT rademacher signs, else None
    srows: Optional[jax.Array]     # (k,) SRHT sampled Hadamard rows, else None
    omega: Optional[jax.Array] = None      # (n2, p) held-out probes, else None
    probe_acc: Optional[jax.Array] = None  # (n1, p) running (A^T B) @ omega
    decay_rate: Optional[jax.Array] = None  # () f32 per-tick retention gamma
                                            #    in (0, 1); None = no decay
                                            #    (bit-identical legacy path)
    t_state: Optional[jax.Array] = None    # () int32 logical now (advanced by
                                           #    decay_state; None w/o decay)
    t_data: Optional[jax.Array] = None     # () int32 time the accumulators
                                           #    are aged to (t_data <= t_state;
                                           #    the gap is pending decay)
    cosketch_omega: Optional[jax.Array] = None  # (n2, s) co-sketch range test
    cosketch_psi: Optional[jax.Array] = None    # (l, n1) co-range test
    cosketch_Y: Optional[jax.Array] = None      # (n1, s) running (A^T B) Omega_c
    cosketch_W: Optional[jax.Array] = None      # (l, n2) running Psi_c (A^T B)

    @property
    def k(self) -> int:
        """Sketch size."""
        return self.A_acc.shape[0]

    @property
    def n_probes(self) -> int:
        """Held-out probe count p (0 when no probe block is carried)."""
        return 0 if self.probe_acc is None else self.probe_acc.shape[-1]

    @property
    def n_cosketch(self) -> int:
        """Co-sketch width s (0 when no refinement block is carried)."""
        return 0 if self.cosketch_Y is None else self.cosketch_Y.shape[-1]

    @property
    def decayed(self) -> bool:
        """Whether this state carries the exponential-decay time algebra."""
        return self.decay_rate is not None


def _check_mergeable(s1: StreamState, s2: StreamState) -> None:
    """Shape-level compatibility guard (cheap; skips traced fields)."""
    if s1.A_acc.shape != s2.A_acc.shape or s1.B_acc.shape != s2.B_acc.shape:
        raise ValueError(
            f"cannot merge stream states of different shapes: "
            f"{s1.A_acc.shape}/{s1.B_acc.shape} vs "
            f"{s2.A_acc.shape}/{s2.B_acc.shape}")
    if (s1.signs is None) != (s2.signs is None):
        raise ValueError("cannot merge gaussian and srht stream states")
    if (s1.probe_acc is None) != (s2.probe_acc is None):
        raise ValueError("cannot merge a probe-carrying stream state with a "
                         "probe-free one (init both with the same probes=)")
    if (s1.cosketch_Y is None) != (s2.cosketch_Y is None):
        raise ValueError(
            "cannot merge a cosketch-carrying stream state with a "
            "cosketch-free one (init both with the same cosketch=)")
    if (s1.decay_rate is None) != (s2.decay_rate is None):
        raise ValueError(
            "cannot merge a decayed stream state with an undecayed one "
            "(init both with the same decay=)")
    if (s1.decay_rate is not None
            and not isinstance(s1.decay_rate, jax.core.Tracer)
            and not isinstance(s2.decay_rate, jax.core.Tracer)
            and float(s1.decay_rate) != float(s2.decay_rate)):
        raise ValueError(
            f"cannot merge stream states with different decay rates: "
            f"{float(s1.decay_rate)} vs {float(s2.decay_rate)}")


def _check_row_bounds(state: StreamState, lo: int, hi: int) -> None:
    """Eagerly reject global row ids outside [0, d_total).

    Out-of-range ids would otherwise corrupt the summary silently (SRHT
    clamps into the sign vector; gaussian folds in a wrong index). Skipped
    under tracing (concrete values unavailable) — streaming ingestion is
    an eager host loop in practice, so the guard fires where it matters.
    """
    if isinstance(state.d_total, jax.core.Tracer):
        return
    d = int(state.d_total)
    if lo < 0 or hi >= d:
        raise ValueError(
            f"global row ids [{lo}, {hi}] fall outside the declared "
            f"streamed dimension d_total={d} from init()")


def _scale_blocks(state: StreamState, factor) -> StreamState:
    """Multiply every linear accumulator block (sketches, squared norms, the
    probe block, and the co-sketch pair) by one scalar — decay settlement is
    exactly this."""
    return state._replace(
        A_acc=state.A_acc * factor,
        B_acc=state.B_acc * factor,
        na2=state.na2 * factor,
        nb2=state.nb2 * factor,
        probe_acc=(None if state.probe_acc is None
                   else state.probe_acc * factor),
        cosketch_Y=(None if state.cosketch_Y is None
                    else state.cosketch_Y * factor),
        cosketch_W=(None if state.cosketch_W is None
                    else state.cosketch_W * factor))


def _concrete_eq(a, b) -> bool:
    """True when both scalars are concrete and equal (False under tracing —
    the caller then takes the general traceable path)."""
    if isinstance(a, jax.core.Tracer) or isinstance(b, jax.core.Tracer):
        return False
    return int(a) == int(b)


def _settle_state(state: StreamState) -> StreamState:
    """Apply pending decay eagerly: age the accumulators from ``t_data`` up
    to ``t_state`` (one scalar multiply per block; a no-op without decay or
    when nothing is pending)."""
    if state.decay_rate is None or _concrete_eq(state.t_state, state.t_data):
        return state
    factor = state.decay_rate ** (state.t_state - state.t_data)
    return _scale_blocks(state, factor)._replace(t_data=state.t_state)


def decay_state(state: StreamState, dt: int = 1) -> StreamState:
    """Advance the state's logical clock by ``dt`` ticks (the decay op).

    Each tick multiplies all *previously absorbed* mass by the state's
    ``decay_rate`` — but lazily: only the integer timestamp moves here, and
    the scalar multiply per block settles at the next update / merge
    alignment / finalize. That laziness is what makes
    ``decay_state(merge_states(s1, s2), dt)`` bitwise equal to
    ``merge_states(decay_state(s1, dt), decay_state(s2, dt))``: both sides
    run the identical float ops in the identical order. On an undecayed
    state (``decay_rate is None``, i.e. ``decay=1.0``) this is the
    identity. ``dt`` must be a non-negative integer (time only advances).
    """
    if not isinstance(dt, jax.core.Tracer):
        dt = int(dt)
        if dt < 0:
            raise ValueError(
                f"decay_state needs a non-negative tick count, got dt={dt}")
        if dt == 0:
            return state
    if state.decay_rate is None:
        return state
    return state._replace(t_state=state.t_state + jnp.asarray(dt, jnp.int32))


def _align_states(s1: StreamState, s2: StreamState
                  ) -> Tuple[StreamState, StreamState]:
    """Age both decayed operands to the later ``t_data`` so ``merge`` can be
    a plain sum. Symmetric in (s1, s2) — the basis of bitwise merge
    commutativity — and the side already at the common timestamp is left
    untouched."""
    td = jnp.maximum(s1.t_data, s2.t_data)

    def _age(s: StreamState) -> StreamState:
        if _concrete_eq(s.t_data, td):
            return s._replace(t_data=td)
        return _scale_blocks(s, s.decay_rate ** (td - s.t_data)
                             )._replace(t_data=td)

    return _age(s1), _age(s2)


def merge_states(s1: StreamState, s2: StreamState) -> StreamState:
    """Combine summaries of disjoint row sets (the monoid operation).

    A plain sum on every accumulator field: commutative bit-for-bit,
    associative to float reassociation. The key/plan are taken from ``s1``
    (both operands must descend from the same ``init``). Decayed states are
    first aligned to a common data timestamp (the older side is aged by one
    scalar multiply per block); the merged clock is the later of the two —
    so merging never rewinds time, and pending decay stays pending.
    """
    _check_mergeable(s1, s2)
    extra = {}
    if s1.decay_rate is not None:
        s1, s2 = _align_states(s1, s2)
        extra = dict(t_state=jnp.maximum(s1.t_state, s2.t_state),
                     t_data=s1.t_data)
    return s1._replace(
        A_acc=s1.A_acc + s2.A_acc,
        B_acc=s1.B_acc + s2.B_acc,
        na2=s1.na2 + s2.na2,
        nb2=s1.nb2 + s2.nb2,
        rows_seen=s1.rows_seen + s2.rows_seen,
        row_high=jnp.maximum(s1.row_high, s2.row_high),
        probe_acc=(None if s1.probe_acc is None
                   else s1.probe_acc + s2.probe_acc),
        cosketch_Y=(None if s1.cosketch_Y is None
                    else s1.cosketch_Y + s2.cosketch_Y),
        cosketch_W=(None if s1.cosketch_W is None
                    else s1.cosketch_W + s2.cosketch_W),
        **extra)


def tree_merge(states: Sequence[StreamState]) -> StreamState:
    """Log-depth pairwise reduction of partial states (Spark treeAggregate
    shape; associativity makes any reduction tree equivalent)."""
    states = list(states)
    if not states:
        raise ValueError("tree_merge needs at least one state")
    while len(states) > 1:
        nxt = [merge_states(states[i], states[i + 1])
               for i in range(0, len(states) - 1, 2)]
        if len(states) % 2:
            nxt.append(states[-1])
        states = nxt
    return states[0]


def finalize_state(state: StreamState) -> SketchSummary:
    """StreamState -> the Step-1 ``SketchSummary`` (sqrt the squared norms;
    the probe block and its test matrix ride along when carried). Pending
    decay is settled first, so the summary — including the probe block the
    ErrorEngine reads — describes the *decayed* product as of ``t_state``:
    ``estimate_error`` stays unbiased for exactly what the factors
    estimate."""
    state = _settle_state(state)
    return SketchSummary(state.A_acc, state.B_acc,
                         jnp.sqrt(state.na2), jnp.sqrt(state.nb2),
                         probes=state.probe_acc, probe_omega=state.omega,
                         cosketch_Y=state.cosketch_Y,
                         cosketch_W=state.cosketch_W,
                         cosketch_omega=state.cosketch_omega,
                         cosketch_psi=state.cosketch_psi)


def _sparse_pair(A_chunk, B_chunk) -> bool:
    """Whether a chunk pair is ``SparseRows``; both or neither must be."""
    sparse = isinstance(A_chunk, SparseRows)
    if sparse != isinstance(B_chunk, SparseRows):
        raise ValueError("A and B chunks must both be SparseRows or both "
                         "be dense arrays")
    return sparse


@functools.partial(jax.jit, static_argnames=("k", "method", "precision"))
def _chunk_contribution(key, signs, srows, A_chunk, B_chunk, gids, *,
                        k: int, method: str, precision: Optional[str]):
    """(dA, dB, dna2, dnb2) for one chunk of rows with global ids ``gids``.

    Performs the exact float ops of the scan backend's body — the basis of
    the bit-parity guarantee for aligned sequential ingestion. The stages
    carry the names ``sketch`` and ``norms`` in the device trace.
    """
    plan = None if method == "gaussian" else (signs, srows)
    Ac, Bc = _cast(A_chunk, precision), _cast(B_chunk, precision)
    with jax.named_scope("sketch"):
        P = projection_rows(key, gids, k, method=method, plan=plan)
        dA, dB = _sketch_pair(P, Ac, Bc, precision)
    with jax.named_scope("norms"):
        dna2 = jnp.sum(Ac.astype(jnp.float32) ** 2, axis=0)
        dnb2 = jnp.sum(Bc.astype(jnp.float32) ** 2, axis=0)
    return dA, dB, dna2, dnb2


def _same_chunk(A_chunk: SparseRows, B_chunk: SparseRows) -> bool:
    """Whether a sparse pair is one chunk fed as both operands: the same
    object, or one shape over the very same leaf arrays (``device_put``
    rebuilds the wrapper around leaves already on the device). Decided
    from identity alone, since comparing values would read the device: an
    equal copy counts as another chunk."""
    return A_chunk is B_chunk or (
        A_chunk.shape == B_chunk.shape and A_chunk.rows is B_chunk.rows
        and A_chunk.cols is B_chunk.cols and A_chunk.vals is B_chunk.vals)


@functools.partial(jax.jit, static_argnames=("k",))
def _sparse_contribution(key, omega, A_chunk: SparseRows,
                         B_chunk: Optional[SparseRows], gids, *, k: int):
    """(dA, dB, dna2, dnb2, probe delta or None) for one ``SparseRows``
    chunk pair with global row ids ``gids``: the same projection rows as
    the dense path's for those ids, every sum in f32
    (``summary_engine.sparse_summary_pass``).

    ``B_chunk=None`` (static, as the pytree's structure) means that B is
    A: one column pass serves both, and dB and dnb2 come back None, for
    the caller to take dA and dna2 in their place; a program that returned
    one value twice would copy it into a second buffer."""
    P = projection_rows(key, gids, k)
    if B_chunk is None:
        dA, _, dna2, _, dprobe = sparse_summary_pass(P, A_chunk, A_chunk,
                                                     omega)
        return dA, None, dna2, None, dprobe
    return sparse_summary_pass(P, A_chunk, B_chunk, omega)


@functools.partial(jax.jit, static_argnames=("precision",))
def _probe_chunk(omega, A_chunk, B_chunk, *, precision: Optional[str]):
    """(n1, p) probe delta for one chunk — the exact float ops of the
    one-shot ``error_engine.probe_pass`` scan body (bit-parity contract),
    named ``probe`` in the device trace."""
    from repro.core.error_engine import probe_contribution
    with jax.named_scope("probe"):
        return probe_contribution(omega, A_chunk, B_chunk, precision)


@functools.partial(jax.jit, static_argnames=("precision",))
def _cosketch_chunk(omega, psi, A_chunk, B_chunk, *,
                    precision: Optional[str]):
    """(dY, dW) co-sketch delta for one chunk — the exact float ops of the
    one-shot ``refinement.cosketch_pass`` scan body (bit-parity contract)."""
    from repro.core.refinement import cosketch_contribution
    return cosketch_contribution(omega, psi, A_chunk, B_chunk, precision)


class StreamingSummarizer:
    """Chunked/mergeable front-end to the SummaryEngine's single pass.

    Configure once (sketch size, method, precision); then drive any number
    of independent streams through ``init -> update* -> merge* -> finalize``.
    All randomness comes from the ``init`` key via the engine's
    (key, global row index) contract, so the result is independent of
    chunking and merge order, and matches the one-shot ``build_summary``.

    >>> import jax, jax.numpy as jnp
    >>> summ = StreamingSummarizer(k=4, method="srht")
    >>> key = jax.random.PRNGKey(7)
    >>> A = jax.random.normal(key, (32, 5))
    >>> B = jax.random.normal(jax.random.fold_in(key, 1), (32, 3))
    >>> left = summ.init(key, (32, 5, 3))        # two independent workers ...
    >>> right = summ.init(key, (32, 5, 3))
    >>> left = summ.update(left, A[:16], B[:16], 0)
    >>> right = summ.update(right, A[16:], B[16:], 16)
    >>> s = summ.finalize(summ.merge(left, right))   # ... merged associatively
    >>> s.B_sketch.shape
    (4, 3)
    """

    def __init__(self, k: int, *, method: str = "gaussian",
                 precision: Optional[str] = None, probes: int = 0,
                 cosketch: int = 0, decay: float = 1.0):
        if method not in METHODS:
            raise ValueError(
                f"unknown sketch method {method!r} (use {METHODS})")
        if isinstance(decay, bool) or not isinstance(decay, (int, float)) \
                or not 0.0 < float(decay) <= 1.0:
            raise ValueError(
                f"decay must be a retention factor in (0, 1], got {decay!r}")
        self.k = k
        self.method = method
        self.precision = precision
        self.probes = probes
        self.cosketch = cosketch
        self.decay = float(decay)

    # -- contract ----------------------------------------------------------

    def init(self, key: jax.Array, shapes: Tuple[int, int, int]) -> StreamState:
        """Empty state for a (d, n1, n2) stream under ``key``.

        ``d`` is the *global* streamed dimension: every update validates its
        row ids against it, and SRHT additionally derives its sign/sample
        plan from (key, d) here — the one O(d) step; every update is
        O(chunk).
        """
        d, n1, n2 = shapes
        if self.method == "srht":
            signs, srows, _ = srht_plan(key, d, self.k)
        else:
            signs = srows = None
        if self.probes:
            from repro.core.error_engine import probe_omega
            omega = probe_omega(key, n2, self.probes)
            probe_acc = jnp.zeros((n1, self.probes), jnp.float32)
        else:
            omega = probe_acc = None
        if self.cosketch:
            from repro.core.refinement import (
                cosketch_omega, cosketch_psi, cosketch_width)
            c_omega = cosketch_omega(key, n2, self.cosketch)
            c_psi = cosketch_psi(key, n1, self.cosketch)
            c_Y = jnp.zeros((n1, self.cosketch), jnp.float32)
            c_W = jnp.zeros((cosketch_width(self.cosketch), n2), jnp.float32)
        else:
            c_omega = c_psi = c_Y = c_W = None
        if self.decay < 1.0:
            decay_rate = jnp.asarray(self.decay, jnp.float32)
            t_state = t_data = jnp.zeros((), jnp.int32)
        else:
            # decay=1.0 keeps the legacy pytree structure: the None fields
            # flatten to nothing, so every historical bit-parity and
            # checkpoint contract is untouched
            decay_rate = t_state = t_data = None
        return StreamState(
            key=key,
            A_acc=jnp.zeros((self.k, n1), jnp.float32),
            B_acc=jnp.zeros((self.k, n2), jnp.float32),
            na2=jnp.zeros((n1,), jnp.float32),
            nb2=jnp.zeros((n2,), jnp.float32),
            rows_seen=jnp.zeros((), jnp.int32),
            row_high=jnp.zeros((), jnp.int32),
            d_total=jnp.asarray(d, jnp.int32),
            signs=signs, srows=srows, omega=omega, probe_acc=probe_acc,
            decay_rate=decay_rate, t_state=t_state, t_data=t_data,
            cosketch_omega=c_omega, cosketch_psi=c_psi,
            cosketch_Y=c_Y, cosketch_W=c_W)

    def update(self, state: StreamState, A_chunk: jax.Array,
               B_chunk: jax.Array, row_offset) -> StreamState:
        """Absorb a contiguous chunk of rows starting at global ``row_offset``.

        ``row_offset`` may be a traced scalar — recompilation keys only on
        the chunk shape. Chunks may arrive in any order and may even repeat
        across partial states as long as each global row is absorbed exactly
        once overall (the summary is a sum over rows). A zero-row chunk is
        the monoid identity: a no-op. With a concrete ``row_offset`` the
        bounds check costs no device work (the chunk is contiguous).
        """
        t = A_chunk.shape[0]
        if B_chunk.shape[0] != t:
            raise ValueError(f"chunk row counts differ: "
                             f"{A_chunk.shape} vs {B_chunk.shape}")
        if t == 0:
            return state
        if isinstance(row_offset, jax.core.Tracer):
            hi1 = jnp.asarray(row_offset, jnp.int32) + t
        else:
            off = int(row_offset)
            _check_row_bounds(state, off, off + t - 1)
            hi1 = off + t
        gids = (jnp.asarray(row_offset, jnp.int32)
                + jnp.arange(t, dtype=jnp.int32))
        return self._absorb(state, A_chunk, B_chunk, gids, t, hi1)

    def update_rows(self, state: StreamState, row_ids: jax.Array,
                    A_rows: jax.Array, B_rows: jax.Array) -> StreamState:
        """Absorb rows with explicit global ids (arbitrary-order arrival —
        the paper's shuffled co-occurrence stream). An empty id array is
        a no-op (the monoid identity)."""
        t = A_rows.shape[0]
        ids = jnp.asarray(row_ids, jnp.int32)
        if B_rows.shape[0] != t or ids.shape[0] != t:
            raise ValueError(
                f"row ids / chunk row counts differ: ids {ids.shape}, "
                f"A {A_rows.shape}, B {B_rows.shape}")
        if t == 0:
            return state
        if isinstance(ids, jax.core.Tracer):
            hi1 = jnp.max(ids) + 1
        else:
            # one fused device fetch for both bounds
            lo, hi = (int(v) for v in
                      jax.device_get(jnp.stack([jnp.min(ids),
                                                jnp.max(ids)])))
            _check_row_bounds(state, lo, hi)
            hi1 = hi + 1
        return self._absorb(state, A_rows, B_rows, ids, t, hi1)

    def merge(self, s1: StreamState, s2: StreamState) -> StreamState:
        """Alias of ``merge_states`` (module-level, needs no config)."""
        return merge_states(s1, s2)

    def advance(self, state: StreamState, dt: int = 1) -> StreamState:
        """Alias of ``decay_state``: advance the logical clock ``dt`` ticks
        (identity on an undecayed summarizer — ``decay=1.0``)."""
        return decay_state(state, dt)

    def finalize(self, state: StreamState) -> SketchSummary:
        """Alias of ``finalize_state`` (module-level, needs no config)."""
        return finalize_state(state)

    # -- conveniences ------------------------------------------------------

    def summarize_chunks(self, key: jax.Array,
                         shapes: Tuple[int, int, int],
                         chunks: Iterable[Tuple[jax.Array, jax.Array]]
                         ) -> SketchSummary:
        """One-call sequential ingestion: ``(A_chunk, B_chunk)`` pairs in row
        order -> finalized summary."""
        state = self.init(key, shapes)
        off = 0
        for A_chunk, B_chunk in chunks:
            state = self.update(state, A_chunk, B_chunk, off)
            off += A_chunk.shape[0]
        return self.finalize(state)

    def ingest(self, state: StreamState,
               chunks: Iterable[Tuple[jax.Array, jax.Array]], *,
               row_offset: Optional[int] = None,
               prefetch: int = 2) -> StreamState:
        """Double-buffered sequential ingestion of ``(A_chunk, B_chunk)``
        pairs in row order.

        Up to ``prefetch`` upcoming chunks are staged host->device with
        ``jax.device_put`` while the fused update for the current chunk is
        still executing — jax dispatch is asynchronous, so the copy for
        chunk ``c+1`` overlaps chunk ``c``'s compute and the pass approaches
        memory-bandwidth speed instead of alternating copy/compute.
        ``prefetch=0`` degrades to the serial copy-then-update loop.
        The chip benchmark's ``stream4k.ingest`` cell measures this call,
        and ``nytbow.ingest`` with ``SparseRows`` chunks.

        The math is untouched: staging only moves bytes, so ``ingest`` is
        **bit-identical** to the equivalent ``update`` loop at the same
        chunk boundaries (tested in tests/core/test_streaming_ingest.py).
        Chunks start at ``row_offset`` (default: the state's ``row_high``
        cursor — the resume-contiguously convention of ``serve.engine``).

        Each call records ``repro.telemetry`` spans: ``repro.ingest``
        around the whole call and, inside it, ``repro.ingest.feed`` per
        pull from ``chunks`` (one more than the chunks when the source
        ends), ``repro.ingest.stage`` per chunk's ``device_put`` and
        ``repro.ingest.update`` per chunk's ``update`` launch.
        """
        if isinstance(prefetch, bool) or not isinstance(prefetch, int) \
                or prefetch < 0:
            raise ValueError(
                f"prefetch must be a non-negative chunk count, "
                f"got {prefetch!r}")
        with telemetry.span("repro.ingest"):
            off = (int(state.row_high) if row_offset is None
                   else int(row_offset))
            it = iter(chunks)
            staged: collections.deque = collections.deque()

            def _stage_next() -> None:
                nonlocal it
                if it is None:                  # the source has ended
                    return
                try:
                    with telemetry.span("repro.ingest.feed"):
                        A_chunk, B_chunk = next(it)
                except StopIteration:
                    it = None
                    return
                with telemetry.span("repro.ingest.stage"):
                    A_dev = jax.device_put(A_chunk)
                    # a chunk fed as both operands is staged once, so the
                    # update still sees one chunk (one sparse column pass)
                    staged.append((A_dev, A_dev if B_chunk is A_chunk
                                   else jax.device_put(B_chunk)))

            for _ in range(prefetch + 1):       # prime the pipeline
                _stage_next()
            while staged:
                A_chunk, B_chunk = staged.popleft()
                # enqueue the next host->device copy BEFORE dispatching the
                # update when running serial (prefetch=0) would instead wait
                if prefetch:
                    _stage_next()
                with telemetry.span("repro.ingest.update"):
                    state = self.update(state, A_chunk, B_chunk, off)
                off += A_chunk.shape[0]
                if not prefetch:
                    jax.block_until_ready(state.A_acc)
                    _stage_next()
        return state

    def _absorb(self, state, A_chunk, B_chunk, gids, t, hi1) -> StreamState:
        if A_chunk.shape[0] != B_chunk.shape[0]:
            raise ValueError(f"chunk row counts differ: "
                             f"{A_chunk.shape} vs {B_chunk.shape}")
        sparse = _sparse_pair(A_chunk, B_chunk)
        if sparse:
            off = [name for name, on in (
                ("method='srht'", self.method == "srht"),
                ("cosketch", state.cosketch_Y is not None),
                ("decay", state.decay_rate is not None),
                ("precision='bf16'", self.precision == "bf16")) if on]
            if off:
                raise NotImplementedError(
                    f"a SparseRows chunk cannot be absorbed with "
                    f"{', '.join(off)}: the sparse path is gaussian and f32, "
                    f"without co-sketch or decay (docs/streaming.md, "
                    f"'Sparse rows')")
        # Settle pending decay *before* absorbing: new rows enter at weight
        # 1 (they arrive "now"), old mass is physically scaled down so
        # accumulator magnitudes stay bounded on long decayed streams.
        state = _settle_state(state)
        probe_acc = state.probe_acc
        if sparse:
            shared = _same_chunk(A_chunk, B_chunk)
            dA, dB, dna2, dnb2, dprobe = _sparse_contribution(
                state.key, state.omega, A_chunk,
                None if shared else B_chunk, gids, k=self.k)
            if shared:
                telemetry.count("repro.sparse.shared_pass")
                dB, dnb2 = dA, dna2
            if dprobe is not None:
                probe_acc = probe_acc + dprobe
        else:
            dA, dB, dna2, dnb2 = _chunk_contribution(
                state.key, state.signs, state.srows, A_chunk, B_chunk,
                gids, k=self.k, method=self.method,
                precision=self.precision)
            if state.omega is not None:
                probe_acc = probe_acc + _probe_chunk(
                    state.omega, A_chunk, B_chunk, precision=self.precision)
        c_Y, c_W = state.cosketch_Y, state.cosketch_W
        if state.cosketch_omega is not None:
            dY, dW = _cosketch_chunk(
                state.cosketch_omega, state.cosketch_psi, A_chunk, B_chunk,
                precision=self.precision)
            c_Y, c_W = c_Y + dY, c_W + dW
        return state._replace(
            A_acc=state.A_acc + dA, B_acc=state.B_acc + dB,
            na2=state.na2 + dna2, nb2=state.nb2 + dnb2,
            rows_seen=state.rows_seen + jnp.int32(t),
            row_high=jnp.maximum(state.row_high,
                                 jnp.asarray(hi1, jnp.int32)),
            probe_acc=probe_acc, cosketch_Y=c_Y, cosketch_W=c_W)


# -- wire format: compressed StreamState for checkpoints and transfer --------

#: sketch-block precisions a WireSpec may name, cheapest-last
WIRE_DTYPES = ("f32", "bf16", "int8")


class WireSpec(NamedTuple):
    """On-the-wire precision policy for a compressed ``StreamState``.

    One knob: the storage dtype of the *sketch-shaped* blocks (the two
    sketches and, when carried, the co-sketch pair) — they dominate the
    state's bytes and are noise-floored by sketching error anyway. The
    squared-norm vectors and the held-out probe block always stay f32: the
    norms are the rescaled estimator's whole advantage, and the probe block
    is the exact side information that *measures* what quantization cost
    (``wire_error``), so it must not itself be quantized. A NamedTuple of
    one string: hashable, so it can ride ``PipelinePlan`` as a cache key.

    >>> WireSpec("bf16").bits
    16
    >>> WireSpec() == WireSpec("f32")   # default: lossless
    True
    """

    sketch: str = "f32"

    @property
    def bits(self) -> int:
        """Storage bits per sketch-block value."""
        return {"f32": 32, "bf16": 16, "int8": 8}[self.sketch]


class CompressedState(NamedTuple):
    """Arrays-only wire image of a *settled* ``StreamState``.

    Everything derivable from ``key`` is dropped: the probe test matrix,
    the co-sketch test pair, and the SRHT sign/sample plan are pure
    functions of ``(key, shape)`` under the engine's randomness contract,
    so ``decompress_state`` regenerates them bit-identically instead of
    shipping them. ``srht`` is a 0/1 scalar recording which method's plan
    to rebuild. Pending decay is settled by ``compress_state``, so only
    ``t_state`` travels (``t_data == t_state`` on arrival). ``*_scale``
    fields are the per-slice symmetric dequantization scales (int8 only).
    """

    key: jax.Array
    A_blk: jax.Array                     # (k, n1) sketch, spec dtype
    B_blk: jax.Array                     # (k, n2) sketch, spec dtype
    na2: jax.Array                       # (n1,) f32 — never quantized
    nb2: jax.Array                       # (n2,) f32 — never quantized
    rows_seen: jax.Array
    row_high: jax.Array
    d_total: jax.Array
    srht: jax.Array                      # () int32: 1 = rebuild an SRHT plan
    A_scale: Optional[jax.Array] = None  # (k, 1) int8 dequant scales
    B_scale: Optional[jax.Array] = None  # (k, 1)
    probe_acc: Optional[jax.Array] = None   # (n1, p) f32 — never quantized
    decay_rate: Optional[jax.Array] = None
    t_state: Optional[jax.Array] = None
    cosketch_Y: Optional[jax.Array] = None  # (n1, s) spec dtype
    cosketch_W: Optional[jax.Array] = None  # (l, n2) spec dtype
    Y_scale: Optional[jax.Array] = None     # (1, s) int8 dequant scales
    W_scale: Optional[jax.Array] = None     # (l, 1)


def _as_wire_spec(spec: Union[WireSpec, str]) -> WireSpec:
    spec = WireSpec(spec) if isinstance(spec, str) else spec
    if not isinstance(spec, WireSpec) or spec.sketch not in WIRE_DTYPES:
        raise ValueError(
            f"wire spec must name a sketch dtype in {WIRE_DTYPES}, "
            f"got {spec!r}")
    return spec


def _quant_block(x: jax.Array, spec: WireSpec, axis: int):
    """(stored block, dequant scale or None) for one sketch-shaped block.

    int8 is symmetric per-slice along ``axis`` (scale = max|x| / 127 with
    keepdims, clamped away from zero so all-zero slices stay exact zeros).
    """
    if spec.sketch == "f32":
        return x, None
    if spec.sketch == "bf16":
        return x.astype(jnp.bfloat16), None
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        jnp.float32(1e-30)) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant_block(blk: jax.Array, scale: Optional[jax.Array]) -> jax.Array:
    if blk.dtype == jnp.int8:
        return blk.astype(jnp.float32) * scale
    return blk.astype(jnp.float32)


def compress_state(state: StreamState,
                   spec: Union[WireSpec, str] = WireSpec()
                   ) -> CompressedState:
    """StreamState -> its wire image under ``spec``.

    Settles pending decay first (the wire carries one timestamp), then
    stores the sketch-shaped blocks at the spec's precision and everything
    else f32. With the default f32 spec, ``decompress_state`` returns a
    state **bit-identical** to the settled input — structure included
    (property-tested in tests/core/test_streaming_ingest.py).
    """
    spec = _as_wire_spec(spec)
    if state.key is None:
        raise ValueError(
            "compress_state needs the state's base key: the wire format "
            "regenerates the probe/co-sketch test matrices and the SRHT "
            "plan from it instead of shipping them")
    state = _settle_state(state)
    A_blk, A_scale = _quant_block(state.A_acc, spec, 1)
    B_blk, B_scale = _quant_block(state.B_acc, spec, 1)
    c_Y = c_W = Y_s = W_s = None
    if state.cosketch_Y is not None:
        c_Y, Y_s = _quant_block(state.cosketch_Y, spec, 0)
        c_W, W_s = _quant_block(state.cosketch_W, spec, 1)
    return CompressedState(
        key=state.key, A_blk=A_blk, B_blk=B_blk,
        na2=state.na2, nb2=state.nb2,
        rows_seen=state.rows_seen, row_high=state.row_high,
        d_total=state.d_total,
        srht=jnp.asarray(0 if state.signs is None else 1, jnp.int32),
        A_scale=A_scale, B_scale=B_scale,
        probe_acc=state.probe_acc,
        decay_rate=state.decay_rate, t_state=state.t_state,
        cosketch_Y=c_Y, cosketch_W=c_W, Y_scale=Y_s, W_scale=W_s)


def decompress_state(comp: CompressedState) -> StreamState:
    """Wire image -> a full ``StreamState`` ready to keep absorbing rows.

    Rebuilds every key-derived field (probe omega, co-sketch test pair,
    SRHT plan) from ``comp.key`` — bit-identical to the originals by the
    (key, index) randomness contract — and dequantizes the sketch blocks.
    """
    k, n1 = comp.A_blk.shape
    n2 = comp.B_blk.shape[1]
    if int(comp.srht):
        signs, srows, _ = srht_plan(comp.key, int(comp.d_total), k)
    else:
        signs = srows = None
    omega = None
    if comp.probe_acc is not None:
        from repro.core.error_engine import probe_omega
        omega = probe_omega(comp.key, n2, comp.probe_acc.shape[1])
    c_omega = c_psi = c_Y = c_W = None
    if comp.cosketch_Y is not None:
        from repro.core.refinement import cosketch_omega, cosketch_psi
        s = comp.cosketch_Y.shape[1]
        c_omega = cosketch_omega(comp.key, n2, s)
        c_psi = cosketch_psi(comp.key, n1, s)
        c_Y = _dequant_block(comp.cosketch_Y, comp.Y_scale)
        c_W = _dequant_block(comp.cosketch_W, comp.W_scale)
    return StreamState(
        key=comp.key,
        A_acc=_dequant_block(comp.A_blk, comp.A_scale),
        B_acc=_dequant_block(comp.B_blk, comp.B_scale),
        na2=comp.na2, nb2=comp.nb2,
        rows_seen=comp.rows_seen, row_high=comp.row_high,
        d_total=comp.d_total, signs=signs, srows=srows,
        omega=omega, probe_acc=comp.probe_acc,
        decay_rate=comp.decay_rate,
        t_state=comp.t_state, t_data=comp.t_state,
        cosketch_omega=c_omega, cosketch_psi=c_psi,
        cosketch_Y=c_Y, cosketch_W=c_W)


def wire_bytes(comp: CompressedState) -> int:
    """Payload bytes of a wire image (array bytes; the pack header — a few
    dozen bytes of field names — is excluded)."""
    return sum(int(leaf.nbytes) for leaf in comp if leaf is not None)


def wire_pack(comp: CompressedState) -> bytes:
    """Serialize a wire image to self-describing bytes (a JSON field header
    + raw little-endian array payloads) — what actually crosses hosts in
    ``dist.multihost.cross_host_merge`` and lands in compressed
    checkpoints' transport tests."""
    import numpy as np
    header, payload = [], []
    for name, leaf in zip(comp._fields, comp):
        if leaf is None:
            continue
        # NOTE: not ascontiguousarray — it promotes 0-d scalars to 1-d,
        # and tobytes() already serialises any layout in C order
        arr = np.asarray(leaf)
        header.append({"field": name, "dtype": str(arr.dtype),
                       "shape": list(arr.shape)})
        payload.append(arr.tobytes())
    head = json.dumps(header).encode("utf-8")
    return struct.pack("<I", len(head)) + head + b"".join(payload)


def wire_unpack(data: bytes) -> CompressedState:
    """Inverse of ``wire_pack``."""
    import numpy as np
    (hlen,) = struct.unpack_from("<I", data, 0)
    header = json.loads(data[4:4 + hlen].decode("utf-8"))
    off = 4 + hlen
    kw = {}
    for field in header:
        dt = np.dtype(field["dtype"])
        count = 1
        for dim in field["shape"]:
            count *= int(dim)
        arr = np.frombuffer(data, dtype=dt, count=count, offset=off)
        kw[field["field"]] = jnp.asarray(arr.reshape(field["shape"]))
        off += dt.itemsize * count
    return CompressedState(**kw)


def wire_error(state: StreamState, spec: Union[WireSpec, str]) -> float:
    """Probe-measured relative error a round-trip through ``spec`` adds.

    The held-out probe block ``b_j = (A^T B) w_j`` is *exact* side
    information riding the state, so quantization cost is measurable
    without ever forming the n1 x n2 product: sketch-estimate each probe
    from the original and the decompressed state (``A_acc^T (B_acc w_j)``,
    O(k·n·p)), and return

        sqrt(mean_j ||dev_j||^2 / ||w_j||^2) / ||M||_F_est,

    where ``dev_j`` is the per-probe deviation and ``||M||_F_est`` is the
    ErrorEngine's unbiased Frobenius estimate from the exact probe block —
    the same estimator ``estimate_error`` applies to the decompressed
    summary. f32 round-trips are bit-identical, so their error is exactly
    0.0; the result feeds the ``choose_wire_spec`` gate.
    """
    if state.omega is None:
        raise ValueError(
            "wire_error needs the held-out probe block (init the stream "
            "with probes>0) — it is the exact reference quantization "
            "error is measured against")
    spec = _as_wire_spec(spec)
    settled = _settle_state(state)
    rt = decompress_state(compress_state(settled, spec))
    w = settled.omega

    def sketch_probe(s: StreamState) -> jax.Array:
        return s.A_acc.T @ (s.B_acc @ w)        # ~ M @ w, never n1 x n2

    dev = sketch_probe(rt) - sketch_probe(settled)
    wn2 = jnp.sum(w.astype(jnp.float32) ** 2, axis=0)
    frob_dev = jnp.sqrt(jnp.mean(jnp.sum(dev ** 2, axis=0) / wn2))
    frob_m = jnp.sqrt(jnp.mean(
        jnp.sum(settled.probe_acc ** 2, axis=0) / wn2))
    return float(frob_dev / jnp.maximum(frob_m, jnp.float32(1e-30)))


def choose_wire_spec(state: StreamState, tol: float,
                     specs: Sequence[Union[WireSpec, str]] =
                     ("int8", "bf16", "f32")
                     ) -> Tuple[WireSpec, float]:
    """The probe-measured compression gate: cheapest spec meeting ``tol``.

    Tries ``specs`` in order (fewest wire bytes first) and returns the
    first whose ``wire_error`` is within ``tol``, with the measured error.
    f32 is lossless (error exactly 0.0), so the gate is total: when no
    candidate meets ``tol`` it falls back to f32. Used before checkpoint
    writes
    (``ckpt.checkpoint.save_stream_state(wire="auto")``) and inter-host
    transfer (``dist.multihost.cross_host_merge``).
    """
    if isinstance(tol, bool) or not isinstance(tol, (int, float)) \
            or not float(tol) > 0.0:
        raise ValueError(
            f"gate tolerance must be a positive relative error, got {tol!r}")
    for spec in specs:
        spec = _as_wire_spec(spec)
        err = 0.0 if spec.sketch == "f32" else wire_error(state, spec)
        if err <= float(tol):
            return spec, err
    return WireSpec("f32"), 0.0   # lossless meets any tolerance


# -- sliding window over epochs ----------------------------------------------

_WINDOW_TAG = 0x77647721  # ascii "wdw!" — reserved fold tag for bucket keys


def window_bucket_key(key: jax.Array, epoch) -> jax.Array:
    """Projection key for the window bucket holding ``epoch``.

    Two-level reserved fold (the tenant/probe scheme): fold the window tag
    first, then the epoch — so bucket keys can never collide with row folds,
    tenant folds, or probe folds of the same base key, and bucket-local row
    ids may repeat across epochs without reusing projection columns.
    Golden-pinned in tests/core/test_key_contract.py.
    """
    if not isinstance(epoch, jax.core.Tracer):
        epoch = int(epoch)
        if epoch < 0:
            raise ValueError(
                f"window epoch must be non-negative, got {epoch}")
    return jax.random.fold_in(jax.random.fold_in(key, _WINDOW_TAG), epoch)


class WindowState(NamedTuple):
    """Sliding-window summary: a ring of per-epoch partial ``StreamState``s.

    ``buckets[e % n_buckets]`` holds epoch ``e``'s rows; ``head`` is the
    newest live epoch, so the window always covers epochs
    ``head - n_buckets + 1 .. head`` (every slot is live — a fresh window
    starts at ``head = n_buckets - 1`` over all-empty past epochs). The
    whole thing is a pytree: it checkpoints via
    ``ckpt.checkpoint.save_window_state`` with ``head`` in the manifest.
    """

    key: jax.Array                    # base PRNG key (bucket keys fold from it)
    buckets: Tuple[StreamState, ...]  # ring; slot e % n_buckets holds epoch e
    head: jax.Array                   # () int32 newest live epoch

    @property
    def n_buckets(self) -> int:
        """Ring size (the window length in epochs)."""
        return len(self.buckets)


def _dense_only(A_chunk, B_chunk):
    """The pair, unless it is ``SparseRows``, which windows do not take."""
    if _sparse_pair(A_chunk, B_chunk):
        raise NotImplementedError(
            "the windowed summarizer does not take SparseRows chunks "
            "(docs/streaming.md, 'Sparse rows')")
    return A_chunk, B_chunk


class WindowedSummarizer:
    """Sliding-window front-end: the summary of the last ``n_buckets`` epochs.

    Keeps a ring of ``n_buckets`` partial ``StreamState``s (one per epoch,
    each under its own ``window_bucket_key``); the window summary is the
    merge of the live buckets, and ``slide`` retires the oldest epoch in
    O(1) by re-initializing a single ring slot — no rescan, no subtraction.
    Updates land in the head epoch with *bucket-local* row ids (each epoch
    is its own 0..d-1 row space). The ring bookkeeping is host-side eager
    (slot selection needs a concrete ``head``); the per-bucket math is the
    jitted StreamingSummarizer path unchanged.

    With probes, every bucket shares the *base* key's probe test matrix
    (``probe_omega(key, n2, p)``) — probe blocks are linear in the data, so
    they merge across buckets only against a common omega, and the window's
    ``estimate_error`` stays unbiased for the windowed product.

    >>> import jax, jax.numpy as jnp
    >>> win = WindowedSummarizer(k=4, n_buckets=2)
    >>> key = jax.random.PRNGKey(0)
    >>> A = jax.random.normal(key, (8, 3))
    >>> B = jax.random.normal(jax.random.fold_in(key, 1), (8, 2))
    >>> w = win.init(key, (8, 3, 2))
    >>> w = win.update(w, A, B, 0)   # rows land in the head epoch
    >>> w = win.slide(w)             # next epoch opens, oldest expires
    >>> int(jnp.sum(win.merged(w).rows_seen))   # still inside the window
    8
    >>> w = win.slide(w)             # the epoch holding those rows expires
    >>> bool(jnp.all(win.finalize(w).A_sketch == 0))
    True
    """

    def __init__(self, k: int, n_buckets: int, *,
                 method: str = "gaussian",
                 precision: Optional[str] = None, probes: int = 0,
                 cosketch: int = 0):
        if isinstance(n_buckets, bool) or not isinstance(n_buckets, int) \
                or n_buckets < 1:
            raise ValueError(
                f"n_buckets must be a positive int (the window length in "
                f"epochs), got {n_buckets!r}")
        self.n_buckets = n_buckets
        self._inner = StreamingSummarizer(
            k, method=method, precision=precision, probes=probes,
            cosketch=cosketch)

    @property
    def k(self) -> int:
        """Sketch size of every bucket."""
        return self._inner.k

    @property
    def method(self) -> str:
        """Sketch method of every bucket."""
        return self._inner.method

    @property
    def probes(self) -> int:
        """Held-out probe count carried by every bucket."""
        return self._inner.probes

    @property
    def cosketch(self) -> int:
        """Co-sketch width carried by every bucket."""
        return self._inner.cosketch

    def _fresh_bucket(self, key, shapes, epoch, omega,
                      cpair=None) -> StreamState:
        bucket = self._inner.init(window_bucket_key(key, epoch), shapes)
        if omega is not None:
            # all buckets share the BASE key's probe matrix: probe blocks
            # only sum across buckets against a common omega
            bucket = bucket._replace(omega=omega)
        if cpair is not None:
            # same sharing for the co-sketch test pair: (Y, W) blocks only
            # sum across buckets against a common (Omega_c, Psi_c)
            bucket = bucket._replace(cosketch_omega=cpair[0],
                                     cosketch_psi=cpair[1])
        return bucket

    def init(self, key: jax.Array,
             shapes: Tuple[int, int, int]) -> WindowState:
        """Empty window for a (d, n1, n2) stream: ``head = n_buckets - 1``
        over all-empty epochs ``0 .. n_buckets - 1`` (``d`` is the per-epoch
        row space — bucket-local ids restart each epoch)."""
        if self._inner.probes:
            from repro.core.error_engine import probe_omega
            omega = probe_omega(key, shapes[2], self._inner.probes)
        else:
            omega = None
        if self._inner.cosketch:
            from repro.core.refinement import cosketch_omega, cosketch_psi
            cpair = (cosketch_omega(key, shapes[2], self._inner.cosketch),
                     cosketch_psi(key, shapes[1], self._inner.cosketch))
        else:
            cpair = None
        buckets = tuple(self._fresh_bucket(key, shapes, e, omega, cpair)
                        for e in range(self.n_buckets))
        return WindowState(key=key, buckets=buckets,
                           head=jnp.asarray(self.n_buckets - 1, jnp.int32))

    def _check_ring(self, wstate: WindowState) -> None:
        if len(wstate.buckets) != self.n_buckets:
            raise ValueError(
                f"window state carries {len(wstate.buckets)} buckets but "
                f"this summarizer expects n_buckets={self.n_buckets}")

    def _with_head_bucket(self, wstate, bucket) -> WindowState:
        slot = int(wstate.head) % self.n_buckets
        buckets = list(wstate.buckets)
        buckets[slot] = bucket
        return wstate._replace(buckets=tuple(buckets))

    def update(self, wstate: WindowState, A_chunk, B_chunk,
               row_offset) -> WindowState:
        """Absorb a contiguous chunk into the head epoch (bucket-local
        ``row_offset``)."""
        self._check_ring(wstate)
        _dense_only(A_chunk, B_chunk)
        slot = int(wstate.head) % self.n_buckets
        return self._with_head_bucket(wstate, self._inner.update(
            wstate.buckets[slot], A_chunk, B_chunk, row_offset))

    def update_rows(self, wstate: WindowState, row_ids, A_rows,
                    B_rows) -> WindowState:
        """Absorb rows with explicit bucket-local ids into the head epoch."""
        self._check_ring(wstate)
        _dense_only(A_rows, B_rows)
        slot = int(wstate.head) % self.n_buckets
        return self._with_head_bucket(wstate, self._inner.update_rows(
            wstate.buckets[slot], row_ids, A_rows, B_rows))

    def ingest(self, wstate: WindowState,
               chunks: Iterable[Tuple[jax.Array, jax.Array]], *,
               row_offset: Optional[int] = None,
               prefetch: int = 2) -> WindowState:
        """Double-buffered ingestion into the head epoch: delegates to the
        inner ``StreamingSummarizer.ingest`` on the head bucket (same
        overlap, same bit-parity contract, bucket-local row ids)."""
        self._check_ring(wstate)
        chunks = (_dense_only(A, B) for A, B in chunks)
        slot = int(wstate.head) % self.n_buckets
        return self._with_head_bucket(wstate, self._inner.ingest(
            wstate.buckets[slot], chunks, row_offset=row_offset,
            prefetch=prefetch))

    def slide(self, wstate: WindowState, n: int = 1) -> WindowState:
        """Advance the window by ``n`` epochs — O(1) per epoch: the expiring
        slot is re-initialized (under the *new* epoch's bucket key), nothing
        else is touched."""
        self._check_ring(wstate)
        if isinstance(n, bool) or not isinstance(n, int) or n < 1:
            raise ValueError(
                f"slide needs a positive epoch count, got {n!r}")
        ref = wstate.buckets[0]
        shapes = (int(ref.d_total), ref.A_acc.shape[1], ref.B_acc.shape[1])
        cpair = (None if ref.cosketch_omega is None
                 else (ref.cosketch_omega, ref.cosketch_psi))
        head = int(wstate.head)
        buckets = list(wstate.buckets)
        for _ in range(n):
            head += 1
            buckets[head % self.n_buckets] = self._fresh_bucket(
                wstate.key, shapes, head, ref.omega, cpair)
        return wstate._replace(buckets=tuple(buckets),
                               head=jnp.asarray(head, jnp.int32))

    def merged(self, wstate: WindowState) -> StreamState:
        """The window as one ``StreamState``: live buckets merged in
        ascending epoch order (a fixed merge tree, so a window rebuilt from
        the same buckets merges bit-identically)."""
        self._check_ring(wstate)
        head = int(wstate.head)
        return tree_merge([wstate.buckets[e % self.n_buckets]
                           for e in range(head - self.n_buckets + 1,
                                          head + 1)])

    def finalize(self, wstate: WindowState) -> SketchSummary:
        """Finalize the merged window into a Step-1 ``SketchSummary``."""
        return finalize_state(self.merged(wstate))
