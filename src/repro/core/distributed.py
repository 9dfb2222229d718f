"""Distributed one-pass summary: the Spark treeAggregate as TPU collectives.

The streamed dimension d (rows of A, B) is sharded across a mesh axis. Each
device sketches its local row shard with *its slice of the global Pi* (rows of
Pi are indexed by global row id, so the math is identical to the single-device
pass), then a single ``psum`` aggregates sketches and squared column norms.
This is exactly the paper's distributed design: sketch-contributions form a
commutative monoid; Spark's shuffle tree becomes one ICI all-reduce.

Also provides the row-sharded distributed WAltMin: U rows live on the devices
that own them, V is replicated (it is n2 x r — tiny), each half-iteration is
embarrassingly parallel over rows followed by a psum for the V-side normal
equations.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from repro.core.types import LowRankFactors, SketchSummary

# ``axis`` arguments accept a single mesh axis name (flat all-reduce) or an
# ``(outer, inner)`` pair — e.g. ``("host", "device")`` — for the
# hierarchical tree-reduce: intra-host psum over local devices first, then
# one inter-host all-reduce per accumulator block.


def _reduce_axes(mesh: Mesh, axis) -> tuple[str, ...]:
    """Normalize ``axis`` to the reduction hierarchy (outer..inner)."""
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    if not axes or not all(isinstance(a, str) and a in mesh.shape
                           for a in axes):
        raise ValueError(
            f"axis must name mesh axes out of {tuple(mesh.shape)}, "
            f"got {axis!r}")
    return axes


def _axes_size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    size = 1
    for ax in axes:
        size *= mesh.shape[ax]
    return size


def _shard_index(mesh: Mesh, axes: tuple[str, ...]) -> jax.Array:
    """Global shard position: row-major over the hierarchy (the same order
    ``PartitionSpec((outer, inner))`` lays rows out in)."""
    idx = jax.lax.axis_index(axes[0])
    for ax in axes[1:]:
        idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
    return idx


def _block_psum(x: jax.Array, axes: tuple[str, ...]) -> jax.Array:
    """Hierarchical tree-reduce for the large accumulator blocks: psum the
    innermost (device) level first, then one all-reduce per outer (host)
    level — merge is a plain sum, so this is the flat psum reassociated
    (bit-commutative; equal up to float reassociation tolerance)."""
    for ax in reversed(axes):
        x = jax.lax.psum(x, ax)
    return x


def _scalar_psum(x: jax.Array, axes: tuple[str, ...]) -> jax.Array:
    """Single fused all-reduce over the whole hierarchy for the tiny
    squared-norm vectors — one collective over the same devices in the same
    order as the flat path, so norms stay **bit-exact** between the
    hierarchical and flat reductions (pinned by tests/dist)."""
    return jax.lax.psum(x, axes if len(axes) > 1 else axes[0])


def _pad_rows(X: jax.Array, rows: int) -> jax.Array:
    """Zero-pad the leading (row) dim up to ``rows``. Zero rows are exact
    identities for every accumulator: they contribute 0.0 to sketches,
    squared norms, probes, and co-sketches alike."""
    pad = rows - X.shape[0]
    return X if pad == 0 else jnp.pad(X, ((0, pad),) + ((0, 0),) *
                                      (X.ndim - 1))


def distributed_sketch_summary(mesh: Mesh, axis, key: jax.Array,
                               A: jax.Array, B: jax.Array, k: int,
                               method: str = "gaussian",
                               precision: str | None = None
                               ) -> SketchSummary:
    """One-pass summary with A, B sharded over rows (the d axis) on ``axis``.

    The projection operator is never materialized globally: each shard
    generates the operator columns for its own global row range from
    (key, global_row_index) via the SummaryEngine's shared randomness
    contract — identical values regardless of the number of shards (the
    srht sign/sample plan is derived from ``key`` alone, so it is the same
    on every shard). Registered as the engine's 'distributed' backend.

    ``axis`` may be one mesh axis (flat all-reduce) or an
    ``(outer, inner)`` hierarchy such as ``("host", "device")`` — the
    sketch blocks then tree-reduce intra-host first, one inter-host
    all-reduce per block. A ragged ``d`` (not a multiple of the shard
    count) is handled by zero-padding the trailing shard: zero rows are
    exact identities, and the SRHT plan is still derived from the *real*
    ``d``, so the summary is bit-identical to passing pre-padded inputs.
    """
    from repro.core.summary_engine import (
        _cast, _sketch_dot, pi_rows, srht_plan, srht_rows_from_plan)
    axes = _reduce_axes(mesh, axis)
    n_shards = _axes_size(mesh, axes)
    d = A.shape[0]
    d_pad = -(-d // n_shards) * n_shards
    shard_rows = d_pad // n_shards
    if method == "srht":
        # the plan is shard-independent (derived from key alone, for the
        # REAL d); jax's no-replacement sampler does not trace inside
        # shard_map, so derive it once here and close over it (replicated)
        signs, srows, _ = srht_plan(key, d, k)
    elif method != "gaussian":
        raise ValueError(f"unknown sketch method {method!r}")

    def _local_pass(A_loc, B_loc):
        row0 = _shard_index(mesh, axes) * shard_rows
        gids = row0 + jnp.arange(shard_rows)
        if method == "gaussian":
            P_loc = pi_rows(key, gids, k)
        else:
            # clamp padded ids into the sign vector: their operator values
            # are arbitrary, but they only ever multiply zero-padded rows
            P_loc = srht_rows_from_plan(signs[jnp.minimum(gids, d - 1)],
                                        srows, gids, k)
        Ac = _cast(A_loc, precision)
        Bc = _cast(B_loc, precision)
        As = _block_psum(_sketch_dot(P_loc, Ac, precision), axes)
        Bs = _block_psum(_sketch_dot(P_loc, Bc, precision), axes)
        na2 = _scalar_psum(jnp.sum(Ac.astype(jnp.float32) ** 2, axis=0), axes)
        nb2 = _scalar_psum(jnp.sum(Bc.astype(jnp.float32) ** 2, axis=0), axes)
        return SketchSummary(As, Bs, jnp.sqrt(na2), jnp.sqrt(nb2))

    spec = P(axes if len(axes) > 1 else axes[0], None)
    fn = shard_map(
        _local_pass, mesh=mesh,
        in_specs=(spec, spec),
        out_specs=SketchSummary(P(None, None), P(None, None), P(None), P(None)),
    )
    return fn(_pad_rows(A, d_pad), _pad_rows(B, d_pad))


def distributed_streaming_update(mesh: Mesh, axis, summarizer,
                                 state, A_slab: jax.Array, B_slab: jax.Array,
                                 row_offset: int = 0):
    """Absorb a row-sharded slab into a replicated ``StreamState``.

    The slab's rows (global ids ``row_offset .. row_offset + slab_d``) are
    sharded over ``axis``; each device computes its shard's contribution with
    its slice of the global projection (the engine's (key, global row id)
    contract), then ONE psum per block merges the per-device partial states —
    the all-reduce IS the ``streaming.merge`` tree-reduction, executed on the
    ICI (Spark's treeAggregate combiner collapsed into a collective). The
    merged state is returned replicated, ready for the next slab or
    ``finalize``.

    ``axis`` may be an ``(outer, inner)`` hierarchy — ``("host",
    "device")`` — in which case the sketch/probe/co-sketch blocks
    tree-reduce intra-host first and cross hosts once per block, while the
    tiny squared-norm vectors take a single fused all-reduce (bit-exact
    with the flat path). A ragged slab is zero-padded onto the trailing
    shard (zero rows are exact identities); ``rows_seen``/``row_high``
    track the *real* row count.
    """
    from repro.core.streaming import StreamState, merge_states
    axes = _reduce_axes(mesh, axis)
    n_shards = _axes_size(mesh, axes)
    slab_d = A_slab.shape[0]
    slab_pad = -(-slab_d // n_shards) * n_shards
    shard_rows = slab_pad // n_shards
    key, signs, srows = state.key, state.signs, state.srows
    k = summarizer.k

    omega = state.omega
    c_omega, c_psi = state.cosketch_omega, state.cosketch_psi

    def _local_delta(A_loc, B_loc):
        idx = _shard_index(mesh, axes)
        gids = row_offset + idx * shard_rows + jnp.arange(shard_rows)
        from repro.core.streaming import _chunk_contribution
        dA, dB, dna2, dnb2 = _chunk_contribution(
            key, signs, srows, A_loc, B_loc, gids, k=k,
            method=summarizer.method, precision=summarizer.precision)
        # the psum over shards IS the merge of the per-device partial states
        out = (_block_psum(dA, axes), _block_psum(dB, axes),
               _scalar_psum(dna2, axes), _scalar_psum(dnb2, axes))
        if omega is not None:
            # the probe block is linear in the rows too: same one psum
            from repro.core.error_engine import probe_contribution
            dprobe = probe_contribution(omega, A_loc, B_loc,
                                        summarizer.precision)
            out = out + (_block_psum(dprobe, axes),)
        if c_omega is not None:
            # ... and so is the refinement co-sketch pair
            from repro.core.refinement import cosketch_contribution
            dY, dW = cosketch_contribution(c_omega, c_psi, A_loc, B_loc,
                                           summarizer.precision)
            out = out + (_block_psum(dY, axes), _block_psum(dW, axes))
        return out

    out_specs = (P(None, None), P(None, None), P(None), P(None))
    if omega is not None:
        out_specs = out_specs + (P(None, None),)
    if c_omega is not None:
        out_specs = out_specs + (P(None, None), P(None, None))
    in_spec = P(axes if len(axes) > 1 else axes[0], None)
    fn = shard_map(_local_delta, mesh=mesh,
                   in_specs=(in_spec, in_spec),
                   out_specs=out_specs)
    parts = fn(_pad_rows(A_slab, slab_pad), _pad_rows(B_slab, slab_pad))
    dA, dB, dna2, dnb2 = parts[:4]
    nxt = 4
    dprobe = None
    if omega is not None:
        dprobe, nxt = parts[nxt], nxt + 1
    dY = dW = None
    if c_omega is not None:
        dY, dW = parts[nxt], parts[nxt + 1]
    # A decayed delta arrives "now": its data timestamp is the state's
    # logical clock, so the merge alignment settles the state's pending
    # decay (gamma^(t_state - t_data), the same scalar multiply the
    # single-device update performs) and adds the fresh rows at weight 1 —
    # decay commutes with the psum because both are linear.
    delta = StreamState(key=None, A_acc=dA, B_acc=dB, na2=dna2, nb2=dnb2,
                        rows_seen=jnp.asarray(slab_d, jnp.int32),
                        row_high=jnp.asarray(row_offset + slab_d, jnp.int32),
                        d_total=state.d_total, signs=signs, srows=srows,
                        omega=omega, probe_acc=dprobe,
                        decay_rate=state.decay_rate,
                        t_state=state.t_state, t_data=state.t_state,
                        cosketch_omega=c_omega, cosketch_psi=c_psi,
                        cosketch_Y=dY, cosketch_W=dW)
    return merge_states(state, delta)


def distributed_streaming_summary(mesh: Mesh, axis, key: jax.Array,
                                  A: jax.Array, B: jax.Array, k: int,
                                  method: str = "gaussian",
                                  precision: str | None = None,
                                  slab: int | None = None,
                                  probes: int = 0, cosketch: int = 0):
    """Full streaming pass over row-sharded (A, B): slab-chunked ingestion +
    per-slab tree-merge. With ``slab=None`` the whole pair is one slab —
    semantically ``distributed_sketch_summary`` re-expressed through the
    streaming monoid (parity-tested in tests/core/test_streaming.py).
    ``probes`` retains the held-out probe block, ``cosketch`` the refinement
    co-sketch pair (their per-shard contributions merge through the same
    psum as the sketches). ``axis`` accepts the ``("host", "device")``
    hierarchy, and a ragged ``d`` zero-pads the trailing shard of the last
    slab (exact — zero rows contribute nothing)."""
    from repro.core.streaming import StreamingSummarizer
    d = A.shape[0]
    n_shards = _axes_size(mesh, _reduce_axes(mesh, axis))
    summ = StreamingSummarizer(k, method=method, precision=precision,
                               probes=probes, cosketch=cosketch)
    state = summ.init(key, (d, A.shape[1], B.shape[1]))
    slab = d if slab is None else slab
    # round full slabs to a shard multiple; the trailing partial slab is
    # zero-padded by distributed_streaming_update
    slab = max(n_shards, slab - slab % n_shards)
    for off in range(0, d, slab):
        state = distributed_streaming_update(
            mesh, axis, summ, state, A[off:off + slab], B[off:off + slab],
            row_offset=off)
    return summ.finalize(state)


def distributed_smppca(mesh: Mesh, axis: str, key: jax.Array, A: jax.Array,
                       B: jax.Array, *, r: int, k: int, m: int, T: int = 10,
                       method: str = "gaussian") -> LowRankFactors:
    """Full distributed pipeline. Steps 2-3 run replicated (they are o(n k + m
    r^2 T) — negligible next to the pass) after the single all-reduced pass;
    every device computes identical factors (same seed), mirroring the
    every-worker-completes design of the gradient compressor."""
    from repro.core.summary_engine import build_summary
    k1, k2 = jax.random.split(key)
    summary = build_summary(k1, A, B, k, method=method, backend="distributed",
                            mesh=mesh, axis=axis)
    from repro.core.smppca import smppca_from_summary
    return smppca_from_summary(k2, summary, r=r, m=m, T=T).factors
