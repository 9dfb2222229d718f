"""Gradient-tap dense layer: the paper's single-pass product sketch applied
to the *true* factored form of the weight gradient.

For a dense layer y = x W, autodiff gives dW = X^T dY with X (T x n_in),
dY (T x n_out), T = tokens — exactly the paper's A^T B with the huge streamed
dimension d = T. Stable ranks of activations/cotangents are far below T, so
the paper's bounds bite at small sketch k (unlike the A=I mapping used by the
grads-level baseline in optim.grad_compression, whose A has stable rank n_in
— tests/train/test_training.py::test_tap_gradient_beats_identity_baseline
holds that contrast).

Mechanics (jit/pjit-pure, no side channels):
  * the layer's params carry zero-initialized *tap* leaves
    {a: (k, n_in), b: (k, n_out), na2: (n_in,), nb2: (n_out,)};
  * a custom_vjp writes the one-pass summary of (X, dY) into the taps'
    cotangents and `zeros` into W's cotangent — the sketches ride the
    ordinary grads pytree, so DP all-reduce / GSPMD contraction over the
    token dimension aggregates them exactly like the paper's treeAggregate
    (sketches and squared norms are linear/additive over row shards);
  * the optimizer-side ``decompress_tapped_grads`` runs the same-seeded
    SMP-PCA completion to materialize the rank-r dW on every worker.

Under pjit the contraction Pi @ X over the sharded token dimension becomes a
(k x n_in)-sized all-reduce instead of the (n_in x n_out) gradient
all-reduce: communication drops by ~ n_out / k per layer with zero extra
passes over activations (the sketch is computed from the same X/dY tiles the
backward matmul would have read — the paper's one-pass principle).
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import streaming
from repro.core.smppca import smppca_from_summary
from repro.core.summary_engine import tap_pair_summary


class TapConfig(NamedTuple):
    sketch_k: int = 64
    rank: int = 8
    sample_factor: int = 8
    als_iters: int = 4
    block: int = 2048           # streaming block for the Pi generation


def tap_init(n_in: int, n_out: int, k: int) -> Dict[str, jax.Array]:
    return {"a": jnp.zeros((k, n_in), jnp.float32),
            "b": jnp.zeros((k, n_out), jnp.float32),
            "na2": jnp.zeros((n_in,), jnp.float32),
            "nb2": jnp.zeros((n_out,), jnp.float32)}


def _sketch_pair(key, X, Y, k, block):
    """One-pass (Pi X, Pi Y, col-norms^2) over X, Y (T x n) — delegated to
    the SummaryEngine's tap path (``tap_pair_summary``), which keeps the
    single fused contraction over the token dimension: under pjit the
    T-sharded contraction produces exactly ONE (k x n) psum per output.
    (The original scan-over-blocks variant made GSPMD emit a partial
    all-reduce per block — the C1 refutation in EXPERIMENTS.md §Perf.)"""
    del block
    return tap_pair_summary(key, X, Y, k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def sketched_dense(w, taps, x, key, k: int = 64, block: int = 2048):
    """y = x @ w; the backward pass emits sketch taps instead of dW."""
    del taps, key
    return jax.lax.dot_general(
        x, w.astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _fwd(w, taps, x, key, k, block):
    y = jax.lax.dot_general(
        x, w.astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return y, (w, x, key)


def _bwd(k, block, res, gy):
    w, x, key = res
    n_in, n_out = w.shape
    dx = jax.lax.dot_general(
        gy.astype(x.dtype), w.astype(x.dtype),
        (((gy.ndim - 1,), (1,)), ((), ()))).astype(x.dtype)
    X2 = x.reshape(-1, n_in).astype(jnp.float32)
    G2 = gy.reshape(-1, n_out).astype(jnp.float32)
    a, b, na2, nb2 = _sketch_pair(key, X2, G2, k, block)
    dw = jnp.zeros_like(w)          # never materialized/communicated
    dtaps = {"a": a, "b": b, "na2": na2, "nb2": nb2}
    return dw, dtaps, dx, None


sketched_dense.defvjp(_fwd, _bwd)


def tap_state(tap_grads: Dict[str, jax.Array]) -> streaming.StreamState:
    """View a tap-grads dict as a ``streaming.StreamState`` partial summary.

    The taps ARE a stream state over token chunks: {a, b} are the running
    sketches, {na2, nb2} the running *squared* norms — exactly the mergeable
    accumulator layout (squared norms so the DP all-reduce stays a plain
    sum). The Pi here is the tap path's own (fused, per-call) draw rather
    than the per-global-row fold_in — token ids are not globally meaningful
    across microbatches — so the state carries no key/plan; it can be merged
    and finalized, not updated further.
    """
    na2 = jnp.maximum(tap_grads["na2"], 0.0)
    nb2 = jnp.maximum(tap_grads["nb2"], 0.0)
    return streaming.StreamState(
        key=None, A_acc=tap_grads["a"], B_acc=tap_grads["b"],
        na2=na2, nb2=nb2, rows_seen=jnp.zeros((), jnp.int32),
        row_high=jnp.zeros((), jnp.int32),
        d_total=jnp.asarray(-1, jnp.int32), signs=None, srows=None)


def accumulate_taps(t1: Dict[str, jax.Array],
                    t2: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Merge tap grads from two microbatches (gradient accumulation).

    Delegates to ``streaming.merge_states`` — the same commutative monoid
    the chunked ingestion and the distributed tree-reduce use, so
    accumulate-then-decompress equals decompressing the concatenated-token
    summary.
    """
    m = streaming.merge_states(tap_state(t1), tap_state(t2))
    return {"a": m.A_acc, "b": m.B_acc, "na2": m.na2, "nb2": m.nb2}


def decompress_tap(key: jax.Array, tap_grads: Dict[str, jax.Array],
                   cfg: TapConfig) -> jax.Array:
    """Same-seeded SMP-PCA completion of the tapped summary -> rank-r dW."""
    summary = streaming.finalize_state(tap_state(tap_grads))
    n1, n2 = summary.n1, summary.n2
    m = int(cfg.sample_factor * (n1 + n2) * cfg.rank)
    res = smppca_from_summary(key, summary, r=cfg.rank, m=m, T=cfg.als_iters)
    return res.factors.U @ res.factors.V.T


def decompress_tapped_grads(key: jax.Array, grads, cfg: TapConfig):
    """Walk a grads pytree; wherever a dict holds {'w', 'taps'}, replace the
    zero dW with the SMP-PCA reconstruction and zero out the tap grads."""
    def walk(subkey, node):
        if isinstance(node, dict) and "taps" in node and "w" in node:
            node = dict(node)
            a = node["taps"]["a"]
            if a.ndim == 3:      # scan-stacked layer group: vmap over layers
                keys = jax.random.split(subkey, a.shape[0])
                recon = jax.vmap(lambda kk, tg: decompress_tap(kk, tg, cfg))(
                    keys, node["taps"])
            else:
                recon = decompress_tap(subkey, node["taps"], cfg)
            node["w"] = recon.astype(node["w"].dtype)
            node["taps"] = jax.tree.map(jnp.zeros_like, node["taps"])
            return node
        if isinstance(node, dict):
            return {kk: walk(jax.random.fold_in(subkey, i), vv)
                    for i, (kk, vv) in enumerate(sorted(node.items()))}
        if isinstance(node, (list, tuple)):
            walked = [walk(jax.random.fold_in(subkey, i), vv)
                      for i, vv in enumerate(node)]
            return type(node)(walked)
        return node
    return walk(key, grads)
