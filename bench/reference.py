"""The plain reference the benchmark holds the program to.

It imports nothing of the program and takes nothing the program made. It
rebuilds the one-pass summary of arXiv:1610.06656 Alg. 1 step 1 from the
benchmark's own inputs under the key contract the repository documents
(``docs/architecture.md``):

* the projection column of global row ``i`` is
  ``normal(fold_in(key, i), (k,)) / sqrt(k)``;
* the held-out probes are
  ``normal(fold_in(fold_in(key, 0x70726F62), 0x6521), (n2, p))``;

and it judges factors against the exact product ``A^T B``.

Every product takes ``passes``: 6 is f32 at ``Precision.HIGHEST``, what the
configurations state; 3 is the control, bf16 in three passes (the
``Precision.HIGH`` split ``hi*hi + hi*lo + lo*hi``), spelled out so that it
computes the same on any backend.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PROBE_TAGS = (0x70726F62, 0x6521)

_TN = (((0,), (0,)), ((), ()))      # X^T Y
_NN = (((1,), (0,)), ((), ()))      # X Y


def dot(x, y, dims, passes: int):
    """``dot_general`` with f32 accumulation: 6 passes = f32 at HIGHEST,
    3 passes = the bf16x3 split."""
    if passes == 6:
        return jax.lax.dot_general(x, y, dims,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
    if passes != 3:
        raise ValueError(f"passes must be 6 or 3, got {passes}")

    def split(v):
        # reduce_precision, not a round trip through bf16: XLA may drop a
        # convert pair under excess precision, which on a TPU left lo = 0
        # and turned this into one bf16 pass
        hi = jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(v - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)

    (xh, xl), (yh, yl) = split(x), split(y)
    one = functools.partial(jax.lax.dot_general, dimension_numbers=dims,
                            preferred_element_type=jnp.float32)
    return one(xh, yh) + one(xh, yl) + one(xl, yh)


def projection(key, row_ids, k: int):
    """(t, k): the projection column of each global row id."""
    one = lambda i: jax.random.normal(jax.random.fold_in(key, i), (k,))
    return jax.vmap(one)(row_ids.astype(jnp.uint32)) / jnp.sqrt(
        jnp.float32(k))


def probe_omega(key, n2: int, p: int):
    """(n2, p) held-out probe directions of a summary key."""
    pk = jax.random.fold_in(jax.random.fold_in(key, PROBE_TAGS[0]),
                            PROBE_TAGS[1])
    return jax.random.normal(pk, (n2, p))


@functools.partial(jax.jit, static_argnames=("k", "passes"))
def _chunk_step(acc_a, acc_b, key, offset, A_cols, B_cols, *, k: int,
                passes: int):
    rows = A_cols.shape[0]
    P = projection(key, offset + jnp.arange(rows, dtype=jnp.int32), k)
    return (acc_a + dot(P, A_cols, _TN, passes),
            acc_b + dot(P, B_cols, _TN, passes))


@functools.partial(jax.jit, static_argnames=("passes",))
def _pool_side(A, B, omega, *, passes: int):
    """Per pool block: squared column norms and the probe summand."""
    sq_a = jnp.sum(A ** 2, axis=0)
    sq_b = jnp.sum(B ** 2, axis=0)
    probe = dot(A, dot(B, omega, _NN, passes), _TN, passes)
    return sq_a, sq_b, probe


def stream_summary(key, pool_A: Sequence, pool_B: Sequence,
                   chunks: Sequence[tuple], k: int, p: int,
                   cols_A, cols_B, passes: int = 6) -> Dict[str, np.ndarray]:
    """The summary of the chunks ``(pool index, first global row)`` taken
    in order, as the streaming monoid defines it: sketch columns
    ``cols_A``/``cols_B`` (a sample), every column norm, the whole probe
    block and the row count. Accumulates chunk by chunk in f32."""
    n1, n2 = pool_A[0].shape[1], pool_B[0].shape[1]
    omega = probe_omega(key, n2, p)
    side = [_pool_side(a, b, omega, passes=passes)
            for a, b in zip(pool_A, pool_B)]
    sub = [(a[:, cols_A], b[:, cols_B]) for a, b in zip(pool_A, pool_B)]
    acc_a = jnp.zeros((k, len(cols_A)), jnp.float32)
    acc_b = jnp.zeros((k, len(cols_B)), jnp.float32)
    na2 = jnp.zeros((n1,), jnp.float32)
    nb2 = jnp.zeros((n2,), jnp.float32)
    probes = jnp.zeros((n1, p), jnp.float32)
    rows = 0
    for idx, offset in chunks:
        acc_a, acc_b = _chunk_step(acc_a, acc_b, key, jnp.int32(offset),
                                   *sub[idx], k=k, passes=passes)
        sq_a, sq_b, probe = side[idx]
        na2, nb2, probes = na2 + sq_a, nb2 + sq_b, probes + probe
        rows += pool_A[idx].shape[0]
    out = dict(A_sketch=acc_a, B_sketch=acc_b, norm_A=jnp.sqrt(na2),
               norm_B=jnp.sqrt(nb2), probes=probes, omega=omega)
    out = {name: np.asarray(v) for name, v in jax.device_get(out).items()}
    out["rows"] = rows
    return out


def rel_frob(got, want) -> float:
    """||got - want||_F / ||want||_F in float64."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def summary_gap(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]
                ) -> float:
    """Worst relative Frobenius gap over the summary's blocks (the sketch
    blocks at the reference's sampled columns)."""
    return max(rel_frob(got[name], want[name]) for name in
               ("A_sketch", "B_sketch", "norm_A", "norm_B", "probes"))


def probe_residual(probes, omega, U, V, passes: int = 6) -> float:
    """The probe estimate of ||A^T B - U V^T||_F: the root mean square of
    ``probes_j - U (V^T omega_j)`` over the probes."""
    probes, omega = jnp.asarray(probes), jnp.asarray(omega)
    U, V = jnp.asarray(U), jnp.asarray(V)
    resid = probes - dot(U, dot(V, omega, _TN, passes), _NN, passes)
    return float(jnp.sqrt(jnp.mean(jnp.sum(resid ** 2, axis=0))))


@functools.partial(jax.jit, static_argnames=("count",))
def _top_singular(M, key, *, count: int, iters: int = 40):
    """The top ``count`` singular values of M by subspace iteration with
    ``count + 8`` vectors, in f32 at HIGHEST."""
    hi = jax.lax.Precision.HIGHEST
    Q = jax.random.normal(key, (M.shape[1], count + 8))

    def body(_, Q):
        Y, _ = jnp.linalg.qr(jnp.dot(M, Q, precision=hi))
        Z, _ = jnp.linalg.qr(jnp.dot(M.T, Y, precision=hi))
        return Z

    Q = jax.lax.fori_loop(0, iters, body, jnp.linalg.qr(Q)[0])
    s = jnp.linalg.svd(jnp.dot(M, Q, precision=hi), compute_uv=False)
    return s[:count]


def exact_product(pool_A: Sequence, pool_B: Sequence, counts: Sequence[int]):
    """``A^T B`` of a stream that took pool block i ``counts[i]`` times, in
    f32 at HIGHEST on the device."""
    hi = jax.lax.Precision.HIGHEST
    M = None
    for a, b, c in zip(pool_A, pool_B, counts):
        if c:
            term = c * jnp.dot(a.T, b, precision=hi)
            M = term if M is None else M + term
    return M


def spectral_ratio(M, U, V, s_next: float) -> float:
    """||M - U V^T||_2 / sigma_{r+1}(M): how far the factors are from the
    best rank-r approximation, in its own spectral error."""
    R = M - jnp.dot(jnp.asarray(U), jnp.asarray(V).T,
                    precision=jax.lax.Precision.HIGHEST)
    top = float(_top_singular(R, jax.random.PRNGKey(1), count=1)[0])
    return top / s_next


def next_singular(M, r: int) -> float:
    """sigma_{r+1}(M)."""
    return float(_top_singular(M, jax.random.PRNGKey(0), count=r + 1)[r])
