"""Step 2b of SMP-PCA: the rescaled JL estimator (Eq. 2).

    M~(i,j) = ||A_i|| * ||B_j|| * <A~_i, B~_j> / (||A~_i|| * ||B~_j||)

i.e. keep the *sketched angle* but substitute the *exact* column norms carried
as side information from the single pass. Compact form: D_A (A~^T B~) D_B with
D_A = diag(||A_i||/||A~_i||), D_B = diag(||B_j||/||B~_j||) (Appendix B).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import SketchSummary

_EPS = 1e-12

#: samples gathered per step: the (k, m) gathers of the whole paper-default
#: sample would take 2 * 4 * k * m bytes (13 GiB at n = 4096, k = 512)
_M_CHUNK = 65536


def rescaled_entries(summary: SketchSummary, rows: jax.Array,
                     cols: jax.Array) -> jax.Array:
    """M~ evaluated at (rows, cols) — O(m k), never materializes (n1, n2).

    This is the pure-XLA path; repro.kernels.sampled_dot is the TPU kernel.
    Past ``_M_CHUNK`` samples the list is zero-padded to whole chunks
    (index 0 is always valid), mapped chunk by chunk, and cropped.
    """
    m = rows.shape[0]
    if m <= _M_CHUNK:
        return _rescaled_block(summary, rows, cols)
    pad = (-m) % _M_CHUNK
    chunks = (jnp.pad(rows, (0, pad)).reshape(-1, _M_CHUNK),
              jnp.pad(cols, (0, pad)).reshape(-1, _M_CHUNK))
    out = jax.lax.map(lambda rc: _rescaled_block(summary, *rc), chunks)
    return out.reshape(-1)[:m]


def _rescaled_block(summary: SketchSummary, rows: jax.Array,
                    cols: jax.Array) -> jax.Array:
    Ai = summary.A_sketch[:, rows]              # (k, m)
    Bj = summary.B_sketch[:, cols]              # (k, m)
    dots = jnp.sum(Ai * Bj, axis=0)             # (m,)
    sa = jnp.sqrt(jnp.sum(Ai ** 2, axis=0))
    sb = jnp.sqrt(jnp.sum(Bj ** 2, axis=0))
    scale = (summary.norm_A[rows] * summary.norm_B[cols]) / \
        jnp.maximum(sa * sb, _EPS)
    return dots * scale


def plain_jl_entries(summary: SketchSummary, rows: jax.Array,
                     cols: jax.Array) -> jax.Array:
    """The naive estimator <A~_i, B~_j> the paper improves upon (Fig 2a)."""
    Ai = summary.A_sketch[:, rows]
    Bj = summary.B_sketch[:, cols]
    return jnp.sum(Ai * Bj, axis=0)


def rescaled_matrix(summary: SketchSummary) -> jax.Array:
    """Dense M~ = D_A (A~^T B~) D_B. Small-n tests/benchmarks only."""
    sa = jnp.sqrt(jnp.sum(summary.A_sketch ** 2, axis=0))
    sb = jnp.sqrt(jnp.sum(summary.B_sketch ** 2, axis=0))
    da = summary.norm_A / jnp.maximum(sa, _EPS)
    db = summary.norm_B / jnp.maximum(sb, _EPS)
    return (summary.A_sketch.T @ summary.B_sketch) * da[:, None] * db[None, :]
