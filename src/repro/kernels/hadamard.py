"""Blocked fast Walsh-Hadamard transform — the SRHT sketch on the MXU.

The paper's Spark implementation uses SRHT (sqrt(d/k) R H D) to cut the
sketch cost from O(ndk) to O(nd log d). A recursive butterfly FWHT is
pointer-chasing and hostile to the TPU; instead we use the Kronecker
factorization (Sylvester): for d = a * b with row-major index split i = p*b+j,

    H_d = H_a (x) H_b   =>   H_d X = stage2( stage1(X) )
    stage1: Y[p] = H_b @ X[p]      -- a independent (b x n) MXU matmuls
    stage2: Z[q] = sum_p H_a[q,p] Y[p]  == H_a @ Y  viewed as (a, b*n)

Both stages are dense matmuls against small constant Hadamard tiles
(<=256x256, resident in VMEM), which run on the systolic MXU at full rate —
this is the TPU-native adaptation of the GPU butterfly described in
DESIGN.md §4. The SRHT sign flips (D) are fused into stage 1's input read.

Cost: 2 * d * n * max(a, b) MACs; with a = b = sqrt(d) that is O(n d sqrt(d))
MXU work but only O(n d) HBM traffic per stage — on TPU the MXU is free
relative to HBM here (arithmetic intensity ~ b), so the matmul form beats an
O(n d log d) scalar butterfly by keeping everything in 128x128 systolic tiles.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def hadamard_matrix(n: int, dtype=jnp.float32) -> jax.Array:
    """Sylvester Hadamard matrix H_n (n a power of two), unnormalized."""
    if n < 1 or n & (n - 1):
        raise ValueError(
            f"Hadamard matrix size must be a power of two, got n={n}")
    H = np.array([[1.0]], dtype=np.float32)
    while H.shape[0] < n:
        H = np.block([[H, H], [H, -H]])
    return jnp.asarray(H, dtype)


def _stage1_kernel(h_ref, sign_ref, x_ref, out_ref):
    xs = x_ref[...].astype(jnp.float32) * sign_ref[...].astype(jnp.float32)
    out_ref[...] = jax.lax.dot_general(
        h_ref[...], xs, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _stage2_kernel(h_ref, y_ref, out_ref):
    out_ref[...] = jax.lax.dot_general(
        h_ref[...], y_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("b", "bn", "grid_order", "interpret"))
def blocked_fwht(X: jax.Array, signs: jax.Array, *, b: int = 128,
                 bn: int = 256, grid_order: str | None = None,
                 interpret: bool | None = None) -> jax.Array:
    """H_d @ (signs[:, None] * X), unnormalized. X: (d, n), d = a*b, both
    powers of two, n % bn == 0 (ops.py pads). ``interpret=None`` resolves
    from the platform (``kernels.ops._interpret``).

    ``grid_order`` picks stage 1's traversal: ``None``/``'n_inner'`` walks
    n-tiles innermost (one Hb/sign stripe resident per p), ``'p_inner'``
    walks p innermost (one X column stripe's tiles consecutive — better when
    bn is wide and b small). Legal because stage 1 writes each output block
    exactly once (no revisit/accumulation), so traversal order cannot change
    the result — bit-identical by construction, which tests/kernels assert.
    """
    if interpret is None:
        from repro.kernels.ops import _interpret
        interpret = _interpret()
    d, n = X.shape
    if d % b:
        raise ValueError(f"blocked_fwht: d={d} not divisible by block b={b}")
    a = d // b
    if (a & (a - 1)) or (b & (b - 1)):
        raise ValueError(f"blocked_fwht: tile split d = a*b needs both "
                         f"powers of two, got a={a}, b={b}")
    if n % bn:
        raise ValueError(f"blocked_fwht: n={n} not divisible by bn={bn}; "
                         f"pad first (kernels.ops.blocked_fwht does this)")
    if grid_order not in (None, "n_inner", "p_inner"):
        raise ValueError(f"blocked_fwht: unknown grid_order {grid_order!r} "
                         f"(None|'n_inner'|'p_inner')")
    Hb = hadamard_matrix(b)
    Ha = hadamard_matrix(a)

    # stage 1: per-p tile, out[p*b:(p+1)*b, :] = Hb @ (D X)[p*b:(p+1)*b, :]
    if grid_order == "p_inner":
        grid1 = (n // bn, a)
        ix = lambda ni, p: (p, ni)      # (p_idx, n_idx) from (outer, inner)
        iy = lambda ni, p: (p, 0)
    else:
        grid1 = (a, n // bn)
        ix = lambda p, ni: (p, ni)
        iy = lambda p, ni: (p, 0)
    Y = pl.pallas_call(
        _stage1_kernel,
        grid=grid1,
        in_specs=[
            pl.BlockSpec((b, b), lambda *_: (0, 0)),
            pl.BlockSpec((b, 1), iy),
            pl.BlockSpec((b, bn), ix),
        ],
        out_specs=pl.BlockSpec((b, bn), ix),
        out_shape=jax.ShapeDtypeStruct((d, n), jnp.float32),
        interpret=interpret,
        name="fwht_stage1",
    )(Hb, signs.reshape(d, 1), X)

    if a == 1:
        return Y

    # stage 2: combine across tiles: view Y as (a, b*n), Z = Ha @ Y_mat.
    # The (d, n) row-major buffer *is* (a, b*n) row-major — a free reshape.
    # Columns are independent, so stage 2 tiles them bn wide: an (a, bn)
    # block keeps VMEM at O(a * bn) whatever b is.
    Ym = Y.reshape(a, b * n)
    Z = pl.pallas_call(
        _stage2_kernel,
        grid=(b * n // bn,),
        in_specs=[
            pl.BlockSpec((a, a), lambda c: (0, 0)),
            pl.BlockSpec((a, bn), lambda c: (0, c)),
        ],
        out_specs=pl.BlockSpec((a, bn), lambda c: (0, c)),
        out_shape=jax.ShapeDtypeStruct((a, b * n), jnp.float32),
        interpret=interpret,
        name="fwht_stage2",
    )(Ha, Ym)
    return Z.reshape(d, n)
