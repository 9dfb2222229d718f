"""Fused sketch kernel: A_sketch = Pi @ A  AND  column norms, one HBM pass.

The paper's step 1 reads the data once and produces both the sketch and the
column-norm side information. On TPU the analogous resource is HBM->VMEM
traffic: this kernel streams each (bd, bn) tile of A into VMEM exactly once
and feeds it to (a) the MXU for the sketch matmul and (b) the VPU for the
squared-column-norm accumulation.

Design (TPU v5e):
  * The sketch dimension k is small by construction (that is the point of
    sketching), so the whole (k, bn) output tile stays resident in VMEM for
    the entire d-loop: grid = (n/bn, d/bd) with d innermost -> A is read from
    HBM exactly once, the output is flushed exactly once per n-tile.
  * Block shapes are MXU-aligned (multiples of 8 x 128 for f32); the matmul
    contracts over bd with preferred_element_type=f32 so bf16 inputs hit the
    MXU at full rate with f32 accumulation. ``Precision.HIGHEST`` keeps f32
    inputs f32 on the MXU (the default would round them to bf16), matching
    ``summary_engine._sketch_dot``; bf16 inputs keep the single pass.
  * Column norms ride the same pass: a (1, bn) f32 row accumulated on the VPU.

VMEM budget per grid step: k*bd (Pi tile) + bd*bn (A tile) + k*bn (out) +
bn (norms) floats. Defaults (k<=2048, bd=512, bn=256) stay under ~4.5 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(pi_ref, a_ref, out_ref, norm_ref):
    di = pl.program_id(1)

    @pl.when(di == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        norm_ref[...] = jnp.zeros_like(norm_ref)

    a_tile = a_ref[...]
    out_ref[...] += jax.lax.dot_general(
        pi_ref[...], a_tile,
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=(jax.lax.Precision.HIGHEST
                   if a_tile.dtype == jnp.float32 else None),
        preferred_element_type=jnp.float32)
    norm_ref[...] += jnp.sum(
        a_tile.astype(jnp.float32) ** 2, axis=0, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("bn", "bd", "interpret", "precision"))
def sketch_fused(Pi: jax.Array, A: jax.Array, *, bn: int = 256, bd: int = 512,
                 interpret: bool | None = None,
                 precision: str | None = None) -> tuple[jax.Array, jax.Array]:
    """Returns (Pi @ A as f32, squared column norms of A as f32 (n,)).

    Pi: (k, d), A: (d, n). d must divide by bd and n by bn (callers pad; the
    ops.py wrapper handles padding for arbitrary shapes).

    ``interpret=None`` auto-detects from the platform (one policy for all
    kernels: ``kernels.ops._interpret`` — compiled on TPU, interpreted
    elsewhere). ``precision='bf16'`` feeds bf16 tiles to the MXU; both
    outputs still accumulate in f32 (``preferred_element_type`` / VPU cast
    in the body).
    """
    if interpret is None:
        from repro.kernels.ops import _interpret
        interpret = _interpret()
    if precision == "bf16":
        Pi = Pi.astype(jnp.bfloat16)
        A = A.astype(jnp.bfloat16)
    elif precision not in (None, "f32"):
        raise ValueError(f"unknown precision {precision!r} (None|'f32'|'bf16')")
    k, d = Pi.shape
    d2, n = A.shape
    if d != d2:
        raise ValueError(f"sketch_fused: Pi {Pi.shape} and A {A.shape} "
                         f"disagree on d ({d} != {d2})")
    if d % bd or n % bn:
        raise ValueError(f"sketch_fused: shape (d={d}, n={n}) not divisible "
                         f"by blocks (bd={bd}, bn={bn}); pad first "
                         f"(kernels.ops.sketch_fused does this)")

    grid = (n // bn, d // bd)
    out, norm2 = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, bd), lambda ni, di: (0, di)),   # Pi tile
            pl.BlockSpec((bd, bn), lambda ni, di: (di, ni)),  # A tile (1 read)
        ],
        out_specs=[
            pl.BlockSpec((k, bn), lambda ni, di: (0, ni)),    # sketch tile
            pl.BlockSpec((1, bn), lambda ni, di: (0, ni)),    # norms row
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=interpret,
        name="sketch_fused",
    )(Pi, A)
    return out, norm2[0]
