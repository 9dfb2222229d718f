"""Sampled rescaled-JL dot products (paper step 2, O(mk) term) as a gather
kernel with scalar-prefetched indices.

Given row-major sketches As (n1, k), Bs (n2, k) (columns of the original
sketch transposed once at the end of the pass — k is small so this is cheap),
exact norms, and the sampled index pairs (rows, cols), computes

    out[t] = ||A_rows[t]|| * ||B_cols[t]|| * <As[rows[t]], Bs[cols[t]]>
             / (||As[rows[t]]|| * ||Bs[cols[t]]||)

TPU design: the Omega indices live in SMEM via scalar prefetch
(``pltpu.PrefetchScalarGridSpec``), and each operand's BlockSpec index_map
*dereferences the prefetched index* to DMA exactly the sketch row the grid
step needs — the standard TPU fused-embedding-gather pattern (no (n, k)
tile ever enters VMEM). Grid pipelining overlaps the row DMAs with compute.

Mosaic tiles the last two dims of every VMEM block by (8, 128) unless they
span the whole array, so the sketches are viewed as (n, 1, k) and gathered
as (1, 1, k) blocks, and the output is an (m, 1, 1) column written one
(1, 1, 1) block per step. The two norm vectors are tiny (n floats) and sit
whole in SMEM, read by the same prefetched indices. SMEM also bounds how
many indices one call can prefetch, so the sample list is processed in
chunks of ``_M_CHUNK`` (one ``pallas_call`` per chunk under ``lax.map``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EPS = 1e-12

#: samples per kernel launch: two int32 index vectors of this length are
#: scalar-prefetched into SMEM next to the two norm vectors
_M_CHUNK = 32768


def _kernel(rows_ref, cols_ref, na_ref, nb_ref, a_ref, b_ref, out_ref):
    g = pl.program_id(0)
    a = a_ref[0].astype(jnp.float32)          # (1, k)
    b = b_ref[0].astype(jnp.float32)          # (1, k)
    dot = jnp.sum(a * b, axis=1, keepdims=True)              # (1, 1)
    sa = jnp.sqrt(jnp.sum(a * a, axis=1, keepdims=True))
    sb = jnp.sqrt(jnp.sum(b * b, axis=1, keepdims=True))
    scale = na_ref[rows_ref[g]] * nb_ref[cols_ref[g]]
    out_ref[0] = dot * scale / jnp.maximum(sa * sb, _EPS)


def _launch(As3, Bs3, norm_A, norm_B, rows, cols, interpret):
    m = rows.shape[0]
    k = As3.shape[2]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m,),
        in_specs=[
            smem,
            smem,
            pl.BlockSpec((1, 1, k), lambda g, rows, cols: (rows[g], 0, 0)),
            pl.BlockSpec((1, 1, k), lambda g, rows, cols: (cols[g], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1), lambda g, rows, cols: (g, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, 1, 1), jnp.float32),
        interpret=interpret,
        name="sampled_dot",
    )(rows, cols, norm_A, norm_B, As3, Bs3)
    return out[:, 0, 0]


@functools.partial(jax.jit, static_argnames=("interpret", "precision"))
def sampled_rescaled_dot(As_rows: jax.Array, Bs_rows: jax.Array,
                         norm_A: jax.Array, norm_B: jax.Array,
                         rows: jax.Array, cols: jax.Array, *,
                         interpret: bool | None = None,
                         precision: str | None = None) -> jax.Array:
    """As_rows: (n1, k), Bs_rows: (n2, k), rows/cols: (m,) int32 -> (m,) f32.

    ``m`` is the static sample budget: any m >= 0 works, including m = 0
    (an empty Omega — no grid to launch, return the empty result directly;
    a zero-size grid would slice zero-size operands) and m > n1 * n2 (more
    samples than distinct entries — duplicates gather the same sketch rows,
    each grid step is independent). Past ``_M_CHUNK`` samples the list is
    zero-padded to whole chunks (index 0 is always valid) and cropped.

    ``interpret=None`` resolves from the platform (``kernels.ops.
    _interpret``). ``precision='bf16'`` casts the gathered sketch rows
    (halves the per-step row DMA — the kernel has no block knobs, this is
    its one tunable); the body always reduces in f32, so ``None``/``'f32'``
    on f32 inputs are bit-identical. Norm vectors stay f32 (they rescale
    the final estimate).
    """
    if interpret is None:
        from repro.kernels.ops import _interpret
        interpret = _interpret()
    if precision == "bf16":
        As_rows = As_rows.astype(jnp.bfloat16)
        Bs_rows = Bs_rows.astype(jnp.bfloat16)
    elif precision not in (None, "f32"):
        raise ValueError(
            f"unknown precision {precision!r} (None|'f32'|'bf16')")
    m = rows.shape[0]
    if m == 0:
        return jnp.zeros((0,), jnp.float32)
    As3 = As_rows[:, None, :]
    Bs3 = Bs_rows[:, None, :]
    norm_A = norm_A.astype(jnp.float32)
    norm_B = norm_B.astype(jnp.float32)
    rows = rows.astype(jnp.int32)
    cols = cols.astype(jnp.int32)
    if m <= _M_CHUNK:
        return _launch(As3, Bs3, norm_A, norm_B, rows, cols, interpret)
    pad = (-m) % _M_CHUNK
    chunks = (jnp.pad(rows, (0, pad)).reshape(-1, _M_CHUNK),
              jnp.pad(cols, (0, pad)).reshape(-1, _M_CHUNK))
    out = jax.lax.map(
        lambda rc: _launch(As3, Bs3, norm_A, norm_B, rc[0], rc[1], interpret),
        chunks)
    return out.reshape(-1)[:m]
