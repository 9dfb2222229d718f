"""Flash attention (forward) as a Pallas TPU kernel — the §Perf cell-A mover.

The roofline analysis (EXPERIMENTS.md §Perf) showed dense-transformer train
and prefill cells are memory-bound on materialized (S x S) score tensors:
~90% of phi3 train's 73 TB/device/step. This kernel keeps the whole
score -> softmax -> PV chain in VMEM with the online-softmax recurrence, so
the only HBM traffic is Q/K/V in and O out — the same fuse-the-chain
principle the paper's one-pass sketch applies to its own hot loop
(kernels/sketch_fused.py).

Design (TPU v5e):
  grid = (B*H, S/bq, S/bk), k-blocks innermost; the (bq, d) accumulator and
  the (bq,) running max / denominator live in VMEM scratch that persists
  across the k-steps of one q-block. Causal masking is computed in-register;
  fully-masked k-blocks still occupy grid steps (a production kernel would
  clamp the k-range per q-block — noted as the next iteration).
  Block shapes default to (128, 128): MXU-aligned, ~0.6 MB VMEM working set.

Backward: not implemented here — dQ/dK/dV need the same fusion applied to
the two backward matmuls (documented in EXPERIMENTS.md as the remaining
step); training paths fall back to the chunked pure-JAX attention.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, scale: float, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    if causal:
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, _NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=-1)[:, None]              # (bq, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                            # (bq, bk)
    corr = jnp.exp(m_prev - m_new)                    # (bq, 1)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)[:, None]
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, bq: int = 128, bk: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """q/k/v: (BH, S, Dh) with heads pre-expanded (GQA handled by the ops
    wrapper). Returns (BH, S, Dh) in q's dtype. ``interpret=None`` resolves
    from the platform (``kernels.ops._interpret``)."""
    if interpret is None:
        from repro.kernels.ops import _interpret
        interpret = _interpret()
    BH, S, Dh = q.shape
    if S % bq or S % bk:
        raise ValueError(f"flash_attention: S={S} not divisible by blocks "
                         f"(bq={bq}, bk={bk})")
    scale = 1.0 / math.sqrt(Dh)
    grid = (BH, S // bq, S // bk)
    kernel = functools.partial(_kernel, bq=bq, bk=bk, scale=scale,
                               causal=causal)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, Dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, Dh), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, Dh), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dh), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running denominator
            pltpu.VMEM((bq, Dh), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
