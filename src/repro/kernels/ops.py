"""Public wrappers around the Pallas kernels, with tunable configs.

Handles padding to kernel-aligned shapes, backend dispatch (compiled Pallas on
TPU, interpret=True elsewhere — the kernel *body* runs either way so CPU CI
validates the real TPU code path), and integration glue used by repro.core
and the gradient compressor.

Every wrapper takes an optional ``config: tuning.KernelConfig``. Resolution
happens host-side, *before* the jitted impl (so the block sizes are concrete
static arguments and repeat calls hit jax's compile cache):

    explicit kwarg (bn=..., precision=...)   wins over
    explicit ``config``                      wins over
    committed tuning-table hit for the shape bucket   wins over
    ``tuning.DEFAULTS`` (the historical hard-coded values), if they fit
    the VMEM budget, else the best-ranked config that does

With no table entry and no config, a shape the defaults fit resolves to
exactly the old hard-coded blocks, so default-path outputs are
bit-identical to the pre-tuning kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _flash
from repro.kernels import hadamard as _hadamard
from repro.kernels import sampled_dot as _sampled_dot
from repro.kernels import sketch_fused as _sketch_fused
from repro.kernels import tuning as _tuning
from repro.core.types import SketchSummary


def _interpret() -> bool:
    """Single source of the interpret policy: compile the Pallas kernels only
    on TPU; interpret everywhere else. CPU CI still runs the real TPU kernel
    bodies tile-by-tile. GPU must stay interpreted too: the kernels accumulate
    across a grid dimension (``out_ref[...] +=`` with a revisited output
    block), which relies on TPU's sequential grid — Pallas GPU runs grid
    cells in parallel and would race."""
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _resolved(kernel: str, shape: tuple, ref: jax.Array,
              config: "_tuning.KernelConfig | None") -> _tuning.KernelConfig:
    """The effective config: validated explicit one, else table/defaults."""
    if config is None:
        return _tuning.lookup(kernel, shape,
                              dtype_bytes=_tuning.dtype_bytes_of(ref))
    _tuning.validate_config(config)
    if config.kernel != kernel:
        raise ValueError(f"config is for kernel {config.kernel!r}, "
                         f"wrapper is {kernel!r}")
    return config


@functools.partial(jax.jit, static_argnames=("bn", "bd", "precision"))
def _sketch_fused_call(Pi: jax.Array, A: jax.Array, *, bn: int, bd: int,
                       precision: str | None
                       ) -> tuple[jax.Array, jax.Array]:
    n = A.shape[1]
    bd_eff = min(bd, _pad_to(A, 0, 8).shape[0])
    Ap = _pad_to(_pad_to(A, 0, bd_eff), 1, bn)
    Pip = _pad_to(Pi, 1, bd_eff)
    out, norm2 = _sketch_fused.sketch_fused(
        Pip, Ap, bn=bn, bd=bd_eff, interpret=_interpret(),
        precision=precision)
    return out[:, :n], jnp.sqrt(norm2[:n])


def sketch_fused(Pi: jax.Array, A: jax.Array, *, bn: int | None = None,
                 bd: int | None = None, precision: str | None = None,
                 config: "_tuning.KernelConfig | None" = None
                 ) -> tuple[jax.Array, jax.Array]:
    """Fused (Pi @ A, column norms) for arbitrary shapes; pads then crops.

    Zero padding is exact for both outputs (zero rows/cols add nothing).
    ``precision='bf16'`` casts the inputs; accumulation stays f32."""
    k, d = Pi.shape
    n = A.shape[1]
    cfg = _resolved("sketch_fused", (k, d, n), A, config)
    return _sketch_fused_call(
        Pi, A, bn=bn if bn is not None else cfg.block[0],
        bd=bd if bd is not None else cfg.block[1],
        precision=precision if precision is not None else cfg.precision)


def sketch_summary_fused(key: jax.Array, A: jax.Array, B: jax.Array,
                         k: int, method: str = "gaussian",
                         precision: str | None = None) -> SketchSummary:
    """Kernel-backed summary == the SummaryEngine's 'pallas' backend."""
    from repro.core.summary_engine import build_summary
    return build_summary(key, A, B, k, method=method, backend="pallas",
                         precision=precision)


@functools.partial(jax.jit, static_argnames=("precision",))
def _sampled_dot_call(As_rows: jax.Array, Bs_rows: jax.Array,
                      norm_A: jax.Array, norm_B: jax.Array,
                      rows: jax.Array, cols: jax.Array, *,
                      precision: str | None) -> jax.Array:
    return _sampled_dot.sampled_rescaled_dot(
        As_rows, Bs_rows, norm_A, norm_B, rows, cols,
        interpret=_interpret(), precision=precision)


def sampled_rescaled_dot(As_rows: jax.Array, Bs_rows: jax.Array,
                         norm_A: jax.Array, norm_B: jax.Array,
                         rows: jax.Array, cols: jax.Array, *,
                         precision: str | None = None,
                         config: "_tuning.KernelConfig | None" = None
                         ) -> jax.Array:
    """Kernel-backed rescaled-JL estimates on Omega (row-major sketches)."""
    n1, k = As_rows.shape
    n2, m = Bs_rows.shape[0], rows.shape[0]
    cfg = _resolved("sampled_dot", (n1, n2, k, m), As_rows, config)
    return _sampled_dot_call(
        As_rows, Bs_rows, norm_A, norm_B, rows, cols,
        precision=precision if precision is not None else cfg.precision)


@functools.partial(jax.jit, static_argnames=("b", "bn", "grid_order"))
def _blocked_fwht_call(X: jax.Array, signs: jax.Array, *, b: int, bn: int,
                       grid_order: str | None) -> jax.Array:
    d, n = X.shape
    b_eff = min(b, d)
    Xp = _pad_to(X, 1, bn)
    out = _hadamard.blocked_fwht(Xp, signs, b=b_eff, bn=bn,
                                 grid_order=grid_order,
                                 interpret=_interpret())
    return out[:, :n]


def blocked_fwht(X: jax.Array, signs: jax.Array, *, b: int | None = None,
                 bn: int | None = None, grid_order: str | None = None,
                 config: "_tuning.KernelConfig | None" = None) -> jax.Array:
    """Kernel-backed unnormalized FWHT of (signs * X); pads n, crops back."""
    d, n = X.shape
    if d & (d - 1):
        raise ValueError(
            f"blocked_fwht: d must be a power of two (got d={d}); "
            f"pad first (srht_sketch_kernel does this)")
    cfg = _resolved("blocked_fwht", (d, n), X, config)
    return _blocked_fwht_call(
        X, signs, b=b if b is not None else cfg.block[0],
        bn=bn if bn is not None else cfg.block[1],
        grid_order=grid_order if grid_order is not None else cfg.grid_order)


@functools.partial(jax.jit, static_argnames=("k", "config"))
def srht_sketch_kernel(key: jax.Array, X: jax.Array, k: int,
                       config: "_tuning.KernelConfig | None" = None
                       ) -> jax.Array:
    """Kernel-backed SRHT: sqrt(1/k) R H D X with the blocked-FWHT kernel."""
    d, n = X.shape
    dp = 1
    while dp < d:
        dp *= 2
    key_sign, key_rows = jax.random.split(key)
    signs = jax.random.rademacher(key_sign, (d,), dtype=X.dtype)
    signs_p = jnp.pad(signs, (0, dp - d), constant_values=1)
    Xp = jnp.pad(X, ((0, dp - d), (0, 0)))
    HX = blocked_fwht(Xp, signs_p, config=config) / jnp.sqrt(dp)
    rows = jax.random.choice(key_rows, dp, (k,), replace=False)
    return HX[rows] * jnp.sqrt(dp / k)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk"))
def _flash_call(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
                bq: int, bk: int) -> jax.Array:
    B, S, H, Dh = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    kf = jnp.repeat(k, rep, axis=2)
    vf = jnp.repeat(v, rep, axis=2)
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
    out = _flash.flash_attention(fold(q), fold(kf), fold(vf), causal=causal,
                                 bq=bq, bk=bk, interpret=_interpret())
    return out.reshape(B, H, S, Dh).transpose(0, 2, 1, 3)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    config: "_tuning.KernelConfig | None" = None
                    ) -> jax.Array:
    """Fused-attention kernel entry point. q: (B, S, H, Dh), k/v GQA
    (B, S, Hkv, Dh); expands KV groups and folds (B, H) for the kernel."""
    B, S, H, Dh = q.shape
    cfg = _resolved("flash_attention", (B * H, S, Dh), q, config)
    return _flash_call(q, k, v, causal=causal,
                       bq=min(cfg.block[0], S), bk=min(cfg.block[1], S))
