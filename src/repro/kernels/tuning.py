"""Tunable kernel configurations + a roofline-seeded autotuner.

Every Pallas kernel in this package used to ship hard-coded block shapes
(``bn=256, bd=512`` in ``sketch_fused``, ``b=128`` in ``hadamard``,
``bq=128`` in the flash-attention wrapper). This module makes those knobs
first-class:

* ``KernelConfig`` — a hashable description of one kernel's layout knobs
  (block sizes, grid traversal order, input precision). Hashability is the
  point: a config can ride a ``PipelinePlan`` and key the compile-once
  executable cache, so warm repeat-shape traffic under a pinned config
  never re-traces.
* ``candidate_configs`` — enumerate the legal configs for a kernel at a
  concrete shape, under the MXU-alignment constraints (last block dim a
  multiple of 128, sublane a multiple of 8) and the per-step VMEM budget
  documented in each kernel's header.
* ``roofline_cost`` / ``rank_candidates`` — a static cost model in the
  terms of ``repro.roofline.analysis`` (HBM bytes moved per call at
  ``HBM_BW``, MXU flops at ``PEAK_FLOPS`` derated by 128x128 tile
  occupancy, plus a per-grid-step overhead) so interpret-mode CPU runs
  still produce a meaningful, deterministic ranking.
* ``autotune`` — optionally measure the top-N ranked candidates on the
  real backend and persist winners to a versioned JSON ``TuningTable``
  (``kernels/tunings/<backend>.json``) keyed by
  ``(kernel, pow2 shape bucket, dtype)``.
* ``lookup`` — the resolution every ``kernels.ops`` wrapper uses when no
  explicit config is passed: tuning-table hit for the shape bucket, else
  the frozen ``DEFAULTS`` (bit-identical to the historical hard-coded
  values) where they fit ``VMEM_BUDGET_BYTES``, else the best-ranked
  candidate that does.

The tuner never changes numerics beyond float reassociation: it only
enumerates layout knobs (blocks, grid order). ``precision`` is carried on
the config so a pinned config fully determines the kernel call, but
candidates always inherit the caller's precision rather than sweeping it.

>>> from repro.kernels import tuning
>>> tuning.lookup("sketch_fused", (64, 1024, 256)).block   # table miss ->
(256, 512)
>>> cands = tuning.candidate_configs("sketch_fused", (64, 1024, 256))
>>> all(tuning.vmem_bytes(c, (64, 1024, 256)) <= tuning.VMEM_BUDGET_BYTES
...     for c in cands)
True
>>> best = tuning.rank_candidates("sketch_fused", (64, 1024, 256))[0]
>>> best == tuning.rank_candidates("sketch_fused", (64, 1024, 256))[0]
True
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, NamedTuple, Optional, Tuple

from repro.roofline.analysis import HBM_BW, PEAK_FLOPS, kernel_time_lb

#: Per-step VMEM working-set budget (bytes). The v5e core has ~16 MB of
#: VMEM; 12 MB leaves headroom for Mosaic spills and semaphores. Streamed
#: input tiles are counted twice (double-buffered by the grid pipeline),
#: resident outputs once.
VMEM_BUDGET_BYTES = 12 * 2 ** 20

#: Fixed cost charged per grid step in the static model — breaks
#: bandwidth ties toward larger tiles (fewer steps) the way real grid
#: dispatch overhead does.
STEP_OVERHEAD_S = 5e-7

LANE = 128      # last block dim granularity (all dtypes)
SUBLANE = 8     # second-to-last granularity for f32

#: Kernel name -> canonical shape tuple documented per kernel:
#:   sketch_fused     (k, d, n)       Pi: (k, d), A: (d, n)
#:   blocked_fwht     (d, n)          X: (d, n), d a power of two
#:   sampled_dot      (n1, n2, k, m)  row sketches + m sampled pairs
#:   flash_attention  (BH, S, Dh)     folded heads x sequence x head dim
KERNELS = ("sketch_fused", "blocked_fwht", "sampled_dot", "flash_attention")

#: Legal grid traversal orders per kernel (None = the kernel's default).
#: ``sketch_fused`` admits only its default: the d-loop MUST stay
#: innermost so the revisited (k, bn) output block is accumulated over
#: consecutive grid steps (Pallas TPU only guarantees revisit-in-place
#: for consecutive steps). ``blocked_fwht`` stage 1 has no revisited
#: output, so either loop may be inner.
GRID_ORDERS: Dict[str, Tuple[str, ...]] = {
    "sketch_fused": ("d_inner",),
    "blocked_fwht": ("n_inner", "p_inner"),
    "sampled_dot": (),
    "flash_attention": ("k_inner",),
}


class KernelConfig(NamedTuple):
    """One kernel's layout knobs as a hashable value.

    ``block`` is kernel-specific (see ``DEFAULTS``): ``(bn, bd)`` for
    ``sketch_fused``, ``(b, bn)`` for ``blocked_fwht``, ``()`` for
    ``sampled_dot`` (its grid is per-sample), ``(bq, bk)`` for
    ``flash_attention``. ``grid_order=None`` means the kernel's default
    traversal; ``precision`` mirrors the engine-wide None|'f32'|'bf16'
    policy (inputs cast, accumulation always f32).
    """

    kernel: str
    block: Tuple[int, ...] = ()
    grid_order: Optional[str] = None
    precision: Optional[str] = None

    def tag(self) -> str:
        """Stable short label for bench records and table entries."""
        parts = [f"b{'x'.join(str(b) for b in self.block)}" if self.block
                 else "scalar"]
        if self.grid_order:
            parts.append(self.grid_order)
        if self.precision:
            parts.append(self.precision)
        return "_".join(parts)


#: The frozen historical defaults — ``lookup`` falls back to these on a
#: table miss when they fit the VMEM budget, which is what keeps
#: default-config results bit-identical to the pre-tuning hard-coded
#: kernels.
DEFAULTS: Dict[str, KernelConfig] = {
    "sketch_fused": KernelConfig("sketch_fused", (256, 512)),
    "blocked_fwht": KernelConfig("blocked_fwht", (128, 256)),
    "sampled_dot": KernelConfig("sampled_dot", ()),
    "flash_attention": KernelConfig("flash_attention", (128, 128)),
}

_BLOCK_ARITY = {"sketch_fused": 2, "blocked_fwht": 2, "sampled_dot": 0,
                "flash_attention": 2}


class TuningSpec(NamedTuple):
    """A hashable bundle of per-kernel configs — the ``PipelinePlan``
    field. ``configs`` holds at most one config per kernel name;
    ``config_for`` returns it (or None, meaning table lookup/defaults).

    >>> from repro.kernels.tuning import KernelConfig, TuningSpec
    >>> ts = TuningSpec((KernelConfig("sketch_fused", (128, 256)),))
    >>> ts.config_for("sketch_fused").block
    (128, 256)
    >>> ts.config_for("blocked_fwht") is None
    True
    """

    configs: Tuple[KernelConfig, ...] = ()

    def config_for(self, kernel: str) -> Optional[KernelConfig]:
        """The pinned config for ``kernel``, or None (resolve via table)."""
        for cfg in self.configs:
            if cfg.kernel == kernel:
                return cfg
        return None

    def validate(self) -> None:
        """Structural validation of every pinned config (ValueError)."""
        seen = set()
        for cfg in self.configs:
            validate_config(cfg)
            if cfg.kernel in seen:
                raise ValueError(
                    f"TuningSpec pins kernel {cfg.kernel!r} more than once")
            seen.add(cfg.kernel)


def _next_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (int(x) - 1).bit_length()


def _round_up(x: int, mult: int) -> int:
    return ((int(x) + mult - 1) // mult) * mult


def validate_config(cfg: KernelConfig) -> None:
    """Reject structurally illegal configs with a ValueError naming the
    offending field. Shape-dependent feasibility (VMEM at a concrete
    shape) is the tuner's job — ``candidate_configs`` filters on it — so
    a structurally valid config is usable at any shape the kernel pads.
    """
    if not isinstance(cfg, KernelConfig):
        raise TypeError(f"expected a KernelConfig, got {type(cfg).__name__}")
    if cfg.kernel not in KERNELS:
        raise ValueError(f"unknown kernel {cfg.kernel!r} (use one of "
                         f"{KERNELS})")
    arity = _BLOCK_ARITY[cfg.kernel]
    if len(cfg.block) != arity:
        raise ValueError(
            f"{cfg.kernel} takes {arity} block sizes, got {cfg.block!r}")
    if any((not isinstance(b, int)) or b <= 0 for b in cfg.block):
        raise ValueError(f"block sizes must be positive ints, got "
                         f"{cfg.block!r}")
    if cfg.kernel == "sketch_fused":
        bn, bd = cfg.block
        if bn % LANE:
            raise ValueError(f"sketch_fused bn must be a multiple of "
                             f"{LANE}, got bn={bn}")
        if bd % SUBLANE:
            raise ValueError(f"sketch_fused bd must be a multiple of "
                             f"{SUBLANE}, got bd={bd}")
    elif cfg.kernel == "blocked_fwht":
        b, bn = cfg.block
        if b & (b - 1):
            raise ValueError(f"blocked_fwht b must be a power of two, "
                             f"got b={b}")
        if bn % LANE:
            raise ValueError(f"blocked_fwht bn must be a multiple of "
                             f"{LANE}, got bn={bn}")
    elif cfg.kernel == "flash_attention":
        bq, bk = cfg.block
        if bq % SUBLANE or bk % SUBLANE:
            raise ValueError(f"flash_attention bq/bk must be multiples of "
                             f"{SUBLANE}, got {cfg.block}")
    if cfg.grid_order is not None and \
            cfg.grid_order not in GRID_ORDERS[cfg.kernel]:
        raise ValueError(
            f"illegal grid_order {cfg.grid_order!r} for {cfg.kernel} "
            f"(legal: {GRID_ORDERS[cfg.kernel] or 'none'})")
    if cfg.precision not in (None, "f32", "bf16"):
        raise ValueError(f"unknown precision {cfg.precision!r} "
                         f"(use None|'f32'|'bf16')")


def _itemsize(precision: Optional[str], dtype_bytes: int = 4) -> int:
    if precision == "bf16":
        return 2
    if precision == "f32":
        return 4
    return dtype_bytes


def vmem_bytes(cfg: KernelConfig, shape: Tuple[int, ...]) -> int:
    """Per-grid-step VMEM working set (bytes, f32 accounting): streamed
    input tiles double-buffered, resident outputs/scratch single. This is
    the arithmetic from each kernel's header, made executable.
    """
    validate_config(cfg)
    if cfg.kernel == "sketch_fused":
        k, d, n = shape
        bn, bd = cfg.block
        bd = min(bd, _round_up(d, SUBLANE))
        return 4 * (2 * (k * bd + bd * bn) + k * bn + bn)
    if cfg.kernel == "blocked_fwht":
        d, n = shape
        b, bn = cfg.block
        b = min(b, d)
        a = d // b
        stage1 = 4 * (b * b + 2 * (b + b * bn) + b * bn)
        stage2 = 0 if a <= 1 else 4 * (a * a + 3 * a * bn)
        return max(stage1, stage2)
    if cfg.kernel == "sampled_dot":
        n1, n2, k, m = shape
        # two gathered rows double-buffered + the output element; the
        # norm vectors and the indices sit in SMEM
        return 4 * (4 * k + 2)
    if cfg.kernel == "flash_attention":
        BH, S, Dh = shape
        bq, bk = (min(b, S) for b in cfg.block)
        return 4 * (2 * (bq * Dh + 2 * bk * Dh) + bq * Dh + bq * (Dh + 2))
    raise AssertionError(cfg.kernel)


@dataclasses.dataclass(frozen=True)
class RooflineCost:
    """Static cost terms for one kernel call at one shape and config."""

    hbm_bytes: float          # total HBM traffic per call
    flops: float              # MXU/VPU flops per call
    steps: int                # grid steps per call
    mxu_occupancy: float      # fraction of the 128x128 array the tiles fill
    t_memory: float           # hbm_bytes / HBM_BW
    t_compute: float          # flops / (peak * occupancy)
    t_total: float            # max(mem, compute) + steps * STEP_OVERHEAD_S

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def _mxu_occupancy(*dims: int) -> float:
    occ = 1.0
    for d in dims:
        occ *= d / _round_up(d, LANE)
    return occ


def roofline_cost(cfg: KernelConfig, shape: Tuple[int, ...], *,
                  dtype_bytes: int = 4) -> RooflineCost:
    """The static model the ranking runs on. Bytes/flops are modeled over
    the *padded* shapes the ops wrappers actually launch, so a config
    whose blocks force heavy padding is charged for it.
    """
    validate_config(cfg)
    ds = _itemsize(cfg.precision, dtype_bytes)
    if cfg.kernel == "sketch_fused":
        k, d, n = shape
        bn, bd = cfg.block
        bd = min(bd, _round_up(d, SUBLANE))
        dp, np_ = _round_up(d, bd), _round_up(n, bn)
        # A streamed once; the (k, bd) Pi stripe re-fetched per n-tile;
        # f32 sketch + norm rows written once
        hbm = dp * np_ * ds + (np_ // bn) * k * dp * ds + 4 * (k + 1) * np_
        flops = 2.0 * k * dp * np_
        steps = (np_ // bn) * (dp // bd)
        occ = _mxu_occupancy(k, bn)
    elif cfg.kernel == "blocked_fwht":
        d, n = shape
        b, bn = cfg.block
        b = min(b, d)
        a = d // b
        np_ = _round_up(n, bn)
        # stage 1: X in (signs fused), Y out; stage 2 (a > 1): Y in, Z out
        hbm = d * np_ * ds + 4 * d * np_ + 4 * d + 4 * b * b
        flops = 2.0 * d * np_ * b
        steps = a * (np_ // bn)
        if a > 1:
            hbm += 8 * d * np_ + 4 * a * a
            flops += 2.0 * d * np_ * a
            steps += b * np_ // bn
        occ = _mxu_occupancy(b, bn)
    elif cfg.kernel == "sampled_dot":
        n1, n2, k, m = shape
        # two (1, k) gathered rows + one f32 output element per step;
        # norm rows resident (fetched once)
        hbm = m * (2 * k * ds + 4) + 4 * (n1 + n2) + 8 * m
        flops = 6.0 * m * k
        steps = m
        occ = 1.0            # VPU reduction, no MXU tile to fill
    elif cfg.kernel == "flash_attention":
        BH, S, Dh = shape
        bq, bk = (min(b, S) for b in cfg.block)
        # q/o move once; k/v re-streamed once per q-block
        hbm = 2 * BH * S * Dh * ds + 2 * BH * (S // bq) * S * Dh * ds
        flops = 4.0 * BH * S * S * Dh
        steps = BH * (S // bq) * (S // bk)
        occ = _mxu_occupancy(bq, bk)
    else:
        raise AssertionError(cfg.kernel)
    peak = PEAK_FLOPS * (1.0 if ds == 2 else 0.5)   # f32 MXU at half rate
    t_mem = hbm / HBM_BW
    t_comp = flops / (peak * max(occ, 1e-6))
    t_total = kernel_time_lb(flops, hbm, peak_flops=peak * max(occ, 1e-6),
                             steps=steps, step_overhead=STEP_OVERHEAD_S)
    return RooflineCost(hbm_bytes=float(hbm), flops=float(flops),
                        steps=int(steps), mxu_occupancy=float(occ),
                        t_memory=t_mem, t_compute=t_comp, t_total=t_total)


_BLOCK_CHOICES = {
    "sketch_fused": ((128, 256, 512), (128, 256, 512, 1024, 2048)),
    "blocked_fwht": ((32, 64, 128, 256), (128, 256, 512)),
    "flash_attention": ((64, 128, 256), (64, 128, 256)),
}


def candidate_configs(kernel: str, shape: Tuple[int, ...], *,
                      precision: Optional[str] = None,
                      vmem_budget: int = VMEM_BUDGET_BYTES
                      ) -> List[KernelConfig]:
    """All legal configs for ``kernel`` at ``shape``: block choices from
    the MXU-aligned menus, every legal grid order, filtered by the VMEM
    budget. ``precision`` is inherited, never swept (the tuner must not
    change numerics). Always contains at least one entry: if every menu
    candidate busts the budget (huge operand dims), the smallest-footprint
    one is kept so ranking has something to return.
    """
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r} (use one of {KERNELS})")
    if kernel == "sampled_dot":
        return [DEFAULTS[kernel]._replace(precision=precision)]
    choices_a, choices_b = _BLOCK_CHOICES[kernel]
    if kernel == "sketch_fused":
        k, d, n = shape
        cap_a, cap_b = _next_pow2(max(n, LANE)), _next_pow2(max(d, SUBLANE))
    elif kernel == "blocked_fwht":
        d, n = shape
        cap_a, cap_b = d, _next_pow2(max(n, LANE))
    else:                                   # flash_attention
        BH, S, Dh = shape
        cap_a = cap_b = S
    orders = GRID_ORDERS[kernel] or (None,)
    cands: List[KernelConfig] = []
    for ba in choices_a:
        if ba > cap_a:
            continue
        for bb in choices_b:
            if bb > cap_b:
                continue
            if kernel == "flash_attention" and (S % ba or S % bb):
                continue
            for order in orders:
                cands.append(KernelConfig(kernel, (ba, bb), order,
                                          precision))
    cands = [c._replace(grid_order=None)
             if c.grid_order == (GRID_ORDERS[kernel] or (None,))[0]
             else c for c in cands]
    if not cands:
        cands = [DEFAULTS[kernel]._replace(precision=precision)]
    fitting = [c for c in cands if vmem_bytes(c, shape) <= vmem_budget]
    if not fitting:
        fitting = [min(cands, key=lambda c: (vmem_bytes(c, shape), c.block))]
    return fitting


def rank_candidates(kernel: str, shape: Tuple[int, ...], *,
                    precision: Optional[str] = None, dtype_bytes: int = 4,
                    vmem_budget: int = VMEM_BUDGET_BYTES
                    ) -> List[KernelConfig]:
    """Candidates sorted best-first by the static roofline cost.

    Fully deterministic: ties on modeled time break on the config tuple
    itself, so two runs (or CI and a laptop) always agree on the order —
    which is what lets interpret-mode CPU CI pin a static ranking.
    """
    cands = candidate_configs(kernel, shape, precision=precision,
                              vmem_budget=vmem_budget)
    return sorted(cands, key=lambda c: (
        roofline_cost(c, shape, dtype_bytes=dtype_bytes).t_total,
        c.block, c.grid_order or "", c.precision or ""))


# ---------------------------------------------------------------------------
# Measurement (real-hardware half of the tuner)
# ---------------------------------------------------------------------------

def measure_config(cfg: KernelConfig, shape: Tuple[int, ...], *,
                   reps: int = 3) -> float:
    """Wall-time one kernel call (us/call) with synthetic inputs at
    ``shape`` under ``cfg``. Runs on whatever backend jax resolves —
    compiled on TPU, interpret elsewhere — so CPU numbers are only
    meaningful relative to other configs of the same kernel.
    """
    import time

    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    validate_config(cfg)
    key = jax.random.PRNGKey(0)
    if cfg.kernel == "sketch_fused":
        k, d, n = shape
        Pi = jax.random.normal(key, (k, d))
        A = jax.random.normal(jax.random.fold_in(key, 1), (d, n))
        fn = lambda: ops.sketch_fused(Pi, A, config=cfg)
    elif cfg.kernel == "blocked_fwht":
        d, n = shape
        X = jax.random.normal(key, (d, n))
        signs = jax.random.rademacher(jax.random.fold_in(key, 1), (d,),
                                      dtype=jnp.float32)
        fn = lambda: ops.blocked_fwht(X, signs, config=cfg)
    elif cfg.kernel == "sampled_dot":
        n1, n2, k, m = shape
        As = jax.random.normal(key, (n1, k))
        Bs = jax.random.normal(jax.random.fold_in(key, 1), (n2, k))
        na = jnp.ones((n1,))
        nb = jnp.ones((n2,))
        rows = jax.random.randint(jax.random.fold_in(key, 2), (m,), 0, n1)
        cols = jax.random.randint(jax.random.fold_in(key, 3), (m,), 0, n2)
        fn = lambda: ops.sampled_rescaled_dot(As, Bs, na, nb, rows, cols,
                                              config=cfg)
    elif cfg.kernel == "flash_attention":
        BH, S, Dh = shape
        qkv = jax.random.normal(key, (3, BH, S, 1, Dh))
        fn = lambda: ops.flash_attention(qkv[0], qkv[1], qkv[2],
                                         config=cfg)
    else:
        raise AssertionError(cfg.kernel)
    jax.block_until_ready(fn())                     # compile/warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def autotune(kernel: str, shape: Tuple[int, ...], *,
             precision: Optional[str] = None, dtype_bytes: int = 4,
             measure_top: int = 0, reps: int = 3,
             table: Optional["TuningTable"] = None
             ) -> Tuple[KernelConfig, List[dict]]:
    """Pick the best config for ``kernel`` at ``shape``.

    ``measure_top=0`` (the static mode CI uses) returns the roofline
    ranking's head. ``measure_top=N`` wall-times the N best-ranked
    candidates and picks the fastest measured — the real-hardware mode.
    If ``table`` is given the winner is recorded under the shape bucket.
    Returns ``(winner, records)`` where each record carries the config
    tag, the model's cost terms, and (when measured) us/call +
    achieved GB/s.
    """
    ranked = rank_candidates(kernel, shape, precision=precision,
                             dtype_bytes=dtype_bytes)
    records = []
    for cfg in ranked[:max(measure_top, 1)]:
        cost = roofline_cost(cfg, shape, dtype_bytes=dtype_bytes)
        rec = {"config": cfg.tag(), "block": list(cfg.block),
               "grid_order": cfg.grid_order, "precision": cfg.precision,
               **cost.as_dict()}
        if measure_top > 0:
            us = measure_config(cfg, shape, reps=reps)
            rec["us_per_call"] = us
            rec["achieved_gbps"] = cost.hbm_bytes / (us * 1e-6) / 1e9
        records.append((cfg, rec))
    if measure_top > 0:
        winner = min(records, key=lambda cr: cr[1]["us_per_call"])[0]
    else:
        winner = ranked[0]
    if table is not None:
        winning = next(r for c, r in records if c == winner)
        table.put(kernel, shape, winner,
                  stats={k: winning[k] for k in
                         ("us_per_call", "achieved_gbps")
                         if k in winning})
    return winner, [r for _, r in records]


# ---------------------------------------------------------------------------
# The versioned tuning table
# ---------------------------------------------------------------------------

TABLE_VERSION = 1

_DTYPE_TAGS = {2: "bf16", 4: "f32"}


def table_key(kernel: str, shape: Tuple[int, ...],
              dtype_bytes: int = 4) -> str:
    """``kernel|dtype|pow2-bucketed-shape`` — the table's lookup key.
    Bucketing each dim up to a power of two lets one measured winner
    serve the whole neighborhood of shapes that pad/tile identically.
    """
    bucket = "x".join(str(_next_pow2(s)) for s in shape)
    return f"{kernel}|{_DTYPE_TAGS.get(dtype_bytes, dtype_bytes)}|{bucket}"


@dataclasses.dataclass
class TuningTable:
    """Persisted winners: ``{table_key: config dict}`` + provenance.

    >>> from repro.kernels.tuning import (DEFAULTS, KernelConfig,
    ...                                   TuningTable)
    >>> t = TuningTable(backend="cpu")
    >>> t.put("sketch_fused", (64, 1000, 300),
    ...       KernelConfig("sketch_fused", (128, 1024)))
    >>> t.get("sketch_fused", (64, 1024, 512)).block    # same pow2 bucket
    (128, 1024)
    >>> t.get("sketch_fused", (64, 4096, 512)) is None  # unknown bucket
    True
    """

    backend: str = "any"
    version: int = TABLE_VERSION
    entries: Dict[str, dict] = dataclasses.field(default_factory=dict)

    def put(self, kernel: str, shape: Tuple[int, ...], cfg: KernelConfig,
            *, dtype_bytes: int = 4, stats: Optional[dict] = None) -> None:
        """Record ``cfg`` as the winner for the shape's bucket."""
        validate_config(cfg)
        entry = {"block": list(cfg.block), "grid_order": cfg.grid_order,
                 "precision": cfg.precision}
        if stats:
            entry["stats"] = dict(stats)
        self.entries[table_key(kernel, shape, dtype_bytes)] = entry

    def get(self, kernel: str, shape: Tuple[int, ...],
            dtype_bytes: int = 4) -> Optional[KernelConfig]:
        """The recorded winner for the shape's bucket, or None."""
        entry = self.entries.get(table_key(kernel, shape, dtype_bytes))
        if entry is None:
            return None
        return KernelConfig(kernel, tuple(entry["block"]),
                            entry.get("grid_order"),
                            entry.get("precision"))

    def save(self, path: str) -> None:
        """Write the versioned JSON artifact."""
        payload = {"version": self.version, "backend": self.backend,
                   "entries": self.entries}
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")

    @classmethod
    def load(cls, path: str) -> "TuningTable":
        """Read a table; a version newer than this code understands is an
        error (the format is versioned precisely so stale readers fail
        loudly instead of silently mis-tuning)."""
        with open(path) as f:
            payload = json.load(f)
        version = payload.get("version")
        if version != TABLE_VERSION:
            raise ValueError(
                f"{path}: tuning-table version {version!r} not supported "
                f"(this build reads version {TABLE_VERSION})")
        return cls(backend=payload.get("backend", "any"),
                   version=version, entries=dict(payload.get("entries", {})))


_TUNINGS_DIR = os.path.join(os.path.dirname(__file__), "tunings")
_TABLE_CACHE: Dict[str, TuningTable] = {}


def table_path(backend: str) -> str:
    """Where the committed table for a backend lives."""
    return os.path.join(_TUNINGS_DIR, f"{backend}.json")


def builtin_table(backend: Optional[str] = None) -> TuningTable:
    """The committed table for ``backend`` (default: the jax backend),
    cached per process; an absent file is an empty table. Call
    ``reload_tables()`` after editing a table on disk — resolutions are
    read at trace time, so already-compiled executables keep the config
    they were traced with.
    """
    if backend is None:
        import jax
        backend = jax.default_backend()
    if backend not in _TABLE_CACHE:
        path = table_path(backend)
        _TABLE_CACHE[backend] = (TuningTable.load(path)
                                 if os.path.exists(path)
                                 else TuningTable(backend=backend))
    return _TABLE_CACHE[backend]


def reload_tables() -> None:
    """Drop the per-process table cache (next lookup re-reads disk)."""
    _TABLE_CACHE.clear()


def lookup(kernel: str, shape: Tuple[int, ...], *, dtype_bytes: int = 4,
           backend: Optional[str] = None) -> KernelConfig:
    """The ops-wrapper resolution: committed-table hit for the shape
    bucket, else the frozen default when its VMEM working set fits
    ``VMEM_BUDGET_BYTES``, else the roofline ranking's head among the
    candidates that fit (a layout change only: float reassociation at
    most). Never returns None; a shape the default fits gets exactly the
    historical block sizes.
    """
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r} (use one of {KERNELS})")
    hit = builtin_table(backend).get(kernel, shape, dtype_bytes)
    if hit is not None:
        return hit
    if vmem_bytes(DEFAULTS[kernel], shape) <= VMEM_BUDGET_BYTES:
        return DEFAULTS[kernel]
    return rank_candidates(kernel, shape, dtype_bytes=dtype_bytes)[0]


def dtype_bytes_of(x) -> int:
    """Map an array (or dtype) to the table's dtype granularity."""
    try:
        size = x.dtype.itemsize
    except AttributeError:
        import numpy as np
        size = np.dtype(x).itemsize
    return 2 if size == 2 else 4


def retune(shapes: Dict[str, List[Tuple[int, ...]]], *, backend: str,
           measure_top: int = 4, reps: int = 3,
           out_path: Optional[str] = None) -> TuningTable:
    """Measure-and-persist for a dict of ``{kernel: [shapes...]}`` — the
    re-tune-on-new-hardware entry point (see docs/kernels.md). Returns
    the table (written to ``out_path`` or the committed location).
    """
    table = TuningTable(backend=backend)
    for kernel, shape_list in shapes.items():
        for shape in shape_list:
            autotune(kernel, shape, measure_top=measure_top, reps=reps,
                     table=table)
    table.save(out_path or table_path(backend))
    return table


def _occupancy_note() -> str:   # pragma: no cover - doc helper
    return (f"MXU occupancy derates {PEAK_FLOPS / 1e12:.0f} TFLOP/s peak; "
            f"HBM terms assume {HBM_BW / 1e9:.0f} GB/s")
