"""KernelConfig / autotuner tests: block-size invariance sweeps (a legal
config may change wall time, never results — bit-identical where the
accumulation order is unchanged, reassociation tolerance otherwise),
candidate enumeration under the VMEM budget, deterministic roofline
ranking, tuning-table round-trips, and the tuning thread through the
PipelineEngine (default path bit-identical, warm traffic trace-free
under a pinned non-default TuningSpec)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import summary_engine
from repro.core.pipeline import (
    PipelineEngine, PipelinePlan, RankPolicy, SketchSpec, validate_plan)
from repro.kernels import ops, tuning
from repro.kernels.tuning import (
    DEFAULTS, KernelConfig, TuningSpec, TuningTable, candidate_configs,
    rank_candidates, table_key, validate_config, vmem_bytes)

from tests.conftest import gaussian_pair


def _sk(bn, bd, **kw):
    return KernelConfig("sketch_fused", (bn, bd), **kw)


def _fw(b, bn, **kw):
    return KernelConfig("blocked_fwht", (b, bn), **kw)


# ---------------------------------------------------------------------------
# Block-size invariance sweeps: configs tune, they never change answers
# ---------------------------------------------------------------------------

@pytest.mark.kernel
@pytest.mark.parametrize("bn", [128, 256, 512])
def test_sketch_fused_bn_sweep_bit_identical(bn):
    """Fixed bd: every output element sums the same bd-chunks in the same
    order whatever bn tiles the columns. Inside a chunk, the backend's dot
    may still order its products by the tile's width (XLA's CPU dot does),
    so the sweep agrees to the roundoff floor of the bd sweep below."""
    kk = jax.random.PRNGKey(1)
    Pi = jax.random.normal(kk, (16, 512))
    A = jax.random.normal(jax.random.fold_in(kk, 1), (512, 512))
    base, nbase = ops.sketch_fused(Pi, A, config=_sk(512, 256))
    got, ngot = ops.sketch_fused(Pi, A, config=_sk(bn, 256))
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               rtol=1e-5, atol=5e-5)
    np.testing.assert_array_equal(np.asarray(ngot), np.asarray(nbase))


@pytest.mark.kernel
@pytest.mark.parametrize("bd", [128, 256, 512])
def test_sketch_fused_bd_sweep_reassociation_tolerance(bd):
    """Changing bd re-chunks the d-accumulation (different f32
    reassociation); the sweep agrees to the roundoff floor only."""
    kk = jax.random.PRNGKey(1)
    Pi = jax.random.normal(kk, (16, 512))
    A = jax.random.normal(jax.random.fold_in(kk, 1), (512, 256))
    base, nbase = ops.sketch_fused(Pi, A, config=_sk(256, 512))
    got, ngot = ops.sketch_fused(Pi, A, config=_sk(256, bd))
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               rtol=1e-5, atol=5e-5)
    np.testing.assert_allclose(np.asarray(ngot), np.asarray(nbase),
                               rtol=1e-5)


@pytest.mark.kernel
@pytest.mark.parametrize("bn", [128, 256])
@pytest.mark.parametrize("grid_order", [None, "n_inner", "p_inner"])
def test_blocked_fwht_bn_and_grid_order_bit_identical(bn, grid_order):
    """Stage-1 outputs are write-once (no revisited block), so both grid
    traversals and any column tiling must be bit-identical."""
    kk = jax.random.PRNGKey(2)
    X = jax.random.normal(kk, (512, 384))
    signs = jax.random.rademacher(jax.random.fold_in(kk, 1), (512,),
                                  dtype=jnp.float32)
    base = ops.blocked_fwht(X, signs, config=_fw(128, 128))
    got = ops.blocked_fwht(X, signs,
                           config=_fw(128, bn, grid_order=grid_order))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


@pytest.mark.kernel
@pytest.mark.parametrize("b", [32, 64, 256])
def test_blocked_fwht_b_sweep_reassociation_tolerance(b):
    """Changing b re-factors the butterfly (H_d = (H_a (x) I) (I (x) H_b)
    at a different split) — same transform, different f32 order."""
    kk = jax.random.PRNGKey(2)
    X = jax.random.normal(kk, (512, 192))
    signs = jax.random.rademacher(jax.random.fold_in(kk, 1), (512,),
                                  dtype=jnp.float32)
    base = ops.blocked_fwht(X, signs, config=_fw(128, 256))
    got = ops.blocked_fwht(X, signs, config=_fw(b, 256))
    scale = np.abs(np.asarray(base)).max()
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               rtol=1e-4, atol=1e-4 * scale)


@pytest.mark.kernel
def test_sampled_dot_precision_sweep():
    """precision=None and 'f32' are the same kernel on f32 inputs
    (bit-identical); 'bf16' halves the gathered-row DMA and only loosens
    to bf16 accuracy; unknown precision is rejected by name."""
    kk = jax.random.PRNGKey(3)
    As = jax.random.normal(kk, (64, 32))
    Bs = jax.random.normal(jax.random.fold_in(kk, 1), (64, 32))
    na = jnp.abs(jax.random.normal(jax.random.fold_in(kk, 2), (64,))) + 0.5
    nb = jnp.abs(jax.random.normal(jax.random.fold_in(kk, 3), (64,))) + 0.5
    rows = jax.random.randint(jax.random.fold_in(kk, 4), (50,), 0, 64)
    cols = jax.random.randint(jax.random.fold_in(kk, 5), (50,), 0, 64)
    base = ops.sampled_rescaled_dot(As, Bs, na, nb, rows, cols)
    cfg = KernelConfig("sampled_dot", (), precision="f32")
    same = ops.sampled_rescaled_dot(As, Bs, na, nb, rows, cols, config=cfg)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(base))
    half = ops.sampled_rescaled_dot(
        As, Bs, na, nb, rows, cols,
        config=KernelConfig("sampled_dot", (), precision="bf16"))
    np.testing.assert_allclose(np.asarray(half), np.asarray(base),
                               rtol=5e-2, atol=5e-2)
    with pytest.raises(ValueError, match="precision"):
        ops.sampled_rescaled_dot(As, Bs, na, nb, rows, cols,
                                 precision="f64")


# ---------------------------------------------------------------------------
# Config validation + the assert-to-ValueError bugfixes
# ---------------------------------------------------------------------------

def test_validate_config_rejects_bad_configs():
    with pytest.raises(ValueError, match="unknown kernel"):
        validate_config(KernelConfig("nope", (128, 128)))
    with pytest.raises(ValueError, match="block"):
        validate_config(KernelConfig("sketch_fused", (128,)))
    with pytest.raises(ValueError, match="128"):
        validate_config(_sk(100, 256))            # bn not lane-aligned
    with pytest.raises(ValueError, match="power of two"):
        validate_config(_fw(96, 128))             # b not a power of two
    with pytest.raises(ValueError, match="grid_order"):
        validate_config(_sk(128, 256, grid_order="p_inner"))
    with pytest.raises(ValueError, match="precision"):
        validate_config(_sk(128, 256, precision="f64"))
    with pytest.raises(TypeError):
        validate_config(("sketch_fused", (128, 256)))


def test_tuning_spec_rejects_duplicate_kernels():
    with pytest.raises(ValueError, match="more than once"):
        TuningSpec((_sk(128, 256), _sk(256, 256))).validate()
    ts = TuningSpec((_sk(128, 256), _fw(128, 128)))
    ts.validate()
    assert ts.config_for("sketch_fused") == _sk(128, 256)
    assert ts.config_for("sampled_dot") is None


def test_shape_errors_are_valueerrors_not_asserts():
    """The -O-strippable asserts are gone: bad shapes raise ValueErrors
    that name the offending dims even under python -O."""
    from repro.kernels import flash_attention as fa
    from repro.kernels import sketch_fused as sf
    kk = jax.random.PRNGKey(0)
    X = jax.random.normal(kk, (100, 8))           # d=100 not a power of two
    with pytest.raises(ValueError, match="power of two"):
        ops.blocked_fwht(X, jnp.ones((100,)))
    Pi = jax.random.normal(kk, (8, 256))
    A = jax.random.normal(kk, (128, 64))
    with pytest.raises(ValueError, match="disagree on d"):
        sf.sketch_fused(Pi, A, bn=64, bd=128)
    A2 = jax.random.normal(kk, (256, 100))        # n=100 not divisible by bn
    with pytest.raises(ValueError, match="divisible"):
        sf.sketch_fused(Pi, A2, bn=64, bd=128)
    qkv = jax.random.normal(kk, (3, 2, 100, 16))  # S=100, bq=64: 100 % 64
    with pytest.raises(ValueError, match="divisible"):
        fa.flash_attention(qkv[0], qkv[1], qkv[2], bq=64, bk=50)


def test_ops_kwarg_overrides_config_and_kernel_mismatch_rejected():
    kk = jax.random.PRNGKey(4)
    Pi = jax.random.normal(kk, (8, 256))
    A = jax.random.normal(jax.random.fold_in(kk, 1), (256, 128))
    got, _ = ops.sketch_fused(Pi, A, bd=128, config=_sk(128, 256))
    want, _ = ops.sketch_fused(Pi, A, bn=128, bd=128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError, match="sketch_fused"):
        ops.sketch_fused(Pi, A, config=_fw(128, 128))


# ---------------------------------------------------------------------------
# Autotuner: candidates, ranking, tables, fallback
# ---------------------------------------------------------------------------

def test_candidates_respect_vmem_budget_and_alignment():
    shape = (128, 4096, 512)
    cands = candidate_configs("sketch_fused", shape)
    assert cands
    for cfg in cands:
        validate_config(cfg)                      # alignment-legal
        assert vmem_bytes(cfg, shape) <= tuning.VMEM_BUDGET_BYTES


def test_candidates_tiny_budget_falls_back_to_min_footprint():
    """An impossible budget still yields one candidate (the smallest
    footprint) instead of an empty sweep."""
    shape = (128, 4096, 512)
    cands = candidate_configs("sketch_fused", shape, vmem_budget=1)
    assert len(cands) == 1
    full = candidate_configs("sketch_fused", shape)
    assert min(vmem_bytes(c, shape) for c in full) == \
        vmem_bytes(cands[0], shape)


def test_ranking_is_deterministic():
    shape = (64, 2048, 512)
    r1 = rank_candidates("sketch_fused", shape)
    r2 = rank_candidates("sketch_fused", shape)
    assert r1 == r2 and len(r1) >= 2
    costs = [tuning.roofline_cost(c, shape).t_total for c in r1]
    assert costs == sorted(costs)


def test_autotune_static_mode_returns_ranking_head():
    shape = (64, 2048, 512)
    winner, records = tuning.autotune("sketch_fused", shape)
    assert winner == rank_candidates("sketch_fused", shape)[0]
    assert records and "t_total" in records[0]
    assert "us_per_call" not in records[0]        # static: nothing measured


def test_table_round_trip_and_version_check(tmp_path):
    t = TuningTable(backend="cpu")
    cfg = _sk(128, 256)
    t.put("sketch_fused", (100, 3000, 400), cfg, stats={"us_per_call": 7.0})
    # pow2 bucketing: any shape in the same bucket hits the same entry
    assert t.get("sketch_fused", (128, 4096, 512)) == cfg
    assert t.get("sketch_fused", (128, 8192, 512)) is None
    path = str(tmp_path / "cpu.json")
    t.save(path)
    back = TuningTable.load(path)
    assert back.get("sketch_fused", (100, 3000, 400)) == cfg
    assert back.backend == "cpu" and back.version == tuning.TABLE_VERSION
    with open(path) as f:
        blob = json.load(f)
    blob["version"] = tuning.TABLE_VERSION + 1
    with open(path, "w") as f:
        json.dump(blob, f)
    with pytest.raises(ValueError, match="version"):
        TuningTable.load(path)


def test_lookup_unknown_shape_falls_back_to_defaults():
    assert table_key("sketch_fused", (100, 3000, 400)) == \
        table_key("sketch_fused", (128, 4096, 512))
    for kernel in tuning.KERNELS:
        shape = {"sketch_fused": (3, 5, 7), "blocked_fwht": (17, 3),
                 "sampled_dot": (3, 3, 3, 3),
                 "flash_attention": (1, 3, 3)}[kernel]
        assert tuning.lookup(kernel, shape) == DEFAULTS[kernel]


# ---------------------------------------------------------------------------
# The tuning thread: plans, engine cache keys, default parity
# ---------------------------------------------------------------------------

def test_plan_rejects_bad_tuning(key):
    with pytest.raises(ValueError, match="TuningSpec"):
        validate_plan(PipelinePlan(rank=RankPolicy(r=2),
                                   tuning=("sketch_fused",)))
    with pytest.raises(ValueError, match="more than once"):
        validate_plan(PipelinePlan(
            rank=RankPolicy(r=2),
            tuning=TuningSpec((_sk(128, 256), _sk(256, 256)))))


def test_default_tuning_bitwise_parity(key):
    """tuning=None must reproduce the pre-tuner pallas path bit-for-bit:
    the frozen DEFAULTS are the historical hard-coded blocks."""
    A, B = gaussian_pair(key, d=384, n1=12, n2=9)
    base = summary_engine.build_summary(key, A, B, 16, backend="pallas")
    pinned = summary_engine.build_summary(
        key, A, B, 16, backend="pallas",
        tuning=TuningSpec((DEFAULTS["sketch_fused"],)))
    for name in ("A_sketch", "B_sketch", "norm_A", "norm_B"):
        np.testing.assert_array_equal(np.asarray(getattr(base, name)),
                                      np.asarray(getattr(pinned, name)))


def test_nondefault_tuning_close_and_separately_cached(key):
    """A non-default TuningSpec changes only float reassociation — and gets
    its own executable-cache entry (the spec is part of the plan key)."""
    A, B = gaussian_pair(key, d=384, n1=10, n2=8)
    eng = PipelineEngine()
    spec = SketchSpec(backend="pallas", k=16, block=64)
    ts = TuningSpec((_sk(128, 256),))
    s_def = eng.summarize(spec, key, A, B)
    s_tun = eng.summarize(spec, key, A, B, ts)
    assert eng.stats.misses == 2                  # distinct cache entries
    np.testing.assert_allclose(np.asarray(s_tun.A_sketch),
                               np.asarray(s_def.A_sketch),
                               rtol=1e-5, atol=5e-5)
    eng.summarize(spec, key, A, B, ts)            # warm: pure hit
    assert eng.stats.hits == 1


def test_warm_traffic_zero_retraces_with_nondefault_tuning(key):
    """Acceptance gate: a warm engine under a pinned non-default tuning
    never re-traces on repeat-shape traffic."""
    from repro.serve.engine import SketchService
    eng = PipelineEngine()
    ts = TuningSpec((_sk(128, 256), _fw(64, 128, grid_order="p_inner")))
    svc = SketchService(k=16, backend="pallas", block=64, engine=eng,
                        tuning=ts)

    def flush_once():
        for i in range(3):
            kk = jax.random.fold_in(key, i)
            A = jax.random.normal(kk, (256, 12))
            B = jax.random.normal(jax.random.fold_in(kk, 9), (256, 12))
            svc.submit(kk, A, B)
        return svc.flush_factors(r=2, m=80, T=2)

    cold = flush_once()
    traces0 = eng.stats.traces
    warm = flush_once()
    assert eng.stats.traces == traces0            # zero new traces
    for t_c, t_w in zip(cold, warm):
        np.testing.assert_array_equal(
            np.asarray(cold[t_c].factors.U), np.asarray(warm[t_w].factors.U))


def test_srht_pipeline_with_tuned_fwht(key):
    """The srht sketch path threads the blocked_fwht config end-to-end and
    stays a valid subspace embedding under a non-default tiling."""
    A, B = gaussian_pair(key, d=300, n1=9, n2=6)   # non-pow2 d: pad + fwht
    ts = TuningSpec((_fw(64, 128, grid_order="p_inner"),))
    s = summary_engine.build_summary(key, A, B, 64, method="srht",
                                     backend="pallas", tuning=ts)
    ref_s = summary_engine.build_summary(key, A, B, 64, method="srht",
                                         backend="pallas")
    np.testing.assert_allclose(np.asarray(s.A_sketch),
                               np.asarray(ref_s.A_sketch),
                               rtol=1e-4, atol=1e-4)
