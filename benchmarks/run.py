"""Benchmark harness — one benchmark per paper table/figure, plus the
EstimationEngine sweep that feeds the perf trajectory.

``--suite paper`` (default) prints ``name,us_per_call,derived`` CSV rows
(derived = the figure's claim, see each docstring). ``--suite estimation``
runs the ``estimation_backends`` sweep — every EstimationEngine
(method, backend) cell timed on one summary, spectral error measured against
the two-pass LELA baseline — and writes machine-readable
``BENCH_estimation.json`` (``--out``). ``--suite streaming`` runs the
``streaming_sweep`` — chunk-size x ingestion-mode cells (sequential /
tree-merge / shuffled-rows StreamingSummarizer vs the one-shot backends)
with parity errors — and writes ``BENCH_streaming.json``
(``--out-streaming``). ``--suite error`` runs the ``error_sweep`` —
estimated-vs-true residual across rank x probe-count cells plus the
``adaptive_rank`` tolerance sweep — and writes ``BENCH_error.json``
(``--out-error``). ``--suite serving`` runs the ``serving_sweep`` —
cold-vs-warm ``SketchService`` plans through the compile-once
PipelineEngine (per-request latency, trace counts, executable-cache hits
for fixed-rank, with-error, and quality-gated plans) — and writes
``BENCH_serving.json`` (``--out-serving``). ``--suite traffic`` runs the
``traffic_sweep`` — Poisson arrivals x shape-mix x tenant-mix through the
continuously-batched ``ServingLoop`` (requests/sec, p50/p99 latency, batch
occupancy, shed rate) — and merges its report into the same
``BENCH_serving.json`` under the ``"traffic"`` key. ``--suite kernels``
runs the ``kernel_sweep`` — every tunable Pallas kernel x shape x
KernelConfig cell (the roofline ranking head plus the frozen default),
recording us/call, achieved GB/s against the model's HBM-byte count, and
the static cost terms — and writes ``BENCH_kernels.json``
(``--out-kernels``). Every suite stamps a ``meta`` block (git sha, jax
version, backend, smoke flag) into its JSON so ``tools/bench_compare.py``
can refuse cross-backend comparisons; ``--smoke`` shrinks sizes for CI.

Real datasets (SIFT10K/NIPS-BW/URL) are not redistributable offline;
spectrum-matched synthetic stand-ins validate the paper's *relative* claims
(orderings/ratios/trends). CPU container: absolute wall times are
CPU-relative; ratios are the signal.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import time
import zlib

import jax
import jax.numpy as jnp

from repro import core
from repro.compile_cache import enable_compile_cache
from repro.core import estimator as est

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _meta(smoke: bool) -> dict:
    """Provenance block every BENCH_*.json carries: which commit, which jax,
    which device backend, and whether sizes were smoke-reduced. This is what
    lets tools/bench_compare.py refuse apples-to-oranges comparisons."""
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=_REPO, text=True,
            capture_output=True, timeout=10).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        sha = "unknown"
    return {
        "git_sha": sha,
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "smoke": bool(smoke),
    }


def _timed(fn, *args, reps=1, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) / reps * 1e6


def _gd_pair(key, d, n, corr=None, decay=1.0):
    kA, kB = jax.random.split(key)
    D = jnp.diag(1.0 / jnp.arange(1.0, n + 1.0) ** decay)
    A = jax.random.normal(kA, (d, n)) @ D
    B = A + corr * jax.random.normal(kB, (d, n)) @ D if corr is not None \
        else jax.random.normal(kB, (d, n)) @ D
    return A, B


def _cone_pair(key, d, n, theta):
    """Unit vectors from a cone of angle theta (paper Fig 2b construction)."""
    kx, kt, ks = jax.random.split(key, 3)
    x = jax.random.normal(kx, (d, 1))
    x = x / jnp.linalg.norm(x)
    t = jax.random.normal(kt, (d, 2 * n)) * (math.tan(theta / 2) / math.sqrt(d))
    sign = jnp.where(jax.random.bernoulli(ks, 0.5, (2 * n,)), 1.0, -1.0)
    y = (x + t) * sign[None, :]
    y = y / jnp.linalg.norm(y, axis=0)
    return y[:, :n], y[:, n:]


def _err(A, B, factors):
    return float(core.spectral_error(A, B, factors))


# ---------------------------------------------------------------------------

def fig2a_rescaled_jl(key):
    """Fig 2(a): rescaled-JL dot products have lower MSE than plain JL
    (paper: 0.053 vs 0.129 at d=1000, k=10). derived = mse_plain/mse_resc."""
    d, k, npairs = 1000, 10, 512
    kx, kt, ks, ka = jax.random.split(key, 4)
    x = jax.random.normal(kx, (d, npairs))
    x = x / jnp.linalg.norm(x, axis=0)
    # paper construction: y = x + t, E||t|| = tan(theta/2), theta ~ U(0.1, 3)
    theta = jax.random.uniform(ka, (npairs,), minval=0.1, maxval=3.0)
    t = jax.random.normal(kt, (d, npairs)) / math.sqrt(d) *         jnp.tan(theta / 2)[None, :]
    y = x + t
    y = y / jnp.linalg.norm(y, axis=0)
    true = jnp.sum(x * y, axis=0)

    def run():
        s = core.build_summary(ks, x, y, k)
        idx = jnp.arange(npairs)
        return (est.rescaled_entries(s, idx, idx),
                est.plain_jl_entries(s, idx, idx))

    (resc, plain), us = _timed(run)
    mse_r = float(jnp.mean((resc - true) ** 2))
    mse_p = float(jnp.mean((plain - true) ** 2))
    return us, mse_p / mse_r, f"mse_resc={mse_r:.4f} mse_plain={mse_p:.4f}"


def fig2b_cone(key):
    """Fig 2(b): ||A^TB - A~^TB~|| / ||A^TB - M~|| >= 1, growing as the cone
    angle shrinks. derived = ratio at theta=0.2rad."""
    d, n, k = 1000, 120, 32
    ratios = {}
    us_tot = 0.0
    for theta in (0.2, 0.8, 2.0):
        A, B = _cone_pair(jax.random.fold_in(key, int(theta * 10)), d, n, theta)
        M = A.T @ B

        def run():
            s = core.build_summary(key, A, B, k)
            plain = s.A_sketch.T @ s.B_sketch
            resc = est.rescaled_matrix(s)
            return (jnp.linalg.norm(M - plain, ord=2),
                    jnp.linalg.norm(M - resc, ord=2))
        (e_plain, e_resc), us = _timed(run)
        us_tot += us
        ratios[theta] = float(e_plain) / max(float(e_resc), 1e-12)
    notes = " ".join(f"theta={t}:ratio={r:.2f}" for t, r in ratios.items())
    return us_tot, ratios[0.2], notes


def fig3a_runtime(key):
    """Fig 3(a): one-pass SMP-PCA vs two-pass LELA wall time (paper: ~2x from
    halved IO passes; here both matrices are in memory so the ratio reflects
    compute only — passes over data are 1 vs 2 by construction)."""
    d, n, r = 16384, 768, 5
    A, B = _gd_pair(key, d, n, corr=0.3)
    m = int(4 * n * r * math.log(n))
    _, us_smp = _timed(lambda: core.smppca(key, A, B, r=r, k=256, m=m, T=5),
                       reps=1)
    _, us_lela = _timed(lambda: core.lela(key, A, B, r=r, m=m, T=5), reps=1)
    return us_smp, us_lela / us_smp, \
        f"smp_ms={us_smp/1e3:.0f} lela_ms={us_lela/1e3:.0f} passes=1v2"


def fig3b_error_vs_k(key):
    """Fig 3(b): SMP-PCA error decreases with k and beats SVD(A~^T B~)
    (paper: 1.8x on SIFT10K, 1.1x on NIPS-BW). Synthetic stand-in:
    SIFT-like dense image-by-feature matrix, A=B (PCA task)."""
    r = 5
    kk = jax.random.fold_in(key, 1)
    feats = jax.random.normal(kk, (2000, 128)) @ \
        jnp.diag(1.0 / jnp.arange(1.0, 129.0) ** 0.7)
    A_s = feats
    m = int(10 * 128 * r * math.log(128))
    errs = {}
    us_tot = 0.0
    for k in (64, 128, 256):
        res, us = _timed(lambda k=k: core.smppca(
            kk, A_s, A_s, r=r, k=k, m=m, T=6))
        us_tot += us
        errs[k] = _err(A_s, A_s, res.factors)
    sf, _ = _timed(lambda: core.sketch_svd(kk, A_s, A_s, r=r, k=128))
    e_svd = _err(A_s, A_s, sf)
    mono = errs[64] >= errs[256]
    return us_tot, e_svd / errs[128], \
        (f"err@k64={errs[64]:.3f} k128={errs[128]:.3f} k256={errs[256]:.3f} "
         f"sketchsvd@128={e_svd:.3f} monotone={mono}")


def table1_errors(key):
    """Table 1: Optimal <= LELA <= SMP-PCA with small gaps (synthetic GD).
    derived = err_smppca / err_optimal."""
    d, n, r, k = 2000, 1000, 5, 512
    # CPU-scale note: the paper's synthetic is n=d=1e5 where the Remark-2
    # ratio ||A||*||B||/||A^TB||_F is benign; at n=1e3 the independent case
    # is eta-divergent, so we add mild correlation (URL datasets are
    # correlated cross-covariances too). See EXPERIMENTS.md.
    A, B = _gd_pair(key, d, n, corr=0.5)
    m = int(10 * n * r * math.log(n))
    res, us = _timed(lambda: core.smppca(key, A, B, r=r, k=k, m=m, T=6))
    e_smp, e_opt = core.spectral_error_vs_optimal(A, B, r, res.factors)
    lf, _ = _timed(lambda: core.lela(key, A, B, r=r, m=m, T=6))
    e_lela = _err(A, B, lf)
    return us, float(e_smp) / float(e_opt), \
        (f"optimal={float(e_opt):.4f} lela={e_lela:.4f} "
         f"smppca={float(e_smp):.4f}")


def fig4a_phase(key):
    """Fig 4(a): phase transition at m = Theta(nr log n).
    derived = err(m=0.5x) / err(m=4x)."""
    d, n, r = 1000, 400, 3
    kU, kV = jax.random.split(key)
    A = jax.random.normal(kU, (d, n))
    B = (A @ jax.random.normal(kV, (n, r)) @ jax.random.normal(
        jax.random.fold_in(kV, 1), (r, n)) / n
         + 0.01 * jax.random.normal(jax.random.fold_in(kV, 2), (d, n)))
    base = n * r * math.log(n)
    errs = {}
    us_tot = 0.0
    for mult in (0.5, 1.0, 4.0):
        m = int(mult * base)
        lf, us = _timed(lambda m=m: core.lela(key, A, B, r=r, m=m, T=8))
        us_tot += us
        errs[mult] = _err(A, B, lf)
    return us_tot, errs[0.5] / errs[4.0], \
        " ".join(f"{mu}x:{e:.3f}" for mu, e in errs.items())


def fig4b_cone_full(key):
    """Fig 4(b): full-pipeline (sampling+ALS) error ratio SVD(A~^TB~)/SMP-PCA
    grows as the cone angle shrinks."""
    d, n, r, k = 1000, 150, 3, 64
    out = {}
    us_tot = 0.0
    m = int(10 * n * r * math.log(n))
    for theta in (0.2, 1.0):
        A, B = _cone_pair(jax.random.fold_in(key, int(theta * 10)), d, n, theta)
        res, us = _timed(lambda A=A, B=B: core.smppca(
            key, A, B, r=r, k=k, m=m, T=6))
        us_tot += us
        sf, _ = _timed(lambda A=A, B=B: core.sketch_svd(key, A, B, r=r, k=k))
        out[theta] = _err(A, B, sf) / max(_err(A, B, res.factors), 1e-9)
    return us_tot, out[0.2], \
        " ".join(f"theta={t}:ratio={v:.2f}" for t, v in out.items())


def fig4c_orthogonal(key):
    """Fig 4(c): A_r^T B_r fails when per-matrix top subspaces are orthogonal
    while the product's signal lives in shared lower directions."""
    d, n, r = 600, 60, 3
    kq, kn = jax.random.split(key)
    Q, _ = jnp.linalg.qr(jax.random.normal(kq, (d, 3 * r)))
    CA = jax.random.normal(jax.random.fold_in(key, 1), (r, n))
    CB = jax.random.normal(jax.random.fold_in(key, 2), (r, n))
    SA = jax.random.normal(jax.random.fold_in(key, 3), (r, n))
    SB = jax.random.normal(jax.random.fold_in(key, 4), (r, n))
    A = 3.0 * Q[:, :r] @ CA + 1.5 * Q[:, 2 * r:] @ SA + \
        0.05 * jax.random.normal(kn, (d, n))
    B = 3.0 * Q[:, r:2 * r] @ CB + 1.5 * Q[:, 2 * r:] @ SB + \
        0.05 * jax.random.normal(jax.random.fold_in(kn, 1), (d, n))
    m = int(14 * n * r * math.log(n))
    pp, us = _timed(lambda: core.product_of_pcas(key, A, B, r))
    e_pp = _err(A, B, pp)
    res, _ = _timed(lambda: core.smppca(key, A, B, r=r, k=512, m=m, T=6))
    e_smp = _err(A, B, res.factors)
    return us, e_pp / e_smp, f"ArBr={e_pp:.3f} smppca={e_smp:.3f}"


def grad_compression(key):
    """Beyond-paper §3 integration: tap-path (X, dY sketches) vs A=I baseline
    gradient compression quality. derived = cosine(tap reconstruction, true
    grad); notes include the A=I baseline cosine — the gap shows why the
    paper's side information (true column norms + low stable rank) matters."""
    from repro.train import sketched_dense as sd
    from repro.optim import grad_compression as gc
    n_in, n_out, T = 256, 1024, 8192
    kw, kx, kz, kp1, kp2 = jax.random.split(key, 5)
    w_true = jax.random.normal(kw, (n_in, n_out)) * 0.05
    pert = (jax.random.normal(kp1, (n_in, 6)) @
            jax.random.normal(kp2, (6, n_out))) * 0.02
    w = w_true + pert
    z = jax.random.normal(kz, (8, T // 8, 16))
    E = jax.random.normal(jax.random.fold_in(kx, 1), (16, n_in))
    x = z @ E + 0.05 * jax.random.normal(kx, (8, T // 8, n_in))
    target = x @ w_true
    taps = sd.tap_init(n_in, n_out, 128)

    def loss_fn(w, taps, x):
        return jnp.mean((sd.sketched_dense(w, taps, x, key, 128, 1024)
                         - target) ** 2)

    def run():
        _, dtaps, _ = jax.grad(loss_fn, argnums=(0, 1, 2))(w, taps, x)
        return sd.decompress_tap(key, dtaps, sd.TapConfig(sketch_k=128, rank=8))

    ghat, us = _timed(run)
    dw_true = jax.grad(lambda w: jnp.mean((x @ w - target) ** 2))(w)
    cos_t = float(jnp.sum(dw_true * ghat) /
                  (jnp.linalg.norm(dw_true) * jnp.linalg.norm(ghat)))
    ghat2 = gc.compress_leaf(key, dw_true,
                             gc.CompressionConfig(rank=8, sketch_k=128))
    cos_b = float(jnp.sum(dw_true * ghat2) /
                  (jnp.linalg.norm(dw_true) * jnp.linalg.norm(ghat2)))
    comm = (128 * (n_in + n_out) + n_in + n_out) / (n_in * n_out)
    return us, cos_t, f"cos_taps={cos_t:.3f} cos_AeqI={cos_b:.3f} comm={comm:.3f}"


def kernel_sketch_fused(key):
    """Fused Pallas sketch kernel vs oracle (interpret mode: correctness;
    derived = max abs err vs pure-jnp reference)."""
    from repro.kernels import ops, ref
    Pi = jax.random.normal(key, (128, 2048))
    A = jax.random.normal(jax.random.fold_in(key, 1), (2048, 512))
    (out, norms), us = _timed(lambda: ops.sketch_fused(Pi, A))
    out_r, n2 = ref.sketch_fused_ref(Pi, A)
    err = float(jnp.max(jnp.abs(out - out_r)))
    return us, err, "interpret-mode correctness"


def summary_backends(key):
    """SummaryEngine backend sweep on one (d, n) pair: per-backend wall time
    plus the worst cross-backend deviation from the reference summary
    (derived = that max parity error; the engine's contract says it is float
    reassociation only)."""
    d, n, k = 8192, 256, 128
    A, B = _gd_pair(key, d, n, corr=0.3)
    ref_s = core.build_summary(key, A, B, k, backend="reference")
    times, err = {}, 0.0
    for backend in ("reference", "scan", "pallas"):
        s, us = _timed(lambda b=backend: core.build_summary(
            key, A, B, k, backend=b, block=1024), reps=3)
        times[backend] = us
        err = max(err, float(jnp.max(jnp.abs(s.A_sketch - ref_s.A_sketch))))
    notes = " ".join(f"{b}_ms={t/1e3:.1f}" for b, t in times.items())
    return times["scan"], err, notes


def estimation_backends(key, *, smoke: bool = False) -> dict:
    """EstimationEngine sweep: every (method, backend) cell on ONE summary.

    Times ``estimate_product`` per cell and measures spectral error against
    the exact-entry two-pass baseline (LELA = biased sample + exact pass +
    WAltMin) — the record the acceptance gate reads: backend='jit' must beat
    the reference Python-loop WAltMin on wall time.

    Also sweeps the refined-reconstruction cells (``refinement/<method>/
    iters<i>/r<rank>``): spectral error vs rank x iters x method from a
    co-sketch-carrying summary of the same pair, so ``tools/bench_compare``
    tracks refinement accuracy (``spectral_error`` is gated lower-is-better)
    alongside wall time across commits.
    """
    if smoke:
        d, n, r, k, m, T = 1024, 64, 3, 64, 1200, 4
    else:
        d, n, r, k, m, T = 8192, 256, 5, 256, 6000, 8
    A, B = _gd_pair(key, d, n, corr=0.3)
    summary = core.build_summary(key, A, B, k, backend="reference")
    jax.block_until_ready(summary)

    # two-pass baseline: same sampler + WAltMin but exact entries
    base_f, base_us = _timed(
        lambda: core.lela(key, A, B, r=r, m=m, T=T), reps=1)
    base_err = _err(A, B, base_f)
    baseline = {"name": "lela_two_pass", "us_per_call": base_us,
                "spectral_error": base_err}

    cells = [
        ("rescaled_jl", "reference"), ("rescaled_jl", "jit"),
        ("rescaled_jl", "pallas"),
        ("lela_waltmin", "jit"),
        ("direct_svd", "reference"), ("direct_svd", "jit"),
    ]
    results = []
    for method, backend in cells:
        exact = (A, B) if method == "lela_waltmin" else None
        reps = 3 if backend == "jit" and not smoke else 1

        def run(method=method, backend=backend, exact=exact):
            out = core.estimate_product(
                key, summary, r, method=method, backend=backend, m=m, T=T,
                exact_pair=exact)
            return out.factors

        factors, us = _timed(run, reps=reps)
        results.append({
            "name": f"{method}/{backend}",
            "us_per_call": us,
            "spectral_error": _err(A, B, factors),
            "baseline_spectral_error": base_err,
        })

    # refinement sweep: spectral error vs rank x iters x method, from a
    # co-sketch-carrying summary of the same pair (s = 2r exact columns)
    s_width = 2 * r
    summary_c = core.build_summary(key, A, B, k, backend="reference",
                                   cosketch=s_width)
    jax.block_until_ready(summary_c)
    for rank in (max(2, r // 2), r):
        for ref_method, iters in (("tropp", 0), ("power", 1), ("power", 2)):
            spec = core.RefineSpec(iters=iters, method=ref_method)

            def run_refined(rank=rank, spec=spec):
                out = core.estimate_product(
                    key, summary_c, rank, method="power", backend="jit",
                    refine=spec)
                return out.factors

            factors, us = _timed(run_refined, reps=1)
            results.append({
                "name": f"refinement/{ref_method}/iters{iters}/r{rank}",
                "us_per_call": us,
                "spectral_error": _err(A, B, factors),
                "baseline_spectral_error": base_err,
            })

    times = {rec["name"]: rec["us_per_call"] for rec in results}
    return {
        "suite": "estimation_backends",
        "meta": _meta(smoke),
        "config": {"d": d, "n": n, "r": r, "k": k, "m": m, "T": T,
                   "cosketch": s_width,
                   "smoke": smoke, "backend_platform": jax.default_backend()},
        "baseline": baseline,
        "results": results,
        "jit_speedup_vs_reference":
            times["rescaled_jl/reference"] / times["rescaled_jl/jit"],
    }


def streaming_sweep(key, *, smoke: bool = False) -> dict:
    """Streaming ingestion sweep: chunk-size x ingestion-mode on one pair.

    Modes per method: ``one_shot/{reference,scan}`` (the in-memory baselines),
    ``sequential/chunk<c>`` (StreamingSummarizer, contiguous chunks),
    ``tree_merge/chunk<c>`` (independent per-chunk partial states reduced
    pairwise — the distributed/Spark shape), and ``shuffled_rows/chunk<c>``
    (arbitrary-order arrival via ``update_rows``). Every cell records wall
    time, ingested rows/s, and max deviation from the reference summary —
    the monoid contract says the deviation is float reassociation only.
    """
    if smoke:
        d, n, k = 4096, 64, 64
        chunks = (512, 1024)
    else:
        d, n, k = 32768, 256, 128
        chunks = (1024, 4096, 16384)
    A, B = _gd_pair(key, d, n, corr=0.3)
    results = []
    max_err = 0.0

    def record(name, us, summary, ref):
        nonlocal max_err
        err = float(jnp.max(jnp.abs(summary.A_sketch - ref.A_sketch)))
        max_err = max(max_err, err)
        results.append({"name": name, "us_per_call": us,
                        "rows_per_s": d / us * 1e6,
                        "max_err_vs_reference": err})

    refs = {}
    for method in ("gaussian", "srht"):
        ref, us = _timed(lambda m=method: core.build_summary(
            key, A, B, k, method=m, backend="reference"))
        refs[method] = ref
        record(f"{method}/one_shot/reference", us, ref, ref)
        s, us = _timed(lambda m=method: core.build_summary(
            key, A, B, k, method=m, backend="scan", block=chunks[-1]))
        record(f"{method}/one_shot/scan", us, s, ref)

        summ = core.StreamingSummarizer(k, method=method)
        for chunk in chunks:
            def sequential(chunk=chunk, summ=summ):
                st = summ.init(key, (d, n, n))
                for off in range(0, d, chunk):
                    st = summ.update(st, A[off:off + chunk],
                                     B[off:off + chunk], off)
                return summ.finalize(st)
            s, us = _timed(sequential)
            record(f"{method}/sequential/chunk{chunk}", us, s, ref)

            def tree(chunk=chunk, summ=summ):
                empty = summ.init(key, (d, n, n))
                parts = [summ.update(empty, A[off:off + chunk],
                                     B[off:off + chunk], off)
                         for off in range(0, d, chunk)]
                return summ.finalize(core.tree_merge(parts))
            s, us = _timed(tree)
            record(f"{method}/tree_merge/chunk{chunk}", us, s, ref)

    # arbitrary-order arrival (gaussian; same contract for srht)
    summ = core.StreamingSummarizer(k)
    ref = refs["gaussian"]
    perm = jax.random.permutation(key, d)
    chunk = chunks[0]

    def shuffled():
        st = summ.init(key, (d, n, n))
        for off in range(0, d, chunk):
            ids = perm[off:off + chunk]
            st = summ.update_rows(st, ids, A[ids], B[ids])
        return summ.finalize(st)
    s, us = _timed(shuffled)
    record(f"gaussian/shuffled_rows/chunk{chunk}", us, s, ref)

    results += _drift_cells(key, smoke=smoke)

    return {
        "suite": "streaming",
        "meta": _meta(smoke),
        "config": {"d": d, "n": n, "k": k, "chunks": list(chunks),
                   "smoke": smoke, "backend_platform": jax.default_backend()},
        "results": results,
        "max_parity_error": max_err,
    }


def _drift_cells(key, *, smoke: bool) -> list:
    """Drift cells: piecewise-stationary spectrum flip, three summary
    policies.

    Five epochs of rows; epochs 0-2 carry ``A^T B = M1`` (top subspace U1,
    8x mass), epochs 3-4 flip to ``M2`` (U2 ⟂ U1, 4x mass). Each policy
    ingests the same stream — vanilla (cumulative), decayed (gamma=0.5, one
    tick per epoch), windowed (2-epoch ring, one slide per epoch) — and
    ``tracking_error`` is the spectral residual of the final estimate's
    top-q left subspace against the CURRENT phase's U2 (lower is better;
    gated by tools/bench_compare.py). The monoid contract says vanilla
    stays pinned to the heavier U1 while the forgetting policies track the
    flip — the drift claim of docs/streaming.md, measured.
    """
    if smoke:
        d_e, n1, n2, q, k = 512, 24, 16, 4, 96
    else:
        d_e, n1, n2, q, k = 2048, 48, 32, 6, 192
    n_phase1, n_phase2 = 3, 2
    epochs = n_phase1 + n_phase2

    kU, kV1, kV2, kW = jax.random.split(key, 4)
    U_all, _ = jnp.linalg.qr(jax.random.normal(kU, (n1, 2 * q)))
    U1, U2 = U_all[:, :q], U_all[:, q:]
    V1, _ = jnp.linalg.qr(jax.random.normal(kV1, (n2, q)))
    V2, _ = jnp.linalg.qr(jax.random.normal(kV2, (n2, q)))
    M = {1: 8.0 * U1 @ V1.T, 2: 4.0 * U2 @ V2.T}
    stream = []
    for e in range(epochs):
        W, _ = jnp.linalg.qr(jax.random.normal(
            jax.random.fold_in(kW, e), (d_e, n1)))
        phase = 1 if e < n_phase1 else 2
        stream.append((W, W @ M[phase]))

    def tracking_error(summary):
        E = summary.A_sketch.T @ summary.B_sketch
        Uh = jnp.linalg.svd(E, full_matrices=False)[0][:, :q]
        return float(jnp.linalg.norm(U2 - Uh @ (Uh.T @ U2), 2))

    def vanilla():
        summ = core.StreamingSummarizer(k)
        st = summ.init(key, (epochs * d_e, n1, n2))
        for e, (A_e, B_e) in enumerate(stream):
            st = summ.update(st, A_e, B_e, e * d_e)
        return summ.finalize(st)

    def decayed():
        summ = core.StreamingSummarizer(k, decay=0.5)
        st = summ.init(key, (epochs * d_e, n1, n2))
        for e, (A_e, B_e) in enumerate(stream):
            if e:
                st = summ.advance(st)
            st = summ.update(st, A_e, B_e, e * d_e)
        return summ.finalize(st)

    def windowed():
        win = core.WindowedSummarizer(k, 2)
        w = win.init(key, (d_e, n1, n2))
        for e, (A_e, B_e) in enumerate(stream):
            if e:
                w = win.slide(w)
            w = win.update(w, A_e, B_e, 0)
        return win.finalize(w)

    cells = []
    for name, fn in (("drift/vanilla", vanilla),
                     ("drift/decay0.5", decayed),
                     ("drift/window2", windowed)):
        s, us = _timed(fn)
        cells.append({"name": name, "us_per_call": us,
                      "rows_per_s": epochs * d_e / us * 1e6,
                      "tracking_error": tracking_error(s)})
    return cells


def error_sweep(key, *, smoke: bool = False) -> dict:
    """ErrorEngine sweep: estimated vs true residual across rank x probes.

    One known-spectrum pair; for every probe count p the summary is rebuilt
    (probes ride the same single pass) and for every rank r the full
    ``estimate_product(..., with_error=True)`` pipeline runs — each cell
    records the a-posteriori Frobenius estimate, the exact residual
    (materialized here for validation only), their ratio, and the CI hit.
    The final records sweep ``adaptive_rank`` tolerances: chosen rank +
    whether the estimate met the gate. The acceptance gate reads the
    ratios: every cell must sit within 2x of the truth.
    """
    if smoke:
        d, n, k, T = 1024, 48, 64, 3
        ranks, probe_counts, tols = (2, 4, 8), (8, 32), (0.5, 0.2)
    else:
        d, n, k, T = 8192, 192, 256, 6
        ranks, probe_counts, tols = (2, 5, 10, 20), (4, 16, 64), (0.5, 0.2)
    A, B = _gd_pair(key, d, n, corr=0.3, decay=0.8)
    M = A.T @ B
    m_frob = float(jnp.linalg.norm(M))
    results = []
    for p in probe_counts:
        summary = core.build_summary(key, A, B, k, backend="scan", probes=p)
        jax.block_until_ready(summary)
        for r in ranks:
            def run(r=r, summary=summary):
                return core.estimate_product(
                    jax.random.fold_in(key, 1), summary, r,
                    m=int(6 * n * r * math.log(n)), T=T, with_error=True)
            est, us = _timed(run)
            true = float(jnp.linalg.norm(M - est.factors.dense()))
            results.append({
                "name": f"r{r}/p{p}",
                "r": r, "probes": p, "us_per_call": us,
                "frob_true": true,
                "frob_est": float(est.error.frob_est),
                "ratio_est_over_true": float(est.error.frob_est) / true,
                "rel_est": float(est.error.rel_est),
                "rel_true": true / m_frob,
                "ci_covers_true": bool(float(est.error.frob_lo) <= true
                                       <= float(est.error.frob_hi)),
            })
    adaptive = []
    summary = core.build_summary(key, A, B, k, backend="scan",
                                 probes=probe_counts[-1])
    for tol in tols:
        def run(tol=tol):
            return core.adaptive_rank(summary, tol=tol, r_max=max(ranks))
        res, us = _timed(run)
        true = float(jnp.linalg.norm(M - res.factors.dense())) / m_frob
        adaptive.append({"tol": tol, "r": res.r, "us_per_call": us,
                         "rel_est": float(res.error.rel_est),
                         "rel_true": true,
                         "met": bool(res.error.rel_est <= tol)})
    ratios = [rec["ratio_est_over_true"] for rec in results]
    return {
        "suite": "error",
        "meta": _meta(smoke),
        "config": {"d": d, "n": n, "k": k, "T": T, "ranks": list(ranks),
                   "probe_counts": list(probe_counts), "smoke": smoke,
                   "backend_platform": jax.default_backend()},
        "results": results,
        "adaptive_rank": adaptive,
        "worst_ratio": max(max(ratios), 1.0 / min(ratios)),
    }


def serving_sweep(key, *, smoke: bool = False) -> dict:
    """Serving sweep: trace counts + per-request latency, cold vs warm plans.

    One shape bucket of L requests per plan, served by a ``SketchService``
    on a fresh ``PipelineEngine``. The *cold* flush pays the plan's traces
    (compilation); every *warm* flush must be pure cache hits — zero new
    traces, one fused dispatch per bucket. Cells cover the three serving
    modes: fixed rank, fixed rank + attached error estimate, and the
    quality-gated ``r='auto'`` single-sweep path. The record the acceptance
    gate reads: ``traces_warm`` must be 0 in every cell, and
    ``cold_over_warm`` shows what compile-once buys per request.
    """
    from repro.core.pipeline import PipelineEngine
    from repro.serve.engine import SketchService
    if smoke:
        d, n, k, L, probes, m, warm_reps = 512, 32, 64, 4, 8, 800, 3
    else:
        d, n, k, L, probes, m, warm_reps = 4096, 128, 128, 16, 16, 6000, 10
    pairs = [_gd_pair(jax.random.fold_in(key, i), d, n, corr=0.3)
             for i in range(L)]
    plans = [
        ("fixed_r", dict(r=5, m=m, T=4)),
        ("fixed_r_with_error", dict(r=5, m=m, T=4, with_error=True)),
        ("auto_rank", dict(r="auto", tol=0.5, m=m, T=4)),
    ]
    results = []
    for name, kw in plans:
        engine = PipelineEngine()
        svc = SketchService(k=k, backend="scan", block=1024, probes=probes,
                            engine=engine)

        def flush_once(kw=kw, svc=svc):
            for i, (A, B) in enumerate(pairs):
                svc.submit(jax.random.fold_in(key, i), A, B)
            out = svc.flush_factors(**kw)
            jax.block_until_ready([v.factors.U for v in out.values()])
            return out

        t0 = time.perf_counter()
        flush_once()
        cold_us = (time.perf_counter() - t0) * 1e6
        traces_cold = engine.stats.traces
        t0 = time.perf_counter()
        for _ in range(warm_reps):
            flush_once()
        warm_us = (time.perf_counter() - t0) / warm_reps * 1e6
        results.append({
            "name": name,
            "requests_per_flush": L,
            "cold_us_per_request": cold_us / L,
            "warm_us_per_request": warm_us / L,
            "cold_over_warm": cold_us / warm_us,
            "traces_cold": traces_cold,
            "traces_warm": engine.stats.traces - traces_cold,
            "est_dispatches_per_flush":
                engine.stats.est_dispatches / (warm_reps + 1),
            "cache": {"hits": engine.stats.hits,
                      "misses": engine.stats.misses,
                      "evictions": engine.stats.evictions},
        })
    return {
        "suite": "serving",
        "meta": _meta(smoke),
        "config": {"d": d, "n": n, "k": k, "L": L, "probes": probes, "m": m,
                   "warm_reps": warm_reps, "smoke": smoke,
                   "backend_platform": jax.default_backend()},
        "results": results,
        "max_traces_warm": max(rec["traces_warm"] for rec in results),
    }


def traffic_sweep(*, smoke: bool = False) -> dict:
    """Measured-throughput traffic cells through the ServingLoop.

    Four regimes of the same continuously-batched stack (see
    ``repro.serve.traffic``): a single-shape steady state, a mixed-shape
    mix (three buckets batching independently), a multi-tenant mix (many
    tenants, one shared warm cache), and an overload cell (4x the
    calibrated rate into a bounded queue — the backpressure/shedding
    path). The records the acceptance gate reads: steady-state cells must
    show ``occupancy`` > 1 request/dispatch with ``traces_steady`` == 0.
    """
    from repro.serve.traffic import TrafficConfig, run_traffic
    if smoke:
        base = dict(n_requests=48, k=32, m=400, T=2, max_batch=4,
                    target_occupancy=3.0, pairs_per_shape=2)
        s1, s2, s3 = (256, 16, 12), (256, 24, 16), (384, 16, 16)
    else:
        base = dict(n_requests=256, k=64, m=1200, T=3, max_batch=8,
                    target_occupancy=4.0, pairs_per_shape=4)
        s1, s2, s3 = (2048, 64, 48), (2048, 96, 64), (3072, 64, 64)
    cells = [
        TrafficConfig(name="steady_single_shape", shapes=(s1,), **base),
        TrafficConfig(name="mixed_shapes", shapes=(s1, s2, s3), **base),
        TrafficConfig(name="multi_tenant", shapes=(s1,),
                      tenants=("acme", "globex", 7, None), **base),
        TrafficConfig(name="overload_shed", shapes=(s1,), rate_x=4.0,
                      max_queue=2 * base["max_batch"], **base),
    ]
    results = [run_traffic(cfg) for cfg in cells]
    steady = [rec for rec in results if rec["name"] != "overload_shed"]
    return {
        "suite": "traffic",
        "meta": _meta(smoke),
        "config": {"smoke": smoke,
                   "backend_platform": jax.default_backend()},
        "results": results,
        "min_steady_occupancy": min(rec["occupancy"] for rec in steady),
        "max_traces_steady": max(rec["traces_steady"] for rec in results),
        "overload_shed_rate": next(
            rec["shed_rate"] for rec in results
            if rec["name"] == "overload_shed"),
    }


def kernel_sweep(key, *, smoke: bool = False) -> dict:
    """Kernel-perf sweep: every tunable Pallas kernel x shape x config cell.

    For each kernel and canonical shape the autotuner's roofline ranking
    head (top-N candidates under the VMEM budget) plus the frozen default
    config are wall-timed through ``repro.kernels.ops`` — the same entry
    points production traffic uses — and each cell records ``us_per_call``,
    ``achieved_gbps`` (the cost model's HBM-byte count over measured time),
    and the static roofline terms. On interpret-mode CPU the absolute
    times are interpreter-relative; the ranking and the modeled terms are
    the stable signal ``tools/bench_compare.py`` tracks.
    """
    del key                      # measure_config seeds its own inputs
    from repro.kernels import tuning

    if smoke:
        shapes = {
            "sketch_fused": [(64, 512, 256)],
            "blocked_fwht": [(512, 256)],
            "sampled_dot": [(256, 256, 64, 512)],
            "flash_attention": [(4, 256, 64)],
        }
        top_n, reps = 2, 1
    else:
        shapes = {
            "sketch_fused": [(128, 4096, 512), (256, 8192, 512)],
            "blocked_fwht": [(2048, 512)],
            "sampled_dot": [(1024, 1024, 128, 4096)],
            "flash_attention": [(8, 1024, 128)],
        }
        top_n, reps = 3, 2
    results = []
    for kernel, shape_list in shapes.items():
        default = tuning.DEFAULTS[kernel]
        for shape in shape_list:
            ranked = tuning.rank_candidates(kernel, shape)
            cfgs = list(ranked[:top_n])
            if default not in cfgs:
                cfgs.append(default)
            shape_tag = "x".join(str(s) for s in shape)
            for cfg in cfgs:
                cost = tuning.roofline_cost(cfg, shape)
                us = tuning.measure_config(cfg, shape, reps=reps)
                results.append({
                    "name": f"{kernel}/{shape_tag}/{cfg.tag()}",
                    "kernel": kernel,
                    "shape": list(shape),
                    "config": cfg.tag(),
                    "static_rank": (ranked.index(cfg)
                                    if cfg in ranked else None),
                    "is_default": cfg == default,
                    "us_per_call": us,
                    "achieved_gbps": tuning.achieved_gbps(cfg, shape, us),
                    "modeled": cost.as_dict(),
                })
    return {
        "suite": "kernels",
        "meta": _meta(smoke),
        "config": {"shapes": {k: [list(s) for s in v]
                              for k, v in shapes.items()},
                   "top_n": top_n, "reps": reps, "smoke": smoke,
                   "vmem_budget_bytes": tuning.VMEM_BUDGET_BYTES,
                   "backend_platform": jax.default_backend()},
        "results": results,
    }


def ingest_sweep(key, *, smoke: bool = False) -> dict:
    """Multi-host ingest sweep: chunk pipelining x wire precision.

    Overlap cells drive the same chunk stream through
    ``StreamingSummarizer.ingest`` serial (``prefetch=0``: block after every
    fused update, then fetch+stage the next chunk) vs double-buffered
    (``prefetch=2``: chunk c+1 fetched and staged host->device while chunk c
    computes). The fetch models per-chunk arrival latency (``fetch_ms`` in
    the config — the storage/decode stall a real ingest pays per chunk);
    serial eats it on the critical path, double-buffering hides it under
    the fused update. Cells record ``chunks_per_sec``, ``rows_per_s``, and
    ``achieved_gbps`` (the A+B bytes the pass ingests end-to-end over wall
    time), timed best-of-``reps`` (pipelining is latency hiding, so the
    floor is the signal — means smear scheduler noise in). Wire cells
    compress the end-of-pass state at every ``WireSpec`` precision and
    record ``wire_bytes_per_state``, the probe-measured ``wire_error``, and
    the host-side ``wire_pack``+``wire_unpack`` round-trip time — the cost
    of putting one state on the inter-host wire. The gate cell runs
    ``choose_wire_spec`` at ``tol`` and records what the probe gate picked.
    """
    import numpy as np
    from repro.core import streaming

    if smoke:
        d, n, k, chunk, reps = 16384, 128, 128, 512, 5
    else:
        d, n, k, chunk, reps = 65536, 256, 128, 2048, 5
    probes, cosketch, tol, fetch_ms = 8, 8, 0.05, 2.0
    A, B = _gd_pair(key, d, n, corr=0.3)
    A_host, B_host = np.asarray(A), np.asarray(B)
    del A, B
    summ = core.StreamingSummarizer(k, probes=probes, cosketch=cosketch)
    n_chunks = -(-d // chunk)
    pass_bytes = A_host.nbytes + B_host.nbytes
    results = []

    def one_pass(prefetch):
        st = summ.init(key, (d, n, n))

        def chunks():
            for off in range(0, d, chunk):
                time.sleep(fetch_ms / 1e3)       # modeled arrival latency
                yield A_host[off:off + chunk], B_host[off:off + chunk]
        st = summ.ingest(st, chunks(), prefetch=prefetch)
        jax.block_until_ready(st.A_acc)
        return st

    state = None
    for prefetch in (0, 2):
        st = one_pass(prefetch)                  # warm the executables
        us = math.inf
        for _ in range(reps):
            t0 = time.perf_counter()
            st = one_pass(prefetch)
            us = min(us, (time.perf_counter() - t0) * 1e6)
        state = st
        results.append({
            "name": f"ingest/prefetch{prefetch}",
            "prefetch": prefetch,
            "us_per_call": us,
            "chunks_per_sec": n_chunks / us * 1e6,
            "rows_per_s": d / us * 1e6,
            "achieved_gbps": pass_bytes / (us / 1e6) / 1e9,
        })

    f32_bytes = None
    for spec in streaming.WIRE_DTYPES:
        comp = streaming.compress_state(state, spec)
        nbytes = streaming.wire_bytes(comp)
        if spec == "f32":
            f32_bytes = nbytes
        _, us = _timed(
            lambda c=comp: streaming.wire_unpack(streaming.wire_pack(c)))
        results.append({
            "name": f"wire/{spec}",
            "us_per_call": us,
            "wire_bytes_per_state": nbytes,
            "bytes_ratio_vs_f32": f32_bytes / nbytes,
            "wire_error": float(streaming.wire_error(state, spec)),
        })

    gate_spec, gate_err = streaming.choose_wire_spec(state, tol)
    results.append({
        "name": f"wire/gate_tol{tol}",
        "chosen_spec": gate_spec.sketch,
        "wire_error": float(gate_err),
        "wire_bytes_per_state": streaming.wire_bytes(
            streaming.compress_state(state, gate_spec)),
    })

    return {
        "suite": "ingest",
        "meta": _meta(smoke),
        "config": {"d": d, "n": n, "k": k, "chunk": chunk, "reps": reps,
                   "probes": probes, "cosketch": cosketch, "tol": tol,
                   "fetch_ms": fetch_ms, "smoke": smoke,
                   "backend_platform": jax.default_backend()},
        "results": results,
    }


BENCHES = [
    ("fig2a_rescaled_jl", fig2a_rescaled_jl),
    ("fig2b_cone", fig2b_cone),
    ("fig3a_runtime", fig3a_runtime),
    ("fig3b_error_vs_k", fig3b_error_vs_k),
    ("table1_errors", table1_errors),
    ("fig4a_phase", fig4a_phase),
    ("fig4b_cone_full", fig4b_cone_full),
    ("fig4c_orthogonal", fig4c_orthogonal),
    ("grad_compression", grad_compression),
    ("kernel_sketch_fused", kernel_sketch_fused),
    ("summary_backends", summary_backends),
]


def run_paper_suite(key) -> int:
    """Run every paper row; a row that raises prints an ``ERROR`` row and
    the rest still run. Returns the number of rows that errored."""
    print("name,us_per_call,derived,notes")
    errors = 0
    for name, fn in BENCHES:
        try:
            us, derived, notes = fn(jax.random.fold_in(
                key, zlib.crc32(name.encode()) % 2**31))
            print(f"{name},{us:.0f},{derived:.4f},{notes}", flush=True)
        except Exception as e:   # noqa: BLE001
            errors += 1
            print(f"{name},nan,nan,ERROR {e}", flush=True)
    return errors


def run_estimation_suite(key, out_path: str, smoke: bool) -> None:
    report = estimation_backends(jax.random.fold_in(
        key, zlib.crc32(b"estimation_backends") % 2**31), smoke=smoke)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {out_path}", flush=True)
    print("name,us_per_call,spectral_error,baseline_spectral_error")
    for rec in report["results"]:
        print(f"{rec['name']},{rec['us_per_call']:.0f},"
              f"{rec['spectral_error']:.4f},"
              f"{rec['baseline_spectral_error']:.4f}", flush=True)
    print(f"jit_speedup_vs_reference,"
          f"{report['jit_speedup_vs_reference']:.2f}x", flush=True)


def run_error_suite(key, out_path: str, smoke: bool) -> None:
    report = error_sweep(jax.random.fold_in(
        key, zlib.crc32(b"error") % 2**31), smoke=smoke)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {out_path}", flush=True)
    print("name,us_per_call,frob_est,frob_true,ratio,ci_covers_true")
    for rec in report["results"]:
        print(f"{rec['name']},{rec['us_per_call']:.0f},"
              f"{rec['frob_est']:.4f},{rec['frob_true']:.4f},"
              f"{rec['ratio_est_over_true']:.3f},{rec['ci_covers_true']}",
              flush=True)
    for rec in report["adaptive_rank"]:
        print(f"adaptive tol={rec['tol']},r={rec['r']},"
              f"rel_est={rec['rel_est']:.3f},rel_true={rec['rel_true']:.3f},"
              f"met={rec['met']}", flush=True)
    print(f"worst_ratio,{report['worst_ratio']:.3f}", flush=True)


def run_serving_suite(key, out_path: str, smoke: bool) -> None:
    report = serving_sweep(jax.random.fold_in(
        key, zlib.crc32(b"serving") % 2**31), smoke=smoke)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {out_path}", flush=True)
    print("name,cold_us_per_req,warm_us_per_req,cold_over_warm,"
          "traces_cold,traces_warm")
    for rec in report["results"]:
        print(f"{rec['name']},{rec['cold_us_per_request']:.0f},"
              f"{rec['warm_us_per_request']:.0f},"
              f"{rec['cold_over_warm']:.2f},"
              f"{rec['traces_cold']},{rec['traces_warm']}", flush=True)
    print(f"max_traces_warm,{report['max_traces_warm']}", flush=True)


def run_traffic_suite(out_path: str, smoke: bool) -> None:
    """Run the traffic sweep and MERGE it into the serving artifact: the
    serving sweep (cold/warm plan latency) and the traffic sweep (measured
    throughput) are two views of the same stack and share one
    ``BENCH_serving.json``, under the ``"traffic"`` key."""
    report = traffic_sweep(smoke=smoke)
    merged = {}
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                merged = json.load(f)
        except (OSError, json.JSONDecodeError):
            merged = {}
    if not merged:
        merged = {"suite": "serving", "meta": report["meta"]}
    merged["traffic"] = report
    with open(out_path, "w") as f:
        json.dump(merged, f, indent=2)
    print(f"wrote {out_path} (traffic)", flush=True)
    print("name,offered_rps,measured_rps,p50_ms,p99_ms,occupancy,"
          "shed_rate,traces_steady")
    for rec in report["results"]:
        print(f"{rec['name']},{rec['offered_rps']:.1f},"
              f"{rec['measured_rps']:.1f},{rec['p50_ms']:.1f},"
              f"{rec['p99_ms']:.1f},{rec['occupancy']:.2f},"
              f"{rec['shed_rate']:.3f},{rec['traces_steady']}", flush=True)
    print(f"min_steady_occupancy,{report['min_steady_occupancy']:.2f}",
          flush=True)
    print(f"max_traces_steady,{report['max_traces_steady']}", flush=True)


def run_streaming_suite(key, out_path: str, smoke: bool) -> None:
    report = streaming_sweep(jax.random.fold_in(
        key, zlib.crc32(b"streaming") % 2**31), smoke=smoke)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {out_path}", flush=True)
    print("name,us_per_call,rows_per_s,max_err_vs_reference|tracking_error")
    for rec in report["results"]:
        last = rec.get("max_err_vs_reference", rec.get("tracking_error"))
        print(f"{rec['name']},{rec['us_per_call']:.0f},"
              f"{rec['rows_per_s']:.0f},{last:.2e}", flush=True)
    print(f"max_parity_error,{report['max_parity_error']:.2e}", flush=True)


def run_kernels_suite(key, out_path: str, smoke: bool) -> None:
    report = kernel_sweep(jax.random.fold_in(
        key, zlib.crc32(b"kernels") % 2**31), smoke=smoke)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {out_path}", flush=True)
    print("name,us_per_call,achieved_gbps,static_rank,is_default")
    for rec in report["results"]:
        print(f"{rec['name']},{rec['us_per_call']:.0f},"
              f"{rec['achieved_gbps']:.3f},{rec['static_rank']},"
              f"{rec['is_default']}", flush=True)


def run_ingest_suite(key, out_path: str, smoke: bool) -> None:
    report = ingest_sweep(jax.random.fold_in(
        key, zlib.crc32(b"ingest") % 2**31), smoke=smoke)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {out_path}", flush=True)
    print("name,chunks_per_sec|wire_bytes_per_state,achieved_gbps|wire_error")
    for rec in report["results"]:
        if "chunks_per_sec" in rec:
            print(f"{rec['name']},{rec['chunks_per_sec']:.1f},"
                  f"{rec['achieved_gbps']:.3f}", flush=True)
        else:
            print(f"{rec['name']},{rec['wire_bytes_per_state']},"
                  f"{rec.get('wire_error', 0.0):.2e}", flush=True)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--suite",
                   choices=("paper", "estimation", "streaming", "error",
                            "serving", "traffic", "kernels", "ingest",
                            "all"),
                   default="paper")
    p.add_argument("--smoke", action="store_true",
                   help="reduced sizes for CI smoke runs")
    p.add_argument("--out", default="BENCH_estimation.json",
                   help="JSON artifact path for the estimation suite")
    p.add_argument("--out-streaming", default="BENCH_streaming.json",
                   help="JSON artifact path for the streaming suite")
    p.add_argument("--out-error", default="BENCH_error.json",
                   help="JSON artifact path for the error suite")
    p.add_argument("--out-serving", default="BENCH_serving.json",
                   help="JSON artifact path for the serving suite")
    p.add_argument("--out-kernels", default="BENCH_kernels.json",
                   help="JSON artifact path for the kernel-perf suite")
    p.add_argument("--out-ingest", default="BENCH_ingest.json",
                   help="JSON artifact path for the multi-host ingest suite")
    args = p.parse_args()
    enable_compile_cache()
    key = jax.random.PRNGKey(0)
    errors = 0
    if args.suite in ("paper", "all"):
        errors += run_paper_suite(key)
    if args.suite in ("estimation", "all"):
        run_estimation_suite(key, args.out, args.smoke)
    if args.suite in ("streaming", "all"):
        run_streaming_suite(key, args.out_streaming, args.smoke)
    if args.suite in ("error", "all"):
        run_error_suite(key, args.out_error, args.smoke)
    if args.suite in ("serving", "all"):
        run_serving_suite(key, args.out_serving, args.smoke)
    if args.suite in ("traffic", "all"):
        run_traffic_suite(args.out_serving, args.smoke)
    if args.suite in ("kernels", "all"):
        run_kernels_suite(key, args.out_kernels, args.smoke)
    if args.suite in ("ingest", "all"):
        run_ingest_suite(key, args.out_ingest, args.smoke)
    if errors:
        raise SystemExit(f"{errors} benchmark row(s) errored")


if __name__ == "__main__":
    main()
