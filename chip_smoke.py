#!/usr/bin/env python3
"""Chip smoke: the single-pass ingest -> estimate -> serve path on a TPU.

    python chip_smoke.py              # one chip, four phases
    python chip_smoke.py --chips 4    # the cross-chip ingest paths only

One chip, in one process:

1. ingest   ``StreamingSummarizer(k=512, probes=16).ingest`` over
            d = 2^19 rows of an n1 = n2 = 4096 pair, in chunks of 16,384
            rows generated on the device (16 GiB streamed in f32), while
            the exact ``A^T B`` accumulates in f32 beside it;
2. estimate ``estimate_product(method='rescaled_jl', r=10, with_error=True)``
            on the ``jit`` and the compiled ``pallas`` backend, against the
            exact top-10 of ``A^T B``;
3. pallas   ``build_summary(backend='pallas')`` against ``'reference'`` and
   summary  ``'scan'`` on one chunk, gaussian (``sketch_fused``) and srht
            (``blocked_fwht``);
4. serve    8 requests from two tenants through a ``ServingLoop``
            (``pad='pow2'``, ``max_batch=4``) on a pallas-sketch plan, plus
            one quality-gated ``SketchService.flush_factors(r='auto')``.

``--chips 4`` runs only the row-sharded pass (``backend='distributed'`` and
``distributed_streaming_update`` on a flat 4-chip mesh) and the
hierarchical ``(host, device)`` reduce on ``dist.multihost.host_mesh``, and
compares both with the one-chip summary of the same rows.

Inputs are the paper's correlated pair (``tests/conftest.planted_pair``):
``A = G diag(1/i^DECAY)``, ``B = A + SIGMA * G' diag(1/i^DECAY)``, made on the
device from ``--seed``. Every check that fails raises, and the process exits
non-zero. The script never falls back to the CPU: without a TPU it exits
non-zero before any phase. Earlier lines report sizes, errors against their
bounds, wall times (host clock, compilation included) and compile seconds.
The last line of standard output is the JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import NamedTuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: the generator's column decay and correlation noise (B = A + SIGMA * ...)
DECAY, SIGMA = 0.5, 0.1

#: ||M - U V^T||_2 / ||M - M_r||_2 bound for the ingest-size estimates.
#: A CPU run of the same generator measured 1.62 at n = 2048, d = 2^16,
#: k = 512; the bound leaves room for the wider n = 4096. A zero or garbage
#: estimate scores ||M||_2 / ||M - M_10||_2, about 11, or more.
RATIO_BOUND = 3.0

#: the same ratio's bound at the serve size (n = 1024, d = 16384, k = 256)
SERVE_RATIO_BOUND = 3.0

#: confidence of the probe interval the true residual must fall in: the
#: checks are deterministic for a seed, and at 95% one seed in twenty
#: would miss on an honest estimate
CI_CONFIDENCE = 0.999

#: relative Frobenius gap allowed between two f32 summaries of the same rows
#: that sum in a different order (backends, shardings); a bf16-rounded
#: product would miss it by two orders of magnitude
SUMMARY_RTOL = 1e-4

#: relative Frobenius gap between a served (vmapped, batched) estimate and
#: the same request estimated alone
SERVE_RTOL = 1e-3

#: the quality gate's relative-Frobenius tolerance and rank cap for the
#: flush_factors request. At k = 256 the rescaled-JL noise floor of the
#: relative Frobenius error is about 0.37 at the serve size, so the gate
#: is set above it; the cap bounds the escalation (the default sample
#: budget, and WAltMin's (m, r, r) normal equations, grow with r)
GATE_TOL, GATE_R_MAX = 0.5, 16


class Sizes(NamedTuple):
    n: int = 4096              # n1 = n2, the ingest widths
    k: int = 512
    d: int = 2 ** 19           # rows streamed
    chunk: int = 16384
    probes: int = 16
    r: int = 10
    serve_d: int = 16384
    serve_n: int = 1024
    serve_k: int = 256
    serve_requests: int = 8
    dist_slabs: int = 8        # --chips 4: slabs of `chunk` rows


def check(ok, what: str) -> None:
    """Raise (and so fail the run) unless ``ok``."""
    if not bool(ok):
        raise RuntimeError(f"check failed: {what}")


def report(**fields) -> None:
    print(json.dumps(fields, default=float), flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events; ``lap()`` returns the seconds since the last lap."""

    def __init__(self):
        import jax
        self.total = 0.0
        self._mark = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.total += secs

    def lap(self) -> float:
        out, self._mark = self.total - self._mark, self.total
        return out


# ---------------------------------------------------------------------------
# Data, references
# ---------------------------------------------------------------------------

def planted_chunk(key, rows: int, n: int):
    """One row block of the correlated pair, made on the device."""
    import jax
    import jax.numpy as jnp
    k_a, k_b = jax.random.split(key)
    scale = 1.0 / jnp.arange(1.0, n + 1.0) ** DECAY
    A = jax.random.normal(k_a, (rows, n)) * scale
    B = A + SIGMA * jax.random.normal(k_b, (rows, n)) * scale
    return A, B


def _jit_chunk():
    import jax
    return jax.jit(planted_chunk, static_argnums=(1, 2))


def exact_product(A, B):
    import jax
    import jax.numpy as jnp
    return jnp.dot(A.T, B, precision=jax.lax.Precision.HIGHEST)


def rel_frob(got, want) -> float:
    """||got - want||_F / ||want||_F in float64 on the host (the two may
    live on different devices)."""
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def summary_gap(got, want) -> float:
    """Worst relative Frobenius gap over the summary's carried blocks."""
    pairs = [(got.A_sketch, want.A_sketch), (got.B_sketch, want.B_sketch),
             (got.norm_A, want.norm_A), (got.norm_B, want.norm_B)]
    if want.probes is not None:
        pairs.append((got.probes, want.probes))
    return max(rel_frob(g, w) for g, w in pairs)


def singular_values(M):
    """All singular values of the f32 reference, in float64 on the host
    (a TPU SVD at n = 4096 takes about ten minutes to compile)."""
    import numpy as np
    return np.linalg.svd(np.asarray(M, np.float64), compute_uv=False)


def factor_errors(M, factors, r: int, s_M=None):
    """(||M - UV^T||_2 / ||M - M_r||_2, ||M - UV^T||_F) in float64."""
    import numpy as np
    if s_M is None:
        s_M = singular_values(M)
    f64 = lambda x: np.asarray(x, np.float64)
    R = f64(M) - f64(factors.U) @ f64(factors.V).T
    spec = np.linalg.svd(R, compute_uv=False)[0]
    return float(spec / s_M[r]), float(np.linalg.norm(R))


def check_estimate(name, M, summary, est, r, bound, s_M=None,
                   ci: bool = True) -> None:
    """The estimate's spectral ratio against ``bound``; with ``ci``, also
    the true Frobenius residual against the probe interval at
    ``CI_CONFIDENCE`` (the 95% one the estimate carries is reported)."""
    from repro.core.error_engine import estimate_error
    ratio, frob = factor_errors(M, est.factors, r, s_M)
    err = est.error
    wide = estimate_error(summary, est.factors, confidence=CI_CONFIDENCE)
    lo, hi = float(wide.frob_lo), float(wide.frob_hi)
    report(check=name, spectral_ratio=ratio, bound=bound, frob_true=frob,
           frob_est=float(err.frob_est),
           frob_ci95=[float(err.frob_lo), float(err.frob_hi)],
           frob_ci=[lo, hi], ci_confidence=CI_CONFIDENCE)
    check(ratio <= bound, f"{name}: spectral ratio {ratio} > {bound}")
    if ci:
        check(lo <= frob <= hi, f"{name}: true residual {frob} outside the "
              f"{CI_CONFIDENCE} probe CI [{lo}, {hi}]")


def lowers_to_mosaic(fn, *args) -> bool:
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------

def phase_ingest(sz: Sizes, key):
    import jax
    import jax.numpy as jnp
    from repro.core.streaming import StreamingSummarizer
    gen = _jit_chunk()
    acc = jax.jit(lambda M, A, B: M + exact_product(A, B), donate_argnums=0)
    summ = StreamingSummarizer(k=sz.k, probes=sz.probes)
    state = summ.init(jax.random.fold_in(key, 0), (sz.d, sz.n, sz.n))
    M = jnp.zeros((sz.n, sz.n), jnp.float32)
    k_data = jax.random.fold_in(key, 1)

    def chunks():
        nonlocal M
        for c in range(sz.d // sz.chunk):
            A, B = gen(jax.random.fold_in(k_data, c), sz.chunk, sz.n)
            M = acc(M, A, B)
            yield A, B

    t0 = time.perf_counter()
    state = summ.ingest(state, chunks(), prefetch=2)
    summary = summ.finalize(state)
    jax.block_until_ready((summary, M))
    wall = time.perf_counter() - t0
    report(phase="ingest", d=sz.d, n1=sz.n, n2=sz.n, k=sz.k, chunk=sz.chunk,
           probes=sz.probes, rows_seen=int(state.rows_seen),
           gib_streamed=2 * sz.d * sz.n * 4 / 2 ** 30, wall_s=wall)
    check(int(state.rows_seen) == sz.d, "ingest saw every row")
    check(summary.A_sketch.shape == (sz.k, sz.n)
          and summary.probes.shape == (sz.n, sz.probes), "summary shapes")
    check(all(bool(jnp.all(jnp.isfinite(x))) for x in
              (summary.A_sketch, summary.B_sketch, summary.probes, M)),
          "summary and reference are finite")
    return summary, M


def phase_estimate(sz: Sizes, key, summary, M) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core.estimation_engine import estimate_product
    k_est = jax.random.fold_in(key, 2)
    s_M = singular_values(M)
    report(phase="estimate", top_singular=float(s_M[0]),
           optimal_residual=float(s_M[sz.r]), r=sz.r)
    factors = {}
    for backend in ("jit", "pallas"):
        t0 = time.perf_counter()
        est = estimate_product(k_est, summary, sz.r, method="rescaled_jl",
                               backend=backend, with_error=True)
        jax.block_until_ready(est)
        report(phase="estimate", backend=backend, m=int(est.samples.m),
               wall_s=time.perf_counter() - t0)
        check_estimate(f"estimate[{backend}]", M, summary, est, sz.r,
                       RATIO_BOUND, s_M)
        factors[backend] = jnp.dot(est.factors.U, est.factors.V.T,
                                   precision=jax.lax.Precision.HIGHEST)
    report(phase="estimate", jit_vs_pallas_rel_frob=rel_frob(
        factors["pallas"], factors["jit"]))
    check(lowers_to_mosaic(
        lambda kk, s: estimate_product(kk, s, sz.r, backend="pallas"),
        k_est, summary), "pallas estimate lowers to tpu_custom_call")


def phase_pallas_summary(sz: Sizes, key) -> None:
    import jax
    from repro.core.summary_engine import build_summary
    A, B = _jit_chunk()(jax.random.fold_in(key, 3), sz.chunk, sz.n)
    k_sk = jax.random.fold_in(key, 4)
    for method in ("gaussian", "srht"):
        out = {}
        for backend in ("reference", "scan", "pallas"):
            t0 = time.perf_counter()
            out[backend] = jax.block_until_ready(build_summary(
                k_sk, A, B, sz.k, method=method, backend=backend))
            out[backend + "_s"] = time.perf_counter() - t0
        gap_ref = summary_gap(out["pallas"], out["reference"])
        gap_scan = summary_gap(out["pallas"], out["scan"])
        report(phase="pallas_summary", method=method, d=sz.chunk, n=sz.n,
               k=sz.k, pallas_vs_reference=gap_ref, pallas_vs_scan=gap_scan,
               scan_vs_reference=summary_gap(out["scan"], out["reference"]),
               bound=SUMMARY_RTOL, wall_s={b: out[b + "_s"] for b in
                                           ("reference", "scan", "pallas")})
        check(gap_ref <= SUMMARY_RTOL and gap_scan <= SUMMARY_RTOL,
              f"pallas {method} summary within {SUMMARY_RTOL} of reference "
              f"and scan")
        check(lowers_to_mosaic(
            lambda kk, a, b, method=method: build_summary(
                kk, a, b, sz.k, method=method, backend="pallas"),
            k_sk, A, B), f"pallas {method} summary lowers to tpu_custom_call")


def phase_serve(sz: Sizes, key) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import pipeline
    from repro.core.error_engine import estimate_error
    from repro.core.estimation_engine import estimate_product
    from repro.core.summary_engine import build_summary, summary_stage
    from repro.serve.engine import SketchService
    from repro.serve.scheduler import LoopConfig, PipelineWork, ServingLoop
    gen = _jit_chunk()
    d, n, k, r = sz.serve_d, sz.serve_n, sz.serve_k, sz.r
    spec = pipeline.SketchSpec(method="gaussian", backend="pallas", k=k,
                               probes=sz.probes)
    plan = pipeline.PipelinePlan(
        sketch=spec, estimation=pipeline.EstimationSpec(backend="jit"),
        rank=pipeline.RankPolicy(r=r), key_layout="service",
        with_error=True)
    tenants = ("acme", "globex")
    reqs = []
    for i in range(sz.serve_requests):
        A, B = gen(jax.random.fold_in(key, 100 + i), d, n)
        reqs.append((jax.random.fold_in(key, 200 + i), A, B,
                     tenants[i % 2]))
    keys = jnp.stack([pipeline.tenant_key(kk, t) for kk, _, _, t in reqs])
    stacked = (jnp.stack([a for _, a, _, _ in reqs[:4]]),
               jnp.stack([b for _, _, b, _ in reqs[:4]]))
    check(lowers_to_mosaic(
        lambda kk, a, b: summary_stage(spec, kk, a, b), keys[:4], *stacked),
        "batched pallas plan lowers to tpu_custom_call")

    loop = ServingLoop(config=LoopConfig(max_batch=4, pad="pow2"))
    loop.start()
    t0 = time.perf_counter()
    futures = [loop.submit(kk, A, B, work=PipelineWork(plan), tenant=t)
               for kk, A, B, t in reqs]
    results = [f.result(timeout=900) for f in futures]
    jax.block_until_ready([res.estimate.factors for res in results])
    wall = time.perf_counter() - t0
    loop.stop()
    report(phase="serve", requests=len(reqs), d=d, n=n, k=k, r=r,
           dispatches=loop.stats.dispatches,
           occupancy=loop.stats.occupancy, wall_s=wall)
    check(loop.stats.completed == len(reqs), "every request served")
    for i, ((_, A, B, t), res) in enumerate(zip(reqs, results)):
        tk = keys[i]
        ref = build_summary(tk, A, B, k, backend="reference",
                            probes=sz.probes)
        gap = summary_gap(res.summary, ref)
        solo = estimate_product(jax.random.fold_in(tk, 1), res.summary, r,
                                backend="jit", with_error=True)
        dense = lambda f: jnp.dot(f.U, f.V.T,
                                  precision=jax.lax.Precision.HIGHEST)
        solo_gap = rel_frob(dense(res.estimate.factors), dense(solo.factors))
        report(check=f"serve[{i}]", tenant=t, summary_vs_reference=gap,
               estimate_vs_solo=solo_gap, bounds=[SUMMARY_RTOL, SERVE_RTOL])
        check(gap <= SUMMARY_RTOL, f"served summary {i} matches reference")
        check(solo_gap <= SERVE_RTOL, f"served estimate {i} matches solo")
        check_estimate(f"serve[{i}]", exact_product(A, B), res.summary,
                       res.estimate, r, SERVE_RATIO_BOUND, ci=False)

    svc = SketchService(k=k, probes=sz.probes, backend="pallas")
    kk, A, B, _ = reqs[0]
    ticket = svc.submit(kk, A, B)
    t0 = time.perf_counter()
    served = svc.flush_factors(r="auto", tol=GATE_TOL,
                               r_max=GATE_R_MAX)[ticket]
    jax.block_until_ready(served.factors)
    M = exact_product(A, B)
    R = M - jnp.dot(served.factors.U, served.factors.V.T,
                    precision=jax.lax.Precision.HIGHEST)
    frob = float(jnp.linalg.norm(R))
    err = served.error
    wide = estimate_error(served.summary, served.factors,
                          confidence=CI_CONFIDENCE)
    report(phase="serve_gate", tol=GATE_TOL, r_max=GATE_R_MAX,
           r=int(served.factors.r),
           rel_est=float(err.rel_est),
           rel_true=frob / float(jnp.linalg.norm(M)), frob_true=frob,
           frob_ci=[float(wide.frob_lo), float(wide.frob_hi)],
           ci_confidence=CI_CONFIDENCE, wall_s=time.perf_counter() - t0)
    check(float(err.rel_est) <= GATE_TOL, "gated estimate meets its tol")
    check(float(wide.frob_lo) <= frob <= float(wide.frob_hi),
          "gated residual inside the probe CI")


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------

def _sharded_rows(mesh, spec, key, rows: int, n: int):
    """One slab of the pair made directly in ``spec``'s layout (threefry
    is partitionable: the values do not depend on it)."""
    import jax
    from jax.sharding import NamedSharding
    sh = NamedSharding(mesh, spec)
    A, B = jax.jit(planted_chunk, static_argnums=(1, 2),
                   out_shardings=(sh, sh))(key, rows, n)
    n_dev = mesh.devices.size
    for X in (A, B):
        check(X.sharding.is_equivalent_to(sh, 2)
              and len({s.device for s in X.addressable_shards}) == n_dev
              and all(s.data.shape == (rows // n_dev, n)
                      for s in X.addressable_shards),
              f"slab rows are sharded over the {n_dev} chips")
    return A, B


def phase_four_chips(sz: Sizes, key) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)
    from repro.core.distributed import distributed_streaming_update
    from repro.core.streaming import StreamingSummarizer
    from repro.core.summary_engine import build_summary
    from repro.dist.multihost import host_mesh
    devices = jax.devices()
    flat = Mesh(np.array(devices[:4]), ("rows",))
    hier = host_mesh(hosts=2)
    check(hier.devices.shape == (2, 2), "a 2x2 (host, device) mesh")
    d = sz.dist_slabs * sz.chunk
    summ = StreamingSummarizer(k=sz.k, probes=sz.probes)
    k_sk, k_data = jax.random.fold_in(key, 0), jax.random.fold_in(key, 1)
    one = jax.jit(planted_chunk, static_argnums=(1, 2),
                  out_shardings=SingleDeviceSharding(devices[0]))
    states = {name: summ.init(k_sk, (d, sz.n, sz.n))
              for name in ("one_chip", "flat", "hierarchical")}
    meshes = {"flat": (flat, "rows", P("rows", None)),
              "hierarchical": (hier, ("host", "device"),
                               P(("host", "device"), None))}
    walls = dict.fromkeys(states, 0.0)
    for c in range(sz.dist_slabs):
        kc, off = jax.random.fold_in(k_data, c), c * sz.chunk
        t0 = time.perf_counter()
        A, B = one(kc, sz.chunk, sz.n)
        states["one_chip"] = jax.block_until_ready(
            summ.update(states["one_chip"], A, B, off))
        walls["one_chip"] += time.perf_counter() - t0
        for name, (mesh, axis, spec) in meshes.items():
            t0 = time.perf_counter()
            A, B = _sharded_rows(mesh, spec, kc, sz.chunk, sz.n)
            states[name] = jax.block_until_ready(distributed_streaming_update(
                mesh, axis, summ, states[name], A, B, row_offset=off))
            walls[name] += time.perf_counter() - t0
    ref = summ.finalize(states["one_chip"])
    gaps = {name: summary_gap(summ.finalize(states[name]), ref)
            for name in meshes}

    # one-shot backend='distributed' over the whole row-sharded pair
    def whole_pair(kd):
        parts = [planted_chunk(jax.random.fold_in(kd, c), sz.chunk, sz.n)
                 for c in range(sz.dist_slabs)]
        return tuple(jnp.concatenate([p[i] for p in parts]) for i in (0, 1))

    A_all, B_all = jax.jit(whole_pair, out_shardings=NamedSharding(
        flat, P("rows", None)))(k_data)
    check(len({s.device for s in A_all.addressable_shards}) == 4,
          "whole pair row-sharded over 4 chips")
    t0 = time.perf_counter()
    one_shot = jax.block_until_ready(build_summary(
        k_sk, A_all, B_all, sz.k, backend="distributed", mesh=flat,
        axis="rows"))
    walls["one_shot_distributed"] = time.perf_counter() - t0
    gaps["one_shot_distributed"] = summary_gap(
        one_shot, ref._replace(probes=None))
    report(phase="four_chips", d=d, n1=sz.n, n2=sz.n, k=sz.k,
           probes=sz.probes, slabs=sz.dist_slabs,
           rel_frob_vs_one_chip=gaps, bound=SUMMARY_RTOL, wall_s=walls)
    for name, gap in gaps.items():
        check(gap <= SUMMARY_RTOL,
              f"{name} summary within {SUMMARY_RTOL} of the one-chip one")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip ingest paths")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              f"this smoke runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    report(device=device, jax=jax.__version__, compile_cache=cache_dir)
    clock = CompileClock()
    sz = Sizes()
    key = jax.random.PRNGKey(args.seed)
    t_start = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(sz, key)
        report(phase="four_chips", compile_s=clock.lap())
    else:
        summary, M = phase_ingest(sz, key)
        report(phase="ingest", compile_s=clock.lap())
        phase_estimate(sz, key, summary, M)
        report(phase="estimate", compile_s=clock.lap())
        del summary, M
        phase_pallas_summary(sz, jax.random.fold_in(key, 10))
        report(phase="pallas_summary", compile_s=clock.lap())
        phase_serve(sz, jax.random.fold_in(key, 11))
        report(phase="serve", compile_s=clock.lap())
    report(total_wall_s=time.perf_counter() - t_start,
           total_compile_s=clock.total)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
