"""Quickstart: single-pass PCA of a matrix product in ~20 lines.

    PYTHONPATH=src python examples/quickstart.py

Choosing a summary backend
--------------------------
Step 1 (the one pass over A, B) goes through one entry point,
``core.build_summary(key, A, B, k, method=..., backend=...)``, and every
backend produces the same summary for the same key (identical
(key, global_row_index) randomness — parity-tested in
tests/core/test_summary_engine.py):

* ``reference``   — materialize the (k, d) operator, one dense matmul.
      Simplest; fine whenever (k, d) fits in memory.
* ``scan``        — stream row blocks, regenerating each block's operator
      slice on the fly. Use when d is huge (the operator never exists).
* ``rows``        — arbitrary-order row streams: rows arrive as
      (global index, A row, B row) chunks in any order.
* ``pallas``      — fused TPU kernels (sketch + norms in one HBM pass;
      SRHT via the blocked-FWHT MXU kernel). Compiled by Mosaic on a TPU
      (``python chip_smoke.py`` runs it there); interpreted on the CPU,
      which checks the kernel bodies but not what the TPU compiler takes.
* ``distributed`` — rows sharded over a mesh axis (pass mesh=/axis=);
      one psum aggregates the shards (Spark treeAggregate as collectives).

``method`` is 'gaussian' (analyzed in the paper) or 'srht' (the paper's
Spark choice); both work on every backend. Pass stacked (L, d, n) inputs to
sketch L pairs in one vmapped dispatch, and ``precision='bf16'`` for
bf16-in/f32-accumulate on accelerators. ``core.smppca(...)`` forwards
``method``/``backend``/``precision`` straight through.

When the pair never fits in memory (or arrives over time), the same pass
runs chunked through ``core.StreamingSummarizer`` — ``init / update /
merge / finalize`` with any chunking or merge order, checkpointable
mid-pass (see docs/streaming.md and examples/streaming_cooccurrence.py;
the one-shot backends below are the it-fits-in-memory fast path).

Choosing an estimation method (step 2-3)
----------------------------------------
The summary then flows into ``core.estimate_product(key, summary, r,
method=..., backend=...)`` — the EstimationEngine:

* ``rescaled_jl``  — the paper: biased sampling + rescaled-JL entries +
      WAltMin. Best one-pass accuracy on correlated data (Fig 2b/4b).
* ``direct_svd``   — SVD of the sketch product; cheapest, keeps the plain-JL
      bias the paper removes (Fig 2a).
* ``lela_waltmin`` — exact entries from a second pass over (A, B)
      (``exact_pair=(A, B)``): the two-pass accuracy ceiling.

with ``backend`` in {'reference' (eager oracle), 'jit' (scan'd WAltMin),
'pallas' (gather-kernel entry extraction)} — see README.md.
"""
import math

import jax
import jax.numpy as jnp

from repro import core

key = jax.random.PRNGKey(0)

# two tall matrices whose product A^T B we want the top-5 components of
d, n, r = 20_000, 400, 5
D = jnp.diag(1.0 / jnp.arange(1.0, n + 1.0))
A = jax.random.normal(key, (d, n)) @ D
B = A + 0.3 * jax.random.normal(jax.random.fold_in(key, 1), (d, n)) @ D

# one pass: sketches + column norms; then sample, estimate, complete.
# backend="scan" streams row blocks — the (k, d) operator is never built
# (swap in "reference", "pallas", ... freely: same key -> same summary)
result = core.smppca(
    key, A, B,
    r=r,                                 # target rank
    k=256,                               # sketch size (Thm 3.1: eta ~ 1/sqrt k)
    m=int(10 * n * r * math.log(n)),     # samples (Fig 4a: >= nr log n)
    T=8,                                 # WAltMin iterations
    backend="scan",
)

# smppca is exactly the two engines composed — sketch once, estimate later
# (or many times, with different methods, from the same one-pass summary):
summary = core.build_summary(key, A, B, 256, backend="scan")
print(f"summary: sketches {summary.A_sketch.shape} + "
      f"{summary.n1 + summary.n2} norms")
est = core.estimate_product(
    jax.random.fold_in(key, 2), summary, r,
    method="rescaled_jl",                # or "direct_svd" / "lela_waltmin"
    backend="jit",                       # or "reference" / "pallas"
    m=int(10 * n * r * math.log(n)), T=8)
print(f"estimate_product factors: U {est.factors.U.shape}, "
      f"V {est.factors.V.shape}")

err, opt = core.spectral_error_vs_optimal(A, B, r, result.factors)
print(f"SMP-PCA spectral error : {float(err):.4f}")
print(f"optimal rank-{r} error   : {float(opt):.4f}")
print(f"factors: U {result.factors.U.shape}, V {result.factors.V.shape}")

# compare with the naive one-pass baseline the paper beats
sf = core.sketch_svd(key, A, B, r=r, k=256)
err_svd, _ = core.spectral_error_vs_optimal(A, B, r, sf)
print(f"SVD(sketch) error      : {float(err_svd):.4f}  "
      f"(paper Fig 3b: SMP-PCA wins)")
