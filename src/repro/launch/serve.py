"""Serving drivers: LM generation with the Engine, and sketch serving on
the continuously-batched ``ServingLoop`` (reduced-scale CPU).

    PYTHONPATH=src python -m repro.launch.serve --arch phi3-mini-3.8b \
        --reduced --batch 4 --prompt-len 16 --new-tokens 16

    PYTHONPATH=src python -m repro.launch.serve --mode sketch \
        --requests 32 --max-batch 8 --deadline-ms 200 --tenants acme,globex

``--mode sketch`` runs the async serving stack end to end: a ServingLoop is
started on its background pump, requests are submitted (returning futures
immediately), and the driver just waits on the futures — batching,
deadlines, and dispatch all happen on the loop thread. The JSON line it
prints carries the loop stats (dispatches, occupancy, shed) so the driver
doubles as a smoke check that continuous batching is actually batching.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models import build
from repro.serve.engine import Engine, ServeConfig


def run_generate(args) -> dict:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init_params(key)
    batch = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size)}
    for name, sds in model.aux_input_shapes(args.batch).items():
        batch[name] = jnp.zeros(sds.shape, sds.dtype)
    eng = Engine(model, params,
                 ServeConfig(max_new_tokens=args.new_tokens,
                             temperature=args.temperature))
    out = eng.generate(batch)
    return {"arch": cfg.name, "output_shape": list(out.shape),
            "sample_row": out[0].tolist()[:24]}


def run_sketch(args) -> dict:
    from repro.core import pipeline
    from repro.serve.scheduler import LoopConfig, PipelineWork, ServingLoop

    plan = pipeline.PipelinePlan(
        sketch=pipeline.SketchSpec(k=args.k, backend="scan", block=1024),
        estimation=pipeline.EstimationSpec(m=args.m, T=args.T),
        rank=pipeline.RankPolicy(r=args.r),
        key_layout="service")
    loop = ServingLoop(config=LoopConfig(
        max_batch=args.max_batch,
        max_queue=args.max_queue,
        default_deadline=args.deadline_ms / 1e3,
        pad="pow2"))
    tenants = [t or None for t in args.tenants.split(",")] if args.tenants \
        else [None]
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (args.d, args.n))
    B = jax.random.normal(jax.random.fold_in(key, 1), (args.d, args.n))

    loop.start()
    try:
        futures = [
            loop.submit(jax.random.fold_in(key, i), A, B,
                        work=PipelineWork(plan),
                        tenant=tenants[i % len(tenants)])
            for i in range(args.requests)]
        ranks = sorted({f.result(timeout=600).estimate.factors.U.shape[-1]
                        for f in futures})
    finally:
        loop.stop()
    stats = loop.stats
    return {"mode": "sketch", "requests": args.requests,
            "completed": stats.completed,
            "dispatches": stats.dispatches,
            "occupancy": round(stats.occupancy, 3),
            "shed": dict(stats.shed),
            "dispatch_triggers": dict(stats.dispatched),
            "served_ranks": ranks}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("generate", "sketch"),
                    default="generate")
    # generate mode
    ap.add_argument("--arch", default="phi3-mini-3.8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    # sketch mode
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-queue", type=int, default=None)
    ap.add_argument("--deadline-ms", type=float, default=200.0)
    ap.add_argument("--tenants", default="",
                    help="comma-separated tenant ids cycled over requests")
    ap.add_argument("--d", type=int, default=512)
    ap.add_argument("--n", type=int, default=32)
    ap.add_argument("--k", type=int, default=64)
    ap.add_argument("--r", type=int, default=4)
    ap.add_argument("--m", type=int, default=800)
    ap.add_argument("--T", type=int, default=3)
    args = ap.parse_args(argv)
    enable_compile_cache()

    out = run_sketch(args) if args.mode == "sketch" else run_generate(args)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
