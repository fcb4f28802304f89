"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Runs the batched serving engine with synthetic requests (reduced configs on
CPU).

Telemetry: ``--metrics-out`` dumps the engine's metrics registry
(Prometheus text for ``.prom``/``.txt`` paths, JSON otherwise) and
``--trace-out`` writes a Chrome/Perfetto trace of the serving spans —
load it at ``ui.perfetto.dev``. See ``docs/observability.md``.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import add_reduced_overrides, overrides_from
from repro.models import registry as reg
from repro.obs import Tracer, tracing_scope, write_chrome_trace, write_metrics
from repro.serving import ServingEngine
from repro.serving.engine import Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=reg.list_archs())
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-tokens", type=int, default=8)
    ap.add_argument("--workers", type=int, default=1,
                    help="concurrent decode loops (each with its own KV "
                         "caches; requests split round-robin)")
    ap.add_argument("--plan", default=None, metavar="PATH",
                    help="substrate plan: a plan JSON file or a plan-bundle "
                         "directory (see docs/plans.md). Serves the model "
                         "with per-site mixed substrates; a bundle that "
                         "carries params restores them too.")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump serving metrics (.prom/.txt → Prometheus "
                         "text, else JSON)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace-event JSON of the "
                         "serving spans")
    add_reduced_overrides(ap)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = reg.get_config(args.arch, **overrides_from(args))
    bundle = reg._BUILDERS[cfg.family](cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    plan = None
    if args.plan:
        from repro import checkpoint as ckpt
        from repro.nn import plan as plan_mod

        if os.path.isdir(args.plan):
            plan, raw, _ = ckpt.load_plan_bundle(args.plan)
            if raw is not None:   # bundle ships params: restore into our tree
                _, params, _ = ckpt.load_plan_bundle(
                    args.plan, params_template=params)
        else:
            plan = plan_mod.load_plan(args.plan)
        print(f"[serve] substrate plan: {plan.label}")
    engine = ServingEngine(bundle, params, batch_size=args.batch,
                           max_len=args.max_len, substrate=plan)

    rng = np.random.default_rng(0)
    reqs = [Request(prompt=list(rng.integers(1, cfg.vocab, size=4)),
                    max_tokens=args.max_tokens,
                    temperature=0.0 if i % 2 == 0 else 0.8)
            for i in range(args.requests)]
    tracer = Tracer() if args.trace_out else None
    t0 = time.perf_counter()
    with tracing_scope(tracer):
        out = engine.generate(reqs, workers=args.workers)
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.output) for r in out)
    for i, r in enumerate(out):
        print(f"req{i}: prompt={r.prompt} -> {r.output}")
    print(f"[serve] {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens / max(dt, 1e-9):.1f} tok/s)")
    if args.metrics_out:
        p = write_metrics(engine.metrics.registry, args.metrics_out)
        print(f"[serve] metrics -> {p}")
    if args.trace_out:
        p = write_chrome_trace(tracer, args.trace_out)
        print(f"[serve] trace -> {p} ({len(tracer.events())} events)")


if __name__ == "__main__":
    main()
