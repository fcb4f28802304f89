"""Serve edge-detection requests through the micro-batching service.

Queues a stream of mixed-shape images into an ``EdgeDetectService`` running
on a chosen product substrate, verifies every served edge map is
bit-identical to the direct batched pipeline, and prints the telemetry
table (throughput, latency percentiles, batch occupancy).

``--metrics-out`` dumps the combined metrics registry (serving counters +
per-contraction substrate meters; ``.prom``/``.txt`` → Prometheus text,
else JSON) and ``--trace-out`` records the serving spans (queue wait, pad,
compile, execute, crop) as a Chrome/Perfetto trace — CI smoke-validates
both artifacts. See ``docs/observability.md``.

``--workers N`` serves through N concurrent batcher workers (batch k+1
dispatches while batch k runs — the per-worker ``serving_worker_*`` metric
families and the ``serving_inflight_batches_peak`` gauge land in the same
dump); outputs stay bit-identical at every worker count. ``--sharded``
partitions each served contraction across the visible device mesh through
``shard_map`` (run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
on a CPU host).

Run:  PYTHONPATH=src python examples/serve_edge.py [--smoke]
      [--substrate approx_lut:design_du2022] [--requests 24]
      [--workers 4] [--sharded]
      [--metrics-out serve.json] [--trace-out trace.json]
"""
import argparse

import numpy as np

from repro.data import mixed_shape_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.nn import conv
from repro.obs import (ContractionMeter, MetricsRegistry, Tracer,
                       telemetry_scope, tracing_scope, write_chrome_trace,
                       write_metrics)
from repro.serving import EdgeDetectService
from repro.serving.metrics import ServingMetrics


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--substrate", default="approx_bitexact",
                    help="ProductSubstrate spec (e.g. approx_pallas, "
                         "approx_lut:design_du2022)")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--workers", type=int, default=1,
                    help="batcher worker threads (overlap dispatch of "
                         "batch k+1 with batch k's device compute)")
    ap.add_argument("--sharded", action="store_true",
                    help="partition served contractions across the visible "
                         "device mesh via shard_map")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fast run for CI (few small images)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the combined metrics registry (.prom/.txt → "
                         "Prometheus text, else JSON)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace-event JSON of the "
                         "serving spans")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        args.requests = 6
        imgs = mixed_shape_batch(args.requests,
                                 shapes=((16, 16), (24, 31), (32, 32)))
    else:
        imgs = mixed_shape_batch(args.requests, noise=2.0)

    # one shared registry: serving counters + substrate meters, one dump
    registry = MetricsRegistry()
    meter = ContractionMeter(registry)
    tracer = Tracer() if args.trace_out else None
    partitioning = None
    if args.sharded:
        from repro.launch.mesh import (contraction_partitioning,
                                       make_debug_mesh)
        partitioning = contraction_partitioning(make_debug_mesh())
    with tracing_scope(tracer), telemetry_scope(meter):
        svc = EdgeDetectService(args.substrate,
                                max_batch_size=args.max_batch,
                                max_wait_s=args.max_wait_ms * 1e-3,
                                n_workers=args.workers,
                                partitioning=partitioning,
                                metrics=ServingMetrics(registry=registry))
        print(f"serving {len(imgs)} mixed-shape images on "
              f"substrate={svc.spec!r} (max_batch={args.max_batch}, "
              f"max_wait={args.max_wait_ms}ms, workers={args.workers}"
              f"{', sharded' if args.sharded else ''})")

        outs = svc.detect(imgs)
        svc.close()

    # every served map must be bit-identical to the direct batched pipeline
    for im, out in zip(imgs, outs):
        ref = np.asarray(conv.edge_detect_batched(im[None], svc.substrate))[0]
        assert out.shape == im.shape and np.array_equal(out, ref), \
            f"service output diverged from edge_detect_batched at {im.shape}"
    shapes = sorted({im.shape for im in imgs})
    print(f"served == direct edge_detect_batched (bit-identical) across "
          f"{len(shapes)} shapes: OK")
    print(f"compiled bucket shapes: {list(svc.compiled_shapes)}")
    print()
    print(svc.metrics.format_table())
    summary = meter.summary()
    if summary:
        print()
        for spec, row in sorted(summary.items()):
            print(f"meter      {spec}: {row['contractions']} contractions, "
                  f"{row['macs']} MACs, "
                  f"{row['energy_pdp_fj'] / 1e6:.2f} nJ est.")
    if args.metrics_out:
        p = write_metrics(registry, args.metrics_out,
                          extra={"substrate_meter": summary})
        print(f"metrics -> {p}")
    if args.trace_out:
        p = write_chrome_trace(tracer, args.trace_out)
        print(f"trace -> {p} ({len(tracer.events())} events)")


if __name__ == "__main__":
    main()
