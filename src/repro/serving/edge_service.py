"""Edge-detection serving: dynamic micro-batching over the substrate registry.

:class:`EdgeDetectService` queues single uint8 images, buckets them by padded
shape, and drains each bucket through
:func:`repro.nn.conv.edge_detect_batched` on any registered
:class:`~repro.nn.substrate.ProductSubstrate` spec (``"approx_pallas"``,
``"approx_lut:design_du2022"``, ``"approx_pallas:csp_axc1@4"`` — the
Pallas path serves any wiring at widths 3..8 via the LUT kernel, …).

Bit-identity contract: a served edge map equals the direct
``edge_detect_batched(img[None], substrate)[0]`` exactly, for every
substrate. Padding preserves this because

* images are zero-embedded at the top-left of the bucket shape, which is
  indistinguishable (to the 'same'-convolution taps of every kept pixel)
  from the zero border padding the direct path applies, and
* every output pixel depends on its own 3×3 window alone, on the fused
  conv kernel that Pallas substrates run and in every im2col contraction
  (one row per output pixel), so extra pad rows/images never perturb kept
  pixels. Results are cropped back to the request shape.

Compiled-call caching: one jitted ``edge_detect_batched`` closure per
service (= per substrate), so JAX's jit cache keys compiles on the
(batch, H, W) abstract shape — a per-(shape, substrate) compiled-call
cache. The batch dimension is padded up to ``max_batch_size`` so occupancy
changes don't retrace, and the service tracks the shape keys it has seen
(``compiled_shapes``, ``metrics.compiled_calls``) to make the compile count
observable. The seen-shape set is lock-guarded so concurrent workers
hitting a new shape record exactly one compile (JAX's own jit cache already
serializes the compilation itself).

Multi-worker overlap: with ``n_workers > 1`` the service dispatches batch
``k+1`` while batch ``k`` still runs on the device — ``_process`` returns
the jitted call's result *without* materializing it (asynchronous JAX
dispatch) and ``_finalize`` defers the implicit ``block_until_ready`` (the
``np.asarray``) to result delivery. Every batch is still computed by the
same compiled call on the same padded operands, so served maps stay
bit-identical to the single-worker path on every substrate.
"""
from __future__ import annotations

import threading
from typing import Iterable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.nn import conv
from repro.nn import substrate as sub
from repro.obs.trace import current_tracer, trace_span
from repro.serving.batcher import MicroBatcher, Ticket
from repro.serving.metrics import ServingMetrics


def _ceil_to(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


class EdgeDetectService:
    """Micro-batched Laplacian edge detection on one product substrate
    (or a per-tap-group :class:`repro.nn.plan.SubstratePlan`).

    substrate:          spec string, ProductSubstrate instance, or a
                        :class:`~repro.nn.plan.SubstratePlan` (or its dict
                        schema) assigning specs to the edge tap-group sites
                        ``conv.edge.center`` / ``conv.edge.ring`` — plans
                        serve through :func:`repro.nn.conv.edge_detect_planned`
                        (uniform plans ≡ the direct path bit-identically).
    max_batch_size:     flush a shape bucket at this many images.
    max_wait_s:         flush a partial bucket once its oldest image has
                        waited this long.
    bucket_granularity: H and W are rounded up to this multiple to form the
                        bucket key (1 = exact-shape buckets, no padding).
    pad_batches:        pad the batch dim to max_batch_size before the
                        compiled call, so occupancy changes don't retrace.
    n_workers:          worker threads draining the bucketed queue; >1
                        overlaps host-side micro-batching with device
                        compute (results stay bit-identical).
    partitioning:       optional :class:`repro.nn.substrate.Partitioning` —
                        the served contraction lowers through shard_map
                        (data-parallel M / reduce-scattered K). Bit-identity
                        to the unsharded path holds for every bit-exact
                        substrate, so served maps are unchanged.
    """

    def __init__(self, substrate: "str | sub.ProductSubstrate" = "approx_bitexact",
                 *, max_batch_size: int = 8, max_wait_s: float = 2e-3,
                 bucket_granularity: int = 16, pad_batches: bool = True,
                 n_workers: int = 1,
                 partitioning: Optional[sub.Partitioning] = None,
                 metrics: Optional[ServingMetrics] = None, start: bool = True):
        if bucket_granularity < 1:
            raise ValueError(
                f"bucket_granularity must be >= 1, got {bucket_granularity}")
        from repro.nn import plan as plan_mod
        if isinstance(substrate, (plan_mod.SubstratePlan, dict)):
            self.plan = plan_mod.as_plan(substrate)
            self.substrate = sub.get_substrate(self.plan.default)
            self.spec = self.plan.label
        else:
            self.plan = None
            self.substrate = sub.as_substrate(substrate)
            self.spec = self.substrate.meta.spec
        self.bucket_granularity = bucket_granularity
        self.pad_batches = pad_batches
        self.partitioning = partitioning
        # the conv kernel every batch runs, known before the first trace
        # (plans contract their tap groups through im2col)
        fused = self.plan is None and conv.takes_fused_path(
            self.substrate, conv.LAPLACIAN, partitioning)
        self.conv_path = "fused" if fused else "im2col"
        self.metrics = metrics or ServingMetrics()
        self._compiled_keys = set()  # (batch, H, W) shapes traced so far
        self._compiled_lock = threading.Lock()  # workers race on new shapes
        self._jit_fn = jax.jit(self._compute)
        self.batcher = MicroBatcher(
            self._process, max_batch_size=max_batch_size,
            max_wait_s=max_wait_s, bucket_fn=self._bucket,
            finalize_fn=self._finalize, n_workers=n_workers,
            metrics=self.metrics)
        if start:
            self.batcher.start()

    # -- lifecycle -----------------------------------------------------------

    def close(self, drain: bool = True) -> None:
        self.batcher.stop(drain=drain)

    def __enter__(self) -> "EdgeDetectService":
        self.batcher.start()
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- request path --------------------------------------------------------

    def _compute(self, batch):
        """Traced body of the compiled call: the edge-detect contraction."""
        if self.plan is not None:
            return conv.edge_detect_planned(
                batch, self.plan, partitioning=self.partitioning)
        return conv.edge_detect_batched(
            batch, self.substrate, partitioning=self.partitioning)

    def _bucket(self, img: np.ndarray) -> Tuple[int, int]:
        h, w = img.shape
        g = self.bucket_granularity
        return (_ceil_to(h, g), _ceil_to(w, g))

    def _process(self, bucket: Tuple[int, int], imgs: List[np.ndarray]):
        """Dispatch phase: pad to the bucket shape and enqueue the compiled
        call *without* blocking on it — the returned device array is
        materialized by :meth:`_finalize`, so with several workers the next
        batch's dispatch overlaps this batch's device compute."""
        hh, ww = bucket
        b = len(imgs)
        bp = self.batcher.max_batch_size if self.pad_batches else b
        with trace_span("edge.pad", "serving", bucket=f"{hh}x{ww}", size=b):
            batch = np.zeros((bp, hh, ww), np.uint8)
            for i, im in enumerate(imgs):
                h, w = im.shape
                batch[i, :h, :w] = im
        shape = "x".join(map(str, batch.shape))
        if self.conv_path == "fused":
            self.metrics.record_fused_batch()
        with self._compiled_lock:
            first = batch.shape not in self._compiled_keys
            if first:
                self._compiled_keys.add(batch.shape)
        if first:
            self.metrics.record_compile()
            # first call for this shape: the jitted call traces + compiles
            # before dispatching, so this span is compile-dominated
            with trace_span("edge.compile", "serving", shape=shape,
                            spec=self.spec, conv=self.conv_path):
                out = self._jit_fn(batch)
        else:
            with trace_span("edge.execute", "serving", shape=shape,
                            spec=self.spec, conv=self.conv_path):
                out = self._jit_fn(batch)
        return out, [im.shape for im in imgs]

    def _finalize(self, bucket: Tuple[int, int], raw) -> List[np.ndarray]:
        """Delivery phase: block until the dispatched batch is ready and on
        the host, then crop each map back to its request shape. Under a
        tracer the copy is timed apart as ``edge.fetch``; untraced, one
        ``np.asarray`` waits and copies. Each map is copied out of the
        batch array, so a kept map does not hold the whole batch."""
        out_dev, shapes = raw
        with trace_span("edge.wait", "serving", size=len(shapes)):
            if current_tracer() is None:
                out = np.asarray(out_dev)      # implicit block_until_ready
            else:
                # traced only: the device->host copy runs on no device op,
                # so time it apart from the wait for the computation
                out_dev.block_until_ready()
                with trace_span("edge.fetch", "serving",
                                bytes=out_dev.nbytes, size=len(shapes)):
                    out = np.asarray(out_dev)
        with trace_span("edge.crop", "serving", size=len(shapes)):
            return [out[i, :h, :w].copy() for i, (h, w) in enumerate(shapes)]

    @staticmethod
    def _check_image(img) -> np.ndarray:
        a = np.asarray(img)
        if a.ndim != 2 or a.dtype != np.uint8:
            raise ValueError(
                f"expected a single (H, W) uint8 image, got {a.dtype} "
                f"array of shape {a.shape}")
        return a

    def submit(self, img: np.ndarray) -> Ticket:
        """Queue one (H, W) uint8 image; returns a Ticket (``.result()``)."""
        return self.batcher.submit(self._check_image(img))

    def detect(self, imgs: "np.ndarray | Iterable[np.ndarray]",
               timeout: Optional[float] = 60.0) -> List[np.ndarray]:
        """Submit image(s) and block for the edge maps, preserving order.

        Accepts one (H, W) image, a (B, H, W) stack, or an iterable of
        arbitrary-shape (H, W) images (exercises the bucketing path).
        """
        if isinstance(imgs, np.ndarray) and imgs.ndim == 2:
            imgs = [imgs]
        tickets = self.batcher.submit_many(
            self._check_image(im) for im in imgs)
        if not self.batcher.running:
            self.batcher.flush()
        return [t.result(timeout=timeout) for t in tickets]

    # -- introspection -------------------------------------------------------

    @property
    def n_workers(self) -> int:
        return self.batcher.n_workers

    @property
    def compiled_shapes(self) -> Sequence[Tuple[int, int, int]]:
        """(batch, H, W) keys the service has compiled calls for."""
        with self._compiled_lock:
            return tuple(sorted(self._compiled_keys))

    def stats(self) -> dict:
        return self.metrics.snapshot()
