#!/usr/bin/env python3
"""Bring-up smoke test: the served paths on one TPU chip, checked bit-exactly.

Runs in one process (a chip belongs to one process at a time) and fails
loudly: every phase raises on a mismatch, and any error exits non-zero.
Phases, in order:

1. device check — a TPU, and no Pallas interpret mode;
2. edge service (``EdgeDetectService("approx_pallas")``, fused conv) on
   seeded 1920×1080 and 1280×720 frames, against the tap-loop model
   ``conv.edge_detect(img, "proposed")``;
3. planned edge path (center tap on proposed@6, ring on proposed@8,
   through ``closed_form_matmul``) against the same plan on
   ``approx_bitexact``;
4. LUT strategy: ``approx_pallas:exact`` served against ``exact``; the
   LUT matmul kernel (proposed@8 and design_du2022@6) and the fused
   conv's LUT kind (design_du2022@6) against ``approx_bitexact``;
5. LM serving: minitron-8b at its published widths, cut to 4 layers and a
   1/8 vocab, serving requests under ``exact`` and ``approx_pallas``;
6. kernel check: ``closed_form_matmul`` at a layer shape against
   ``approx_bitexact``.

Every compiled ``approx_pallas`` program is checked to hold a
``tpu_custom_call`` (a Mosaic kernel, not the interpreter). Timings printed
on the way are bring-up observations, not benchmark numbers. The last line
of stdout is one JSON object naming the device.

``--four-chips`` runs only the sharded contraction path on a 2×2
``data``×``model`` mesh and what it is compared with: sharded
``dot_general`` and the partitioned edge service, each bit-identical to
its one-device result.

Run:  python chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

LAYER_SHAPE = (8, 4096, 16384)  # (M, K, N): a minitron-8b FFN contraction
SEED = 0  # frames, operands, weights and prompts are made from it
LUT_WIRING = "design_du2022@6"  # an approximate wiring below width 8


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def timed(fn, *args):
    """(result, seconds) of ``fn(*args)``, results materialized."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def device_check(n_chips: int):
    if os.environ.get("REPRO_PALLAS_INTERPRET") is not None:
        fail("REPRO_PALLAS_INTERPRET is set; the chip run must use Mosaic")
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < n_chips:
        fail(f"{n_chips} chips needed, {len(devs)} found")
    from repro.kernels import blocking
    from repro.launch.compile_cache import enable_compile_cache

    if blocking.resolve_interpret():
        fail("Pallas kernels would run in interpret mode")
    log(f"device {devs[0].device_kind} x{len(devs)}; compile cache "
        f"{enable_compile_cache()}")
    return devs


def assert_mosaic(jitted, *args, what: str) -> None:
    """The compiled program of ``jitted(*args)`` holds a Mosaic kernel."""
    text = jitted.lower(*args).compile().as_text()
    check("tpu_custom_call" in text, f"{what}: no tpu_custom_call in the "
          "compiled program")
    log(f"{what}: compiled program holds tpu_custom_call")


def frames():
    from repro.data import image_batch

    return (list(image_batch(16, 1080, 1920, seed=SEED))
            + list(image_batch(3, 720, 1280, seed=SEED + 100)))


def serve(svc, imgs, label: str):
    """Serve ``imgs`` bucket by bucket, logging compile and steady times."""
    import numpy as np

    out = []
    for shape in sorted({im.shape for im in imgs}, reverse=True):
        group = [im for im in imgs if im.shape == shape]
        b = svc.batcher.max_batch_size
        for i in range(0, len(group), b):
            t0 = time.perf_counter()
            res = svc.detect(group[i:i + b], timeout=900)
            dt = time.perf_counter() - t0
            kind = "compile+run" if i == 0 else "steady"
            log(f"{label} {shape[1]}x{shape[0]} batch of "
                f"{len(res)}: {dt:.3f} s ({kind})")
            out += [(im, np.asarray(r)) for im, r in zip(group[i:i + b], res)]
    return out


def padded_batch(svc, shape):
    import numpy as np

    hh, ww = svc._bucket(np.zeros(shape, np.uint8))
    return np.zeros((svc.batcher.max_batch_size, hh, ww), np.uint8)


def phase_edge(imgs) -> None:
    import numpy as np

    from repro.nn import conv
    from repro.serving import EdgeDetectService

    with EdgeDetectService("approx_pallas", max_batch_size=8) as svc:
        served = serve(svc, imgs, "edge approx_pallas")
        for shape in {im.shape for im in imgs}:
            assert_mosaic(svc._jit_fn, padded_batch(svc, shape),
                          what=f"edge approx_pallas {shape}")
    for i, (im, got) in enumerate(served):
        want = np.asarray(conv.edge_detect(im, "proposed"))
        check(got.shape == im.shape and np.array_equal(got, want),
              f"edge approx_pallas frame {i} {im.shape} differs from "
              "conv.edge_detect(proposed)")
    log(f"edge approx_pallas: {len(served)} maps == conv.edge_detect "
        "(bit-identical)")


def phase_planned(imgs) -> None:
    import jax
    import numpy as np

    from repro.nn import conv
    from repro.nn.plan import SubstratePlan
    from repro.serving import EdgeDetectService

    def plan(backend):
        return SubstratePlan(default=f"{backend}:proposed@8", rules=(
            ("conv.edge.center", f"{backend}:proposed@6"),
            ("conv.edge.ring", f"{backend}:proposed@8")))

    with EdgeDetectService(plan("approx_pallas"), max_batch_size=8) as svc:
        served = serve(svc, imgs, "planned approx_pallas")
        for shape in {im.shape for im in imgs}:
            assert_mosaic(svc._jit_fn, padded_batch(svc, shape),
                          what=f"planned approx_pallas {shape}")
    ref_fn = jax.jit(lambda x: conv.edge_detect_planned(
        x, plan("approx_bitexact")))
    for shape in {im.shape for im in imgs}:
        group = [(im, got) for im, got in served if im.shape == shape]
        want = np.asarray(ref_fn(np.stack([im for im, _ in group])))
        for j, (im, got) in enumerate(group):
            check(np.array_equal(got, want[j]),
                  f"planned frame {j} {shape} differs from the "
                  "approx_bitexact plan")
    log(f"planned edge: {len(served)} maps == approx_bitexact plan "
        "(bit-identical)")


def phase_lut(imgs) -> None:
    import jax
    import numpy as np

    from repro.nn import conv
    from repro.nn import substrate as sub
    from repro.serving import EdgeDetectService

    shape = min({im.shape for im in imgs})
    small = [im for im in imgs if im.shape == shape]
    with EdgeDetectService("approx_pallas:exact", max_batch_size=8) as svc:
        served = serve(svc, small, "edge approx_pallas:exact")
        assert_mosaic(svc._jit_fn, padded_batch(svc, small[0].shape),
                      what="edge approx_pallas:exact")
    for i, (im, got) in enumerate(served):
        check(np.array_equal(got, np.asarray(conv.edge_detect(im, "exact"))),
              f"approx_pallas:exact frame {i} differs from exact")
    log(f"edge approx_pallas:exact: {len(served)} maps == exact "
        "(bit-identical)")

    # the forced LUT strategy on the exact-width table and on an
    # approximate wiring at width 6 (table padded from 64 to 128 lanes)
    rng = np.random.default_rng(SEED)
    m, k, n = 256, 1024, 512
    a = rng.integers(-128, 128, (m, k)).astype(np.int8)
    b = rng.integers(-128, 128, (k, n)).astype(np.int8)
    for key in ("proposed", LUT_WIRING):
        lut = sub.PallasSubstrate(key, kernel="lut")
        dot = jax.jit(lut.dot_int)
        got, dt = timed(dot, a, b)
        want = sub.get_substrate(f"approx_bitexact:{key}").dot_int(a, b)
        check(np.array_equal(np.asarray(got), np.asarray(want)),
              f"LUT matmul kernel {key} ({m}x{k}@{k}x{n}) differs from "
              "approx_bitexact")
        assert_mosaic(dot, a, b, what=f"LUT matmul kernel {key}")
        log(f"LUT matmul kernel {key} {m}x{k}@{k}x{n} == approx_bitexact "
            f"(bit-identical; first call {dt:.3f} s)")

    # the fused conv's LUT kind on the approximate wiring (compare-selects)
    px = conv.to_signed_pixels(np.stack(small), 6)
    lut = sub.PallasSubstrate(LUT_WIRING, kernel="lut")
    fused = jax.jit(lambda x: conv.conv2d_batched(x, conv.LAPLACIAN, lut,
                                                  fused=True))
    got, dt = timed(fused, px)
    want = conv.conv2d_batched(px, conv.LAPLACIAN,
                               f"approx_bitexact:{LUT_WIRING}", fused=False)
    check(np.array_equal(np.asarray(got), np.asarray(want)),
          f"fused conv LUT kind {LUT_WIRING} differs from approx_bitexact")
    assert_mosaic(fused, px, what=f"fused conv LUT kind {LUT_WIRING}")
    log(f"fused conv LUT kind {LUT_WIRING} {len(small)}x{shape[1]}x"
        f"{shape[0]} == approx_bitexact im2col (bit-identical; first call "
        f"{dt:.3f} s)")


def phase_lm() -> None:
    import jax
    import numpy as np

    from repro.models import registry as reg
    from repro.serving import ServingEngine
    from repro.serving.engine import Request

    # minitron-8b at its published widths (d_model 4096, d_ff 16384, GQA
    # 32/8, bf16), cut to 4 of 32 layers and 32000 of 256000 vocab rows
    cfg = reg.get_config("minitron-8b", n_layers=4, vocab=32000)
    bundle = reg._BUILDERS[cfg.family](cfg)
    params, dt = timed(jax.jit(bundle.init_params), jax.random.PRNGKey(SEED))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    log(f"minitron-8b x4 layers: {n_params / 1e9:.3f}B params initialized "
        f"in {dt:.1f} s")
    rng = np.random.default_rng(SEED)
    prompts = [list(rng.integers(1, cfg.vocab, size=4)) for _ in range(4)]
    for spec in ("exact", "approx_pallas"):
        engine = ServingEngine(bundle, params, batch_size=2, max_len=128,
                               substrate=spec)
        step, steps = engine._step, []

        def checked_step(state, tokens, cache_len, _step=step):
            t0 = time.perf_counter()
            logits, state = _step(state, tokens, cache_len)
            steps.append(time.perf_counter() - t0)
            check(np.isfinite(logits).all(),
                  f"LM {spec}: non-finite logits at cache_len {cache_len}")
            return logits, state

        engine._step = checked_step
        reqs = [Request(prompt=p, max_tokens=8) for p in prompts]
        t0 = time.perf_counter()
        engine.generate(reqs)
        dt = time.perf_counter() - t0
        check(all(r.done and len(r.output) == 8 for r in reqs),
              f"LM {spec}: not every request got 8 tokens")
        steady = sorted(steps[1:])[len(steps[1:]) // 2]
        log(f"LM {spec}: 4 requests x 8 tokens in {dt:.2f} s; first step "
            f"(compile) {steps[0]:.2f} s, median step {steady * 1e3:.2f} ms "
            f"over {len(steps)} steps; all logits finite")
        if spec == "approx_pallas":
            batch = {"token": np.zeros((2, 1), np.int32),
                     "cache_len": np.int32(0)}
            assert_mosaic(engine._decode, params, engine._init_state(),
                          batch, what="LM approx_pallas decode_step")


def phase_kernel() -> None:
    import jax
    import numpy as np

    from repro.kernels.approx_matmul.ops import closed_form_matmul
    from repro.nn import substrate as sub

    m, k, n = LAYER_SHAPE
    rng = np.random.default_rng(SEED)
    a = rng.integers(-128, 128, (m, k)).astype(np.int8)
    b = rng.integers(-128, 128, (k, n)).astype(np.int8)
    fn = jax.jit(lambda x, y: closed_form_matmul(x, y, "proposed"))
    got, first = timed(fn, a, b)
    times = [timed(fn, a, b)[1] for _ in range(3)]
    want = sub.get_substrate("approx_bitexact").dot_int(a, b)
    check(np.array_equal(np.asarray(got), np.asarray(want)),
          f"closed_form_matmul {m}x{k}@{k}x{n} differs from approx_bitexact")
    assert_mosaic(fn, a, b, what="closed_form_matmul layer shape")
    log(f"closed_form_matmul {m}x{k}@{k}x{n} == approx_bitexact "
        f"(bit-identical); first call {first:.2f} s, steady "
        f"{min(times) * 1e3:.2f} ms")


def phase_four_chips(imgs) -> None:
    import jax
    import numpy as np

    from repro.launch.mesh import contraction_partitioning, make_debug_mesh
    from repro.nn import substrate as sub
    from repro.serving import EdgeDetectService

    mesh = make_debug_mesh(4)
    check(dict(mesh.shape) == {"data": 2, "model": 2},
          f"expected a 2x2 data x model mesh, got {dict(mesh.shape)}")
    part = contraction_partitioning(mesh)
    m, k, n = LAYER_SHAPE
    rng = np.random.default_rng(SEED)
    a = rng.integers(-128, 128, (m, k)).astype(np.int8)
    b = rng.integers(-128, 128, (k, n)).astype(np.int8)
    spec = sub.ContractionSpec(partitioning=part)
    for name in ("approx_pallas", "approx_bitexact"):
        s = sub.get_substrate(name)
        one = np.asarray(s.dot_int(a, b))
        sharded = jax.jit(lambda x, y, s=s: s.dot_general(x, y, spec))
        out, dt = timed(sharded, a, b)
        devs = {sh.device for sh in out.addressable_shards}
        check(len(devs) == 4, f"{name}: sharded output on {len(devs)} "
              "devices, expected 4")
        check(np.array_equal(np.asarray(out), one),
              f"{name}: sharded dot_general differs from one device")
        if name == "approx_pallas":
            assert_mosaic(sharded, a, b, what="sharded approx_pallas")
        log(f"sharded {name} {m}x{k}@{k}x{n} on 4 devices == one device "
            f"(bit-identical; first call {dt:.2f} s)")
    shape = max({im.shape for im in imgs})
    big = [im for im in imgs if im.shape == shape][:8]
    with EdgeDetectService("approx_pallas", max_batch_size=8) as svc:
        ref = [got for _, got in serve(svc, big, "edge unsharded")]
    with EdgeDetectService("approx_pallas", max_batch_size=8,
                           partitioning=part) as svc:
        got = [g for _, g in serve(svc, big, "edge sharded")]
        out = svc._jit_fn(padded_batch(svc, big[0].shape))
        assert_mosaic(svc._jit_fn, padded_batch(svc, big[0].shape),
                      what="edge sharded")
    check(all(np.array_equal(x, y) for x, y in zip(got, ref)),
          "partitioned edge service differs from the unsharded maps")
    log(f"edge service partitioned over 4 devices: {len(got)} "
        f"{shape[1]}x{shape[0]} maps == unsharded (bit-identical); output on "
        f"{len({sh.device for sh in out.addressable_shards})} devices")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded contraction path on a 2x2 "
                         "mesh, against its one-device result")
    args = ap.parse_args()
    t_start = time.perf_counter()
    devs = device_check(4 if args.four_chips else 1)
    imgs = frames()
    if args.four_chips:
        phase_four_chips(imgs)
    else:
        phase_edge(imgs)
        phase_planned(imgs)
        phase_lut(imgs)
        phase_lm()
        phase_kernel()
    log(f"all phases passed in {time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
