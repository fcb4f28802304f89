"""Kernel micro-benchmarks (beyond paper): product-substrate sweep.

Times the integer contraction (``dot_int``) of every substrate registered
in ``repro.nn.substrate`` — no hand-maintained mode list — on CPU. Pallas
substrates run in interpret mode here (wall-clock kernel numbers only mean
something on real TPU); the XLA modes give the CPU-comparable throughput
picture and the relative cost of bit-exact emulation.

Beyond the substrate sweep, this bench times the PR-6 kernel pipeline:

* vectorized k-slab (``k_chunk=8``) vs the scalar fori baseline
  (``k_chunk=1``) for both the generated closed-form matmul and the
  flat-LUT gather matmul;
* the fused conv kernel (in-kernel im2col) vs the host-side
  im2col + ``dot_general`` reference path.

Every row also lands in a machine-readable ``BENCH_kernels.json``
(wall-clock µs after warmup, ``block_until_ready``-fenced, keyed by
kernel × wiring × width) next to the repo root so runs are diffable.

``sharded=True`` (``benchmarks.run --only kernel --sharded``) adds a
``dot_general`` + ``Partitioning`` sweep over a debug mesh of every visible
device (data-parallel M, reduce-scattered K) — the TPU-native benchmark run
uses it to sweep sharded contractions; under
``--xla_force_host_platform_device_count=N`` it exercises the same lowering
on CPU.

Standalone: ``python -m benchmarks.kernelbench [--dry-run] [--sharded]
[--substrates a,b] [--json PATH]`` — ``--dry-run`` shrinks every shape so
the whole bench (interpret mode included) finishes in seconds; CI uses it
as a smoke gate.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.nn import substrate as sub

_REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
DEFAULT_JSON = _REPO_ROOT / "BENCH_kernels.json"


def _time(f, *args, iters=5):
    f(*args)[0].block_until_ready() if isinstance(f(*args), tuple) else \
        f(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*args)
        jax.tree_util.tree_leaves(out)[0].block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def _sharded_rows(specs, a8, b8, macs, records) -> list:
    """dot_general + Partitioning sweep over a debug mesh of all devices."""
    from repro.launch import mesh as mesh_lib

    rows = []
    mesh = mesh_lib.make_debug_mesh()
    part = mesh_lib.contraction_partitioning(mesh)
    print(f"\n== kernel bench: sharded dot_general "
          f"(mesh {dict(mesh.shape)}, m_axis={part.m_axis}, "
          f"k_axis={part.k_axis}) ==")
    for spec in specs:
        s = sub.get_substrate(spec)
        cspec = sub.ContractionSpec(partitioning=part)
        f = jax.jit(lambda a, b, _s=s, _c=cspec: _s.dot_general(a, b, _c))
        us = _time(f, a8, b8)
        gmacs = macs / us / 1e3
        print(f"{spec:>16s}: {us:10.0f} us  ({gmacs:6.2f} GMAC/s) [sharded]")
        rows.append((f"kernel/sharded_{s.meta.label}", us,
                     f"gmacs={gmacs:.2f};devices={mesh.size}"))
        records.append({"section": "sharded", "kernel": "dot_general",
                        "spec": spec, "us": round(us, 1),
                        "gmacs": round(gmacs, 3), "devices": mesh.size})
    return rows


def _kslab_rows(rng, records, dry_run) -> list:
    """Vectorized k-slab (k_chunk=8) vs the fori baseline (k_chunk=1)."""
    from repro.core import lut as lut_lib
    from repro.kernels.approx_matmul.ops import closed_form_matmul
    from repro.kernels.lut_matmul.ops import lut_matmul

    m = k = n = 32 if dry_run else 128
    blk = dict(block_m=m, block_n=n, block_k=k)
    a = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
    b = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int8)
    macs = m * k * n
    rows = []
    print(f"\n== kernel bench: k-slab vectorization ({m}x{k}x{n}, "
          f"k_chunk=8 vs fori k_chunk=1) ==")
    for kernel, fn in (
        ("closed_form_matmul",
         lambda kc: closed_form_matmul(a, b, "proposed", k_chunk=kc, **blk)),
        ("lut_matmul",
         lambda kc, _t=jnp.asarray(lut_lib.flat_lut("proposed"), jnp.int32):
         lut_matmul(a, b, _t, k_chunk=kc, **blk)),
    ):
        base = None
        for kc in (1, 8):
            us = _time(fn, kc)
            gmacs = macs / us / 1e3
            tag = "fori" if kc == 1 else "vectorized"
            speedup = (base / us) if base else 1.0
            if kc == 1:
                base = us
            print(f"{kernel:>20s} k_chunk={kc} ({tag:>10s}): {us:10.0f} us  "
                  f"({gmacs:6.2f} GMAC/s, {speedup:4.2f}x vs fori)")
            rows.append((f"kernel/kslab_{kernel}_kc{kc}", us,
                         f"gmacs={gmacs:.2f};speedup={speedup:.2f}x"))
            records.append({"section": "kslab", "kernel": kernel,
                            "wiring": "proposed", "width": 8, "k_chunk": kc,
                            "shape": [m, k, n], "us": round(us, 1),
                            "gmacs": round(gmacs, 3),
                            "speedup_vs_fori": round(speedup, 3)})
    return rows


def _fused_conv_rows(rng, records, dry_run) -> list:
    """Fused conv kernel (in-kernel im2col) vs host-side im2col path."""
    from repro.nn import conv

    b, h, w = (2, 32, 32) if dry_run else (4, 128, 128)
    imgs = jnp.asarray(rng.integers(-128, 128, (b, h, w)), jnp.int32)
    s = sub.get_substrate("approx_pallas:proposed")
    rows = []
    print(f"\n== kernel bench: fused conv vs im2col ({b}x{h}x{w}, "
          f"3x3 Laplacian) ==")
    base = None
    for fused, tag in ((False, "im2col"), (True, "fused")):
        f = jax.jit(lambda x, _f=fused: conv.conv2d_batched(
            x, conv.LAPLACIAN, s, fused=_f))
        us = _time(f, imgs)
        speedup = (base / us) if base else 1.0
        if not fused:
            base = us
        print(f"{tag:>10s}: {us:10.0f} us  ({speedup:4.2f}x vs im2col)")
        rows.append((f"kernel/conv_{tag}", us,
                     f"imgs={b}x{h}x{w};speedup={speedup:.2f}x"))
        records.append({"section": "fused_conv", "kernel": f"conv_{tag}",
                        "wiring": "proposed", "width": 8,
                        "shape": [b, h, w], "us": round(us, 1),
                        "speedup_vs_im2col": round(speedup, 3)})
    return rows


def run(substrates=None, sharded=False, dry_run=False,
        json_path=DEFAULT_JSON) -> list:
    rows = []
    records: list[dict] = []
    rng = np.random.default_rng(0)
    m, k, n = (32, 64, 32) if dry_run else (256, 512, 256)
    a8 = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
    b8 = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int8)
    specs = list(substrates) if substrates else sub.list_substrates()
    print(f"\n== kernel bench: int8 matmul substrates ({m}x{k}x{n}, CPU) ==")
    macs = m * k * n
    for spec in specs:
        s = sub.get_substrate(spec)
        f = jax.jit(lambda a, b, _s=s: _s.dot_int(a, b))
        us = _time(f, a8, b8)
        gmacs = macs / us / 1e3
        note = " [interpret]" if s.meta.preferred_backend == "tpu" \
            and jax.default_backend() != "tpu" else ""
        print(f"{spec:>16s}: {us:10.0f} us  ({gmacs:6.2f} GMAC/s){note}")
        rows.append((f"kernel/matmul_{s.meta.label}", us, f"gmacs={gmacs:.2f}"))
        records.append({"section": "substrates", "kernel": "dot_int",
                        "spec": spec, "shape": [m, k, n], "us": round(us, 1),
                        "gmacs": round(gmacs, 3),
                        "cost_hint": s.meta.cost_hint})

    if sharded:
        rows.extend(_sharded_rows(specs, a8, b8, macs, records))

    # pallas × wiring × width sweep: every CSP wiring rides the generated
    # closed-form kernel (cost_hint "vpu"); only product models without CSP
    # structure ("exact") fall back to the LUT kernel ("mxu").
    pm = pk = pn = 32 if dry_run else 128
    pa = jnp.asarray(rng.integers(-128, 128, (pm, pk)), jnp.int8)
    pb = jnp.asarray(rng.integers(-128, 128, (pk, pn)), jnp.int8)
    pmacs = pm * pk * pn
    print(f"\n== kernel bench: pallas wiring x width sweep ({pm}x{pk}x{pn}) ==")
    for wiring in ("proposed", "csp_axc1", "design_strollo2020"):
        for width in (4, 8):
            spec = f"approx_pallas:{wiring}@{width}"
            s = sub.get_substrate(spec)
            f = jax.jit(lambda a, b, _s=s: _s.dot_int(a, b))
            us = _time(f, pa, pb)
            gmacs = pmacs / us / 1e3
            note = " [interpret]" if jax.default_backend() != "tpu" else ""
            print(f"{spec:>34s}: {us:10.0f} us  ({gmacs:6.2f} GMAC/s) "
                  f"[{s.meta.cost_hint}]{note}")
            rows.append((f"kernel/pallas_{wiring}@{width}", us,
                         f"gmacs={gmacs:.2f};cost={s.meta.cost_hint}"))
            records.append({"section": "pallas_sweep", "kernel": "dot_int",
                            "wiring": wiring, "width": width,
                            "shape": [pm, pk, pn], "us": round(us, 1),
                            "gmacs": round(gmacs, 3),
                            "cost_hint": s.meta.cost_hint})

    rows.extend(_kslab_rows(rng, records, dry_run))
    rows.extend(_fused_conv_rows(rng, records, dry_run))

    from repro.kernels.approx_mul.ops import approx_mul
    side = 64 if dry_run else 512
    x = jnp.asarray(rng.integers(-128, 128, (side, side)), jnp.int32)
    y = jnp.asarray(rng.integers(-128, 128, (side, side)), jnp.int32)
    us = _time(approx_mul, x, y)
    rows.append(("kernel/approx_mul_pallas_interp", us, f"{side}x{side}"))
    records.append({"section": "elementwise", "kernel": "approx_mul",
                    "wiring": "proposed", "width": 8, "shape": [side, side],
                    "us": round(us, 1)})
    print(f"pallas approx_mul (interpret): {us:.0f} us")

    if json_path:
        payload = {
            "bench": "kernelbench",
            "backend": jax.default_backend(),
            "interpret": jax.default_backend() != "tpu",
            "dry_run": bool(dry_run),
            "timing": "mean wall-clock us over 5 iters, "
                      "1 warmup + block_until_ready",
            "records": records,
        }
        pathlib.Path(json_path).write_text(json.dumps(payload, indent=1)
                                           + "\n")
        print(f"\nwrote {len(records)} records to {json_path}")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny shapes — seconds-fast smoke run (CI gate)")
    ap.add_argument("--sharded", action="store_true",
                    help="add sharded dot_general rows (debug mesh)")
    ap.add_argument("--substrates", default=None,
                    help="CSV of substrate specs (default: all registered)")
    ap.add_argument("--json", default=str(DEFAULT_JSON), dest="json_path",
                    help="output path for BENCH_kernels.json ('' disables)")
    args = ap.parse_args()
    substrates = args.substrates.split(",") if args.substrates else None
    rows = run(substrates=substrates, sharded=args.sharded,
               dry_run=args.dry_run, json_path=args.json_path or None)
    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
