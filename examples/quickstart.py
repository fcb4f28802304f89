"""Quickstart: the paper's approximate multiplier in 60 seconds.

Run: PYTHONPATH=src python examples/quickstart.py [--plan path.json]

``--plan`` loads a per-site substrate plan (a plan JSON or a bundle dir —
e.g. one written by ``python -m repro.launch.autotune``) for the final
mixed-substrate edge-detection step; without it a small hand-written
mixed plan demonstrates the same API.
"""
import argparse

import jax.numpy as jnp
import numpy as np

from repro.core import energy, lut, metrics, multiplier as m
from repro.nn import ContractionSpec, QuantPolicy, get_substrate


def main(plan_path=None):
    # 1. multiply two signed 8-bit numbers with the paper's multiplier
    a, b = jnp.int32(-97), jnp.int32(45)
    print(f"exact   {int(a)} x {int(b)} = {int(a) * int(b)}")
    print(f"approx  {int(a)} x {int(b)} = {int(m.approx_multiply(a, b))}")

    # 2. its exhaustive error metrics (paper Table 4)
    rep = metrics.evaluate(m.approx_multiply, "proposed")
    print(f"\n{rep.row()}")
    print("paper:   ER=98.04%  NMED=0.682%  MRED=26.29%")

    # 3. hardware savings vs the best existing design (paper Table 5)
    s = energy.savings_vs("proposed", "design_du2022")
    print(f"\npower saving vs [2]: {s['power']:.1f}% (paper 14.39%), "
          f"PDP: {s['pdp']:.1f}% (paper 29.21%)")

    # 4. use it as a neural-net matmul execution mode
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
    spec = ContractionSpec(quant=QuantPolicy())
    y_exact = get_substrate("exact").dot_general(x, w, spec)
    y_approx = get_substrate("approx_bitexact").dot_general(x, w, spec)
    rel = float(jnp.linalg.norm(y_approx - y_exact) / jnp.linalg.norm(y_exact))
    print(f"\napproximate matmul relative error vs float: {rel:.4f}")

    # 5. the deployment LUT artifact
    table = lut.build_lut("proposed")
    print(f"\n256x256 product LUT built; f(0,0) = {table[128, 128]} "
          "(the compensation constant fires on zero operands — true to the netlist)")

    # 6. per-site substrate plans: mixed-substrate edge detection
    import pathlib

    from repro.data import test_image
    from repro.nn import conv
    from repro.nn.plan import SubstratePlan, load_plan

    if plan_path:
        p = pathlib.Path(plan_path)
        if p.is_dir():
            from repro.checkpoint import load_plan_bundle
            plan, _, _ = load_plan_bundle(str(p))
        else:
            plan = load_plan(str(p))
    else:  # cheaper center tap, full-width smoothing ring
        plan = SubstratePlan(
            default="approx_bitexact:proposed@8",
            rules=(("conv.edge.center", "approx_bitexact:proposed@6"),))
    img = test_image(96, 96)[None]
    ref = np.asarray(conv.edge_detect_batched(img, "exact"))
    planned = np.asarray(conv.edge_detect_planned(img, plan))
    print(f"\nplanned edge detection under {plan.label}: "
          f"PSNR={conv.psnr(ref, planned):.2f} dB vs exact")
    for pattern, spec in plan.rules:
        print(f"  {pattern} -> {spec}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--plan", default=None, metavar="PATH",
                    help="substrate plan JSON or bundle dir for step 6")
    main(plan_path=ap.parse_args().plan)
