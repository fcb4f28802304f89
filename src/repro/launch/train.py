"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Wires config → model bundle → optimizer → fault-tolerant TrainLoop over a
mesh (production 16×16 / 2×16×16, or a debug mesh over local devices).
Reduced-size overrides make the same path runnable on one CPU for the
examples and tests.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from repro.data import SyntheticLMStream
from repro.launch import mesh as mesh_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry as reg
from repro.nn import plan as plan_mod
from repro.optim import adafactor, adamw, warmup_cosine
from repro.train import QATPolicy, TrainLoop, TrainLoopConfig


def parse_plan_arg(arg: str) -> plan_mod.SubstratePlan:
    """CLI plan argument: a spec string, inline plan JSON, or a JSON path."""
    arg = arg.strip()
    if arg.startswith("{"):
        return plan_mod.SubstratePlan.from_json(arg)
    if arg.endswith(".json"):
        return plan_mod.load_plan(arg)
    return plan_mod.as_plan(arg)


def add_reduced_overrides(ap: argparse.ArgumentParser):
    ap.add_argument("--n-layers", type=int, default=None)
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--d-ff", type=int, default=None)
    ap.add_argument("--vocab", type=int, default=None)
    ap.add_argument("--n-heads", type=int, default=None)
    ap.add_argument("--n-kv-heads", type=int, default=None)
    ap.add_argument("--n-experts", type=int, default=None)
    ap.add_argument("--dot-plan", default=None,
                    help="substrate plan: a uniform spec string (e.g. "
                         "'int8', 'approx_bitexact:proposed@6'), inline "
                         "plan JSON, or path to a plan .json (e.g. an "
                         "autotuner bundle's plan)")


def overrides_from(args) -> dict:
    keys = {"n_layers": args.n_layers, "d_model": args.d_model,
            "d_ff": args.d_ff, "vocab": args.vocab, "n_heads": args.n_heads,
            "n_kv_heads": args.n_kv_heads, "n_experts": args.n_experts}
    out = {k: v for k, v in keys.items() if v is not None}
    # any registered arch trains on an approximate substrate without a
    # dedicated config
    if args.dot_plan:
        out["dot_plan"] = parse_plan_arg(args.dot_plan)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=reg.list_archs())
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--mesh", choices=["none", "debug", "pod", "multipod"],
                    default="none")
    ap.add_argument("--metrics-out", default="")
    ap.add_argument("--qat", action="store_true",
                    help="approximation-aware training: straight-through "
                         "approximate forward on the configured plan")
    ap.add_argument("--qat-forward", choices=["bitexact", "stat"],
                    default="bitexact",
                    help="QAT forward numerics (stat = fast separable "
                         "error-moment model, same wiring+width)")
    ap.add_argument("--qat-moment", action="store_true",
                    help="add the error-moment slope correction to the "
                         "straight-through backward")
    ap.add_argument("--qat-out", default="",
                    help="directory for a final plan+params bundle "
                         "(checkpoint.save_plan_bundle)")
    add_reduced_overrides(ap)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = reg.get_config(args.arch, **overrides_from(args))
    bundle = reg._BUILDERS[cfg.family](cfg)
    optimizer = adafactor() if cfg.n_experts else adamw()

    qat_policy = (QATPolicy(forward=args.qat_forward,
                            moment_correction=args.qat_moment)
                  if args.qat else None)
    loop = TrainLoop(
        bundle.loss_fn, optimizer,
        TrainLoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                        ckpt_dir=args.ckpt_dir, lr=args.lr,
                        grad_accum=args.grad_accum,
                        qat=qat_policy, plan=cfg.dot_plan),
        lr_schedule=warmup_cosine(args.lr, max(1, args.steps // 10), args.steps),
    )
    stream = SyntheticLMStream(vocab=cfg.vocab, batch=args.batch,
                               seq_len=args.seq_len, seed=0)

    mesh = None
    if args.mesh == "debug":
        mesh = mesh_lib.make_debug_mesh()
    elif args.mesh == "pod":
        mesh = mesh_lib.make_production_mesh(multi_pod=False)
    elif args.mesh == "multipod":
        mesh = mesh_lib.make_production_mesh(multi_pod=True)

    def run():
        params, opt_state, start = loop.init_or_restore(
            lambda: bundle.init_params(jax.random.PRNGKey(0)))
        # read back from loop.cfg: restore may have adopted the checkpoint's
        # plan/policy, and what the loop traces is what should be reported
        qat_tag = (f" qat={loop.cfg.qat.forward}"
                   if loop.cfg.qat is not None else "")
        plan_tag = (f" plan={loop.cfg.plan.label}"
                    if loop.cfg.plan is not None else "")
        print(f"[train] arch={args.arch} start_step={start}{plan_tag}{qat_tag} "
              f"params={sum(x.size for x in jax.tree_util.tree_leaves(params)):,}")
        params, _, _ = loop.run(
            params, opt_state, stream, start,
            on_step=lambda s, l: (s % 10 == 0) and print(
                f"  step {s:5d} loss {l:.4f}", flush=True))
        if args.qat_out:
            from repro import checkpoint as ckpt_lib
            plan = loop.cfg.plan or plan_mod.SubstratePlan.uniform("exact")
            path = ckpt_lib.save_plan_bundle(
                args.qat_out, plan, params,
                extra={"arch": args.arch,
                       "final_loss": loop.metrics.get("final_loss"),
                       "qat": (loop.cfg.qat.describe()
                               if loop.cfg.qat is not None else None)})
            print(f"[train] wrote plan bundle: {path}")

    if mesh is not None:
        with mesh:
            run()
    else:
        run()

    fl = loop.metrics["final_loss"]
    print(f"[train] done: "
          f"final_loss={'n/a' if fl is None else format(fl, '.4f')} "
          f"stragglers={loop.metrics['straggler_steps']} "
          f"resumed_from={loop.metrics['resumed_from']}")
    if args.metrics_out:
        json.dump({k: v for k, v in loop.metrics.items() if k != "losses"} |
                  {"losses_head": loop.metrics["losses"][:5],
                   "losses_tail": loop.metrics["losses"][-5:]},
                  open(args.metrics_out, "w"), indent=1)


if __name__ == "__main__":
    main()
