"""End-to-end driver: train a ~100M-param LM for a few hundred steps with the
paper's approximate multiplier as the matmul execution mode, with
checkpointing + resume.

Default is a fast reduced run; pass --full for the ~100M/300-step version
(slow on 1 CPU).

Run: PYTHONPATH=src python examples/train_lm.py [--full]
"""
import argparse

import jax

from repro.data import SyntheticLMStream
from repro.models import registry as reg
from repro.optim import adamw, warmup_cosine
from repro.train import TrainLoop, TrainLoopConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="~100M params, 300 steps (slow on CPU)")
    ap.add_argument("--dot-plan", default="exact",
                    choices=["exact", "int8", "approx_stat", "approx_bitexact"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_example_ckpt")
    args = ap.parse_args()

    if args.full:
        over = dict(n_layers=8, d_model=768, d_ff=2048, vocab=32768,
                    n_heads=12, n_kv_heads=4, attn_chunk=256, loss_chunk=256)
        steps, batch, seq = 300, 8, 256
    else:
        over = dict(n_layers=2, d_model=128, d_ff=256, vocab=1024,
                    n_heads=4, n_kv_heads=2, attn_chunk=64, loss_chunk=64,
                    remat=False)
        steps, batch, seq = 60, 8, 64

    cfg = reg.get_config("minitron-8b", dot_plan=args.dot_plan, **over)
    bundle = reg._BUILDERS[cfg.family](cfg)

    loop = TrainLoop(
        bundle.loss_fn, adamw(),
        TrainLoopConfig(total_steps=steps, ckpt_every=max(10, steps // 5),
                        ckpt_dir=args.ckpt_dir, lr=3e-3),
        lr_schedule=warmup_cosine(3e-3, steps // 10, steps),
    )
    stream = SyntheticLMStream(vocab=cfg.vocab, batch=batch, seq_len=seq, seed=0)
    params, opt_state, start = loop.init_or_restore(
        lambda: bundle.init_params(jax.random.PRNGKey(0)))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"training {n_params:,} params from step {start} "
          f"(dot_plan={cfg.dot_plan}); checkpoints -> {args.ckpt_dir}")
    loop.run(params, opt_state, stream, start,
             on_step=lambda s, l: (s % 10 == 0) and print(
                 f"  step {s:4d}  loss {l:.4f}", flush=True))
    losses = loop.metrics["losses"]
    print(f"done: loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"(resume with the same command; delete {args.ckpt_dir} to restart)")


if __name__ == "__main__":
    main()
