"""Fault-tolerant training loop.

Production invariants, scaled to whatever mesh is present:

* **checkpoint/restart** — async checkpoints every ``ckpt_every`` steps;
  on (re)start the loop discovers the newest complete checkpoint, restores
  params/opt-state *with the current mesh's shardings* (elastic), and seeks
  the data stream to the exact step — bitwise-resumable.
* **failure injection** — ``fail_at_step`` raises mid-run (tests use it to
  prove crash→restart equivalence).
* **straggler mitigation** — step-time EWMA; steps slower than
  ``straggler_factor``× the EWMA are counted and surfaced in metrics (on a
  real cluster this signal feeds the scheduler; here it drives the metric
  surface + tests).
* **gradient compression** — optional int8 all-reduce via shard_map for the
  data-parallel axis (see ``dp_train_step_compressed``).
* **grad accumulation** — microbatching for global batches that exceed
  memory.
* **approximation-aware training** — set ``cfg.qat`` to a
  :class:`repro.train.qat.QATPolicy` (optionally with ``cfg.plan``) and the
  loss traces inside :func:`repro.train.qat.qat_scope`: every plan-resolved
  contraction runs the approximate substrate forward with a
  straight-through backward. A non-None ``cfg.plan`` *governs* the trace —
  the loss is traced inside
  :func:`repro.nn.plan.plan_override_scope(cfg.plan)`, so every
  plan-consulting contraction resolves through it regardless of what the
  model function was built with. The active plan + policy are recorded in
  each checkpoint manifest and re-applied on restore: an unset
  ``cfg.plan``/``cfg.qat`` adopts the checkpoint's (effectively — the
  adopted plan is installed in the trace, not just logged), a conflicting
  one raises. A resumed QAT run therefore cannot silently continue under
  different numerics (see docs/training.md).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.optim import grad_utils
from repro.optim.adamw import Optimizer


@dataclasses.dataclass
class TrainLoopConfig:
    total_steps: int = 100
    ckpt_every: int = 20
    ckpt_dir: str = "/tmp/repro_ckpt"
    keep: int = 3
    log_every: int = 10
    lr: float = 1e-3
    grad_clip: float = 1.0
    grad_accum: int = 1
    fail_at_step: Optional[int] = None       # fault-injection hook
    straggler_factor: float = 3.0
    async_ckpt: bool = True
    qat: Optional[Any] = None                # repro.train.qat.QATPolicy
    plan: Optional[Any] = None               # SubstratePlan / spec / dict


class TrainLoop:
    def __init__(self, loss_fn: Callable, optimizer: Optimizer,
                 cfg: TrainLoopConfig, lr_schedule: Optional[Callable] = None):
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.cfg = cfg
        if cfg.plan is not None:
            from repro.nn import plan as _plan_mod
            cfg.plan = _plan_mod.as_plan(cfg.plan)
        self.lr_schedule = lr_schedule or (lambda step: cfg.lr)
        self.ckpt = CheckpointManager(cfg.ckpt_dir, keep=cfg.keep)
        self.metrics: Dict[str, Any] = {"straggler_steps": 0, "resumed_from": None}
        self._step_fn = self._build_step()

    def _ckpt_extra(self) -> Dict[str, Any]:
        """Manifest record of the numerics this run trains under."""
        extra: Dict[str, Any] = {}
        if self.cfg.plan is not None:
            extra["plan"] = self.cfg.plan.to_dict()
        if self.cfg.qat is not None:
            extra["qat"] = self.cfg.qat.describe()
        return extra

    def _build_step(self):
        cfg = self.cfg

        def one_micro(params, batch):
            # trace-time ambients: entering the scopes inside the traced
            # body installs the plan + STE overrides for exactly this trace,
            # so cfg.plan/cfg.qat (including checkpoint-adopted values) are
            # what the contraction actually runs, not just what is logged
            with contextlib.ExitStack() as scopes:
                if cfg.plan is not None:
                    from repro.nn import plan as _plan_mod
                    scopes.enter_context(
                        _plan_mod.plan_override_scope(cfg.plan))
                if cfg.qat is not None:
                    from repro.train import qat as qat_mod
                    scopes.enter_context(qat_mod.qat_scope(cfg.qat))
                return jax.value_and_grad(self.loss_fn)(params, batch)

        def step(params, opt_state, batch, lr):
            if cfg.grad_accum == 1:
                loss, grads = one_micro(params, batch)
            else:
                def micro(i, carry):
                    acc_loss, acc_grads = carry
                    mb = jax.tree_util.tree_map(
                        lambda x: jax.lax.dynamic_slice_in_dim(
                            x, i * (x.shape[0] // cfg.grad_accum),
                            x.shape[0] // cfg.grad_accum, axis=0), batch)
                    l, g = one_micro(params, mb)
                    return (acc_loss + l,
                            jax.tree_util.tree_map(jnp.add, acc_grads, g))
                zero = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                loss, grads = jax.lax.fori_loop(
                    0, cfg.grad_accum, micro, (jnp.zeros((), jnp.float32), zero))
                scale = 1.0 / cfg.grad_accum
                loss = loss * scale
                grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            grads, gnorm = grad_utils.clip_by_global_norm(grads, cfg.grad_clip)
            new_params, new_state = self.optimizer.update(
                grads, opt_state, params, lr=lr)
            return loss, gnorm, new_params, new_state

        return jax.jit(step)

    # -- lifecycle -----------------------------------------------------------

    def init_or_restore(self, init_params_fn: Callable, shardings=None):
        """Fresh init, or restore newest checkpoint (elastic) + seek step."""
        params = init_params_fn()
        opt_state = self.optimizer.init(params)
        start_step = 0
        latest = self.ckpt.latest_step()
        if latest is not None:
            tree, step, extra = self.ckpt.restore(
                {"params": params, "opt": opt_state}, shardings=shardings)
            params, opt_state = tree["params"], tree["opt"]
            start_step = step
            self.metrics["resumed_from"] = step
            self._check_numerics(extra or {})
        return params, opt_state, start_step

    def _check_numerics(self, extra: Dict[str, Any]):
        """Refuse to resume under different numerics than the checkpoint's.

        A QAT checkpoint is only meaningful together with the plan/policy it
        trained under; an absent ``cfg.plan``/``cfg.qat`` adopts the
        checkpoint's, a conflicting one raises. Adoption is *effective*, not
        cosmetic: the adopted plan/policy land in ``cfg`` before the step
        function has traced, and the step traces the loss inside
        ``plan_override_scope(cfg.plan)`` / ``qat_scope(cfg.qat)`` — so the
        resumed contractions run the checkpoint's numerics even though the
        model function was built earlier. The step function is rebuilt on
        adoption so no previously traced program can be reused.
        """
        from repro.nn import plan as _plan_mod
        adopted = False
        saved_plan = extra.get("plan")
        if saved_plan is not None:
            saved = _plan_mod.as_plan(saved_plan)
            if self.cfg.plan is None:
                self.cfg.plan = saved
                adopted = True
            elif self.cfg.plan != saved:
                raise ValueError(
                    f"checkpoint was trained under plan {saved.label!r} "
                    f"but this run configures {self.cfg.plan.label!r}; "
                    "pass the matching --dot-plan (or none, to adopt the "
                    "checkpoint's)")
        saved_qat = extra.get("qat")
        if saved_qat is not None:
            from repro.train import qat as qat_mod
            saved_pol = qat_mod.QATPolicy.from_dict(saved_qat)
            if self.cfg.qat is None:
                # an approximate-plan resume without the checkpoint's STE
                # policy would run the integer forward un-wrapped: jnp.round
                # has zero gradient a.e. — silent training breakage, not a
                # numerics preference. Adopt, symmetric with the plan above.
                self.cfg.qat = saved_pol
                adopted = True
            elif self.cfg.qat != saved_pol:
                raise ValueError(
                    f"checkpoint QAT policy {saved_qat} differs from this "
                    f"run's {self.cfg.qat.describe()}")
        if adopted:
            self._step_fn = self._build_step()

    def run(self, params, opt_state, data_stream, start_step: int = 0,
            on_step: Optional[Callable] = None):
        cfg = self.cfg
        data_stream.seek(start_step)
        ewma = None
        losses = []
        step = start_step
        try:
            for step in range(start_step, cfg.total_steps):
                if cfg.fail_at_step is not None and step == cfg.fail_at_step:
                    raise RuntimeError(f"injected failure at step {step}")
                batch = {k: jnp.asarray(v) for k, v in data_stream.next().items()}
                t0 = time.time()
                lr = jnp.float32(self.lr_schedule(step))
                loss, gnorm, params, opt_state = self._step_fn(
                    params, opt_state, batch, lr)
                loss = float(loss)
                dt = time.time() - t0
                ewma = dt if ewma is None else 0.9 * ewma + 0.1 * dt
                if dt > cfg.straggler_factor * ewma and step > start_step + 3:
                    self.metrics["straggler_steps"] += 1
                losses.append(loss)
                if on_step:
                    on_step(step, loss)
                if (step + 1) % cfg.ckpt_every == 0:
                    tree = {"params": params, "opt": opt_state}
                    extra = self._ckpt_extra()
                    if cfg.async_ckpt:
                        self.ckpt.save_async(step + 1, tree, extra=extra)
                    else:
                        self.ckpt.save(step + 1, tree, extra=extra)
        finally:
            self.ckpt.wait()
        self.metrics["final_loss"] = losses[-1] if losses else None
        self.metrics["losses"] = losses
        return params, opt_state, step + 1


# ---------------------------------------------------------------------------
# shard_map data-parallel step with int8-compressed gradient all-reduce
# ---------------------------------------------------------------------------


def dp_train_step_compressed(loss_fn, optimizer, mesh, axis_name: str = "data",
                             compress: bool = True):
    """Explicit-collective DP step: per-shard grads → int8 psum → update.

    The pjit path reduces gradients implicitly; this shard_map variant makes
    the all-reduce explicit so it can be compressed (8× fewer gradient
    bytes on the wire — the paper's quantization theme applied to the
    collective layer).
    """
    from jax.sharding import PartitionSpec as P

    def sharded_step(params, opt_state, batch, lr):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if compress:
            grads = grad_utils.compressed_psum(grads, axis_name)
        else:
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, axis_name), grads)
        loss = jax.lax.pmean(loss, axis_name)
        new_params, new_state = optimizer.update(grads, opt_state, params, lr=lr)
        return loss, new_params, new_state

    pspec_batch = P(axis_name)
    return jax.jit(jax.shard_map(
        sharded_step, mesh=mesh,
        in_specs=(P(), P(), {"tokens": pspec_batch, "labels": pspec_batch}, P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    ))
