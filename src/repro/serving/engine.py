"""Batched LM serving engine: prefill + greedy/temperature decode.

A single-process continuous-batching core: requests are padded into a fixed
batch, prefilled token-by-token through ``decode_step`` (uniform code path —
no separate prefill graph to keep per-request state simple), then decoded
until EOS/max_tokens. Per-slot state lives in the model's KV caches; the
queue/slot-refill bookkeeping is the shared
:class:`~repro.serving.batcher.SlotScheduler` (the same scheduling core the
vision micro-batcher builds on), and per-step occupancy plus per-request
latency land in a :class:`~repro.serving.metrics.ServingMetrics`, which
also counts the prompt tokens fed (``serving_lm_prompt_tokens_total``) and
the output tokens emitted (``serving_lm_tokens_total``). Each step is a
``serve.decode_step`` span holding ``lm.fetch`` (the logits' device→host
copy, split out under a tracer) and ``lm.sample`` (host sampling).

``generate(requests, workers=N)`` runs N concurrent decode loops, each with
its *own* KV caches, slot pool, and sampling RNG, all sharing the one
compiled ``decode_step`` (JAX compiled calls are thread-safe) and the one
metrics instance (``serving_worker_*`` families labeled ``lm-0..N-1``).
Requests split round-robin across loops. Greedy decodes of first-wave
requests (seated into fresh cache lanes) are bit-identical at every worker
count; a request seated into a *refilled* slot attends over the previous
occupant's cache prefix, so its tokens depend on scheduling order — a
pre-existing property of the shared-``cache_len`` engine that holds even
at ``workers=1`` (reordering requests changes refilled-slot outputs the
same way).

For the large-scale path, the *dry-run* lowers the dedicated ``prefill``
graph (chunked attention, full-sequence); this engine is the functional
small-scale server used by the examples and tests.

The engine accepts a ``substrate`` override — a ``repro.nn.substrate`` spec
or a per-site :class:`repro.nn.plan.SubstratePlan` — so int8 / approximate /
mixed-substrate serving experiments run against the same bundle + params
without touching the model registry.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import current_tracer, trace_span
from repro.serving.batcher import SlotScheduler
from repro.serving.metrics import ServingMetrics


@dataclasses.dataclass
class Request:
    prompt: List[int]
    max_tokens: int = 16
    temperature: float = 0.0
    eos_id: Optional[int] = None
    # filled by the engine
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class ServingEngine:
    def __init__(self, bundle, params, batch_size: int = 4,
                 max_len: int = 256, seed: int = 0, substrate=None,
                 metrics: Optional[ServingMetrics] = None):
        """substrate: optional override for the bundle's substrate
        assignment — a ProductSubstrate spec string (e.g. ``"int8"``,
        ``"approx_lut:design_du2022"``), a ProductSubstrate instance, or a
        :class:`repro.nn.plan.SubstratePlan` (or its dict/JSON schema) for
        per-site mixed-substrate serving. The bundle is rebuilt on the
        overridden config (``cfg.dot_plan``), so int8/approx/mixed serving
        experiments don't need a separate registry entry. Parameters are
        layout-compatible across substrates (the quantization boundary is
        dynamic), so the same ``params`` tree is served.
        metrics: optional shared :class:`ServingMetrics` (e.g. one backed by
        a shared registry for a combined export); a private one otherwise."""
        if substrate is not None:
            from repro.models import registry as reg
            from repro.nn import plan as plan_mod
            from repro.nn import substrate as psub

            spec = None
            if isinstance(substrate, (plan_mod.SubstratePlan, dict)):
                plan = plan_mod.as_plan(substrate)
            elif isinstance(substrate, str):
                spec = substrate
                plan = plan_mod.SubstratePlan.uniform(substrate)
            else:
                # the model path resolves by spec string (cfg.dot_mode), so a
                # substrate instance must be equivalent to what the registry
                # yields for its spec — a custom subclass would be silently
                # swapped out for the stock backend here
                spec = substrate.meta.spec
                stock = psub.get_substrate(spec)
                if type(stock) is not type(substrate) or \
                        stock.meta != substrate.meta:
                    raise ValueError(
                        f"substrate instance {substrate!r} does not match the "
                        f"registered backend for {spec!r}; pass a spec string "
                        "or register the backend first")
                plan = plan_mod.SubstratePlan.uniform(spec)
            # uniform overrides mirror the spec into cfg.dot_mode too, so
            # introspection (and pre-plan callers) keep seeing the spec
            over = {"dot_plan": plan}
            if spec is not None:
                over["dot_mode"] = spec
            bundle = reg.build_bundle(
                dataclasses.replace(bundle.cfg, **over))
        self.bundle = bundle
        self.cfg = bundle.cfg
        self.params = params
        self.batch = batch_size
        self.max_len = max_len
        self.seed = seed
        self.rng = np.random.default_rng(seed)
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self._decode = jax.jit(bundle.decode_step)
        self._reset_state()

    def _init_state(self):
        state = self.bundle.init_decode_state(self.batch, self.max_len)
        if self.cfg.family == "encdec":
            state["enc_out"] = jnp.zeros(
                (self.batch, self.cfg.n_frames, self.cfg.d_model), self.cfg.dtype)
        return state

    def _reset_state(self):
        self.state = self._init_state()

    def _step(self, state, tokens: np.ndarray, cache_len: int):
        """One compiled decode step; returns the (batch, vocab) float32
        logits on the host. Under a tracer the device→host copy is timed
        apart as ``lm.fetch`` (after the step's device work has finished);
        untraced, one ``np.asarray`` waits and copies."""
        batch = {"token": jnp.asarray(tokens.reshape(self.batch, 1), jnp.int32),
                 "cache_len": jnp.asarray(cache_len, jnp.int32)}
        logits, state = self._decode(self.params, state, batch)
        if current_tracer() is None:
            return np.asarray(logits, np.float32)[:, 0, :], state
        logits.block_until_ready()
        with trace_span("lm.fetch", "serving", bytes=int(logits.nbytes)):
            host = np.asarray(logits, np.float32)
        return host[:, 0, :], state

    def _sample(self, logits: np.ndarray, temps: np.ndarray,
                rng: np.random.Generator) -> np.ndarray:
        out = np.empty(self.batch, np.int64)
        for i in range(self.batch):
            if temps[i] <= 0:
                out[i] = logits[i].argmax()
            else:
                z = logits[i] / temps[i]
                z -= z.max()
                p = np.exp(z)
                p /= p.sum()
                out[i] = rng.choice(len(p), p=p)
        return out

    def generate(self, requests: List[Request],
                 workers: int = 1) -> List[Request]:
        """Serve a list of requests with continuous slot refill.

        ``workers > 1`` runs that many concurrent decode loops, each with
        its own KV caches and ``batch_size`` slots (requests split
        round-robin). Greedy outputs of first-wave requests are identical
        at any worker count (see the module docstring for the refilled-slot
        caveat).
        """
        if workers < 1:
            raise ValueError(f"workers must be >= 1, got {workers}")
        workers = min(workers, max(1, len(requests)))
        with trace_span("serve.generate", "serving", requests=len(requests),
                        workers=workers):
            if workers == 1:
                self._generate(requests, self.rng, worker="lm-0")
                return requests
            chunks = [requests[i::workers] for i in range(workers)]
            errors: List[BaseException] = []

            def run(i: int, chunk: List[Request]) -> None:
                try:
                    self._generate(chunk, np.random.default_rng(
                        (self.seed, i)), worker=f"lm-{i}")
                except BaseException as e:  # noqa: BLE001 - re-raised below
                    errors.append(e)

            threads = [threading.Thread(target=run, args=(i, c),
                                        name=f"lm-decode-{i}")
                       for i, c in enumerate(chunks)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errors:
                raise errors[0]
            return requests

    def _generate(self, requests: List[Request], rng: np.random.Generator,
                  worker: str = "lm-0") -> List[Request]:
        sched = SlotScheduler(self.batch)
        t_start = {}
        for r in requests:
            sched.submit(r)
            t_start[id(r)] = time.perf_counter()
            self.metrics.record_enqueue(len(sched.queue))

        # NOTE: the shared cache_len is the max over slots; per-slot masking
        # is handled by feeding pad tokens for idle slots (logits ignored).
        cache_len = 0
        served: set = set()                           # id(r) with metrics
        state = self._init_state()                    # this loop's KV caches
        cursor = np.zeros(self.batch, np.int64)       # prompt cursor
        while sched.busy and cache_len < self.max_len - 1:
            for i, r in sched.refill():
                if r.done:                           # e.g. re-submitted request
                    sched.release(i)
                    continue
                cursor[i] = 0                        # prompt starts here
            if not sched.occupancy:
                continue                             # nothing seated this step
            tokens = np.zeros(self.batch, np.int64)
            n_prompt = n_out = 0
            for i, r in sched.occupied():
                if r.done:
                    continue
                if cursor[i] < len(r.prompt):
                    tokens[i] = r.prompt[int(cursor[i])]
                    n_prompt += 1
                elif r.output:
                    tokens[i] = r.output[-1]
            self.metrics.record_batch(sched.occupancy, "decode", self.batch)
            t_step = time.perf_counter()
            with trace_span("serve.decode_step", "serving",
                            cache_len=cache_len, occupancy=sched.occupancy,
                            worker=worker):
                logits, state = self._step(state, tokens, cache_len)
                with trace_span("lm.sample", "serving"):
                    temps = np.array([r.temperature if r else 0.0
                                      for r in sched.slots])
                    nxt = self._sample(logits, temps, rng)
            self.metrics.record_worker_batch(
                worker, time.perf_counter() - t_step)
            for i, r in sched.occupied():
                if r.done:
                    continue
                cursor[i] += 1
                if cursor[i] >= len(r.prompt):       # past prefill: emit
                    tok = int(nxt[i])
                    r.output.append(tok)
                    n_out += 1
                    if (r.eos_id is not None and tok == r.eos_id) or \
                            len(r.output) >= r.max_tokens:
                        r.done = True
                        sched.release(i)
                        served.add(id(r))
                        self.metrics.record_done(
                            time.perf_counter() - t_start[id(r)],
                            depth=len(sched.queue))
            self.metrics.record_lm_tokens(n_prompt, n_out)
            cache_len += 1
        for r in requests:
            r.done = True
            if id(r) not in served:  # truncated by max_len / never seated
                self.metrics.record_done(
                    time.perf_counter() - t_start[id(r)], ok=False, depth=0)
        return requests
