"""Batched LM serving engine: token-by-token prefill, then decode with
per-request temperature.

Requests are padded into a fixed batch, prefilled token-by-token through
``decode_step`` (uniform code path — no separate prefill graph to keep
per-request state simple), then decoded until EOS/max_tokens. The batch
shares one ``cache_len``, so it serves waves: a slot refilled from the
queue mid-wave attends over its previous occupant's cache (below).
Per-slot state lives in the model's KV caches; the queue/slot-refill
bookkeeping is the shared :class:`~repro.serving.batcher.SlotScheduler`
(the same scheduling core the vision micro-batcher builds on), and
per-step occupancy plus per-request latency land in a
:class:`~repro.serving.metrics.ServingMetrics`, which
also counts the prompt tokens fed (``serving_lm_prompt_tokens_total``) and
the output tokens emitted (``serving_lm_tokens_total``).

Sampling runs on the device (:func:`sample_tokens`): a slot with
temperature <= 0 takes the argmax of its logits, any other draws from
``softmax(logits / T)`` by Gumbel-max. No sampling runs on the host, and
the engine copies only the batch's token ids there. Each step is a
``serve.decode_step`` span holding ``lm.sample`` (the sampler's dispatch
and its wait) and ``lm.fetch`` (the ids' device→host copy, ``bytes`` =
4 per slot).

``generate(requests, workers=N)`` runs N concurrent decode loops, each with
its *own* KV caches, slot pool, and sampling key (drawn once from its own
``np.random.Generator``; each step folds in the step index), all sharing
the one compiled ``decode_step`` (JAX compiled calls are thread-safe) and
the one metrics instance (``serving_worker_*`` families labeled
``lm-0..N-1``). Requests split round-robin across loops. Greedy decodes of
first-wave requests (seated into fresh cache lanes) are bit-identical at
every worker count; a request seated into a *refilled* slot attends over
the previous occupant's cache prefix, so its tokens depend on scheduling
order — a pre-existing property of the shared-``cache_len`` engine that
holds even at ``workers=1`` (reordering requests changes refilled-slot
outputs the same way).

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


class DeviceLogits:
    """A decode step's (batch, vocab) float32 logits, left on the device.

    JAX takes the device array as it is (``jnp.asarray``). Numpy reads a
    read-only host copy, fetched once and kept by the array
    (``np.asarray``, ``np.stack``, indexing), so reading column blocks of
    many steps copies each step once; ``copy()`` is a host array the
    caller may alter."""

    __slots__ = ("array",)

    def __init__(self, array: jax.Array):
        self.array = array

    def __jax_array__(self) -> jax.Array:
        return self.array

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        return np.asarray(self.array, dtype)

    def __getitem__(self, idx) -> np.ndarray:
        return np.asarray(self.array)[idx]

    def copy(self) -> np.ndarray:
        return np.array(self.array)


@jax.jit
def sample_tokens(logits: jax.Array, temps: jax.Array, key: jax.Array,
                  step) -> jax.Array:
    """(batch,) int32 ids from (batch, vocab) logits, in float32: the
    argmax (first on ties) where ``temps <= 0``, else an exact draw from
    ``softmax(logits / temps)`` by Gumbel-max, with ``step`` folded into
    ``key``."""
    logits = logits.astype(jnp.float32)
    temps = temps.astype(jnp.float32)
    greedy = temps <= 0
    drawn = jax.random.categorical(
        jax.random.fold_in(key, step),
        logits / jnp.where(greedy, 1.0, temps)[:, None], axis=-1)
    return jnp.where(greedy, jnp.argmax(logits, axis=-1),
                     drawn).astype(jnp.int32)


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

            if isinstance(substrate, (plan_mod.SubstratePlan, dict, str)):
                plan = plan_mod.as_plan(substrate)
            else:
                # the model path resolves every site by spec string, so a
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
            bundle = reg.build_bundle(
                dataclasses.replace(bundle.cfg, dot_plan=plan))
        self.bundle = bundle
        self.cfg = bundle.cfg
        self.params = params
        self.batch = batch_size
        self.max_len = max_len
        self.seed = seed
        self.rng = np.random.default_rng(seed)
        self.metrics = metrics if metrics is not None else ServingMetrics()

        def decode_step(params, state, batch):
            logits, state = bundle.decode_step(params, state, batch)
            return logits[:, 0, :].astype(jnp.float32), state

        self._decode = jax.jit(decode_step)
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
        logits as :class:`DeviceLogits`, left on the device. Untraced it
        does not wait; under a tracer it waits for the step's device work,
        so the spans after it hold host time only."""
        batch = {"token": jnp.asarray(tokens.reshape(self.batch, 1), jnp.int32),
                 "cache_len": jnp.asarray(cache_len, jnp.int32)}
        logits, state = self._decode(self.params, state, batch)
        if current_tracer() is not None:
            logits.block_until_ready()
        return DeviceLogits(logits), state

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
        key = jax.random.PRNGKey(int(rng.integers(2**31)))  # this loop's draws
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
                temps = np.array([r.temperature if r else 0.0
                                  for r in sched.slots], np.float32)
                with trace_span("lm.sample", "serving"):
                    # jnp.asarray, not .array: a wrapped _step may hand
                    # back host logits
                    ids = sample_tokens(jnp.asarray(logits), temps, key,
                                        cache_len)
                    ids.block_until_ready()
                with trace_span("lm.fetch", "serving", bytes=int(ids.nbytes)):
                    nxt = np.asarray(ids)
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
