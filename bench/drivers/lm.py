"""LM decode cells: closed-loop waves of requests into ``ServingEngine``.

The model is the configuration's ``arch`` from the program's registry with
the file's ``model`` values over it; its weights are drawn from the seed.
The window serves the traffic's waves one after another, each through
``ServingEngine.generate``; a step that would start after the window ends
is not run. Every step's fed tokens and host logits are kept, and after
the window every request the window seated is compared, logit for logit at
every position it was fed, with the plain reference
(``bench.reference.lm``) run on the tokens the program fed.

End to end, a frame of this cell is one decode step: the batch's forward,
the logits' fetch and the sampling, which hands one token to every seated
request. ``frames_per_s`` is the steps the window ran over the time they
took, and ``frame_p95_ms`` the 95th percentile of the time from one step's
start to the next's, the gap between a request's tokens.
"""
from __future__ import annotations

import contextlib
import importlib
import time

import numpy as np

from bench.reference import lm as ref_lm

NORM_LEAVES = ("ln", "ln_b", "ln_f", "ln_f_b")


class WindowOver(Exception):
    """The window ended before this decode step."""


def model_config(config):
    from repro.models import registry as reg

    return reg.get_config(config["arch"], **config["model"])


def randomize_norms(params, key, std: float):
    """The norms' parameters plus N(0, std²) noise, so γ and β take part."""
    import jax

    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = iter(jax.random.split(key, len(leaves)))
    out = [x + std * jax.random.normal(next(keys), x.shape, x.dtype)
           if str(getattr(path[-1], "key", "")) in NORM_LEAVES else x
           for path, x in leaves]
    return jax.tree_util.tree_unflatten(tree, out)


def reference_params(cfg, params) -> dict:
    """The program's parameter tree in the reference's layout."""
    from repro.models import lm

    if lm.unit_period(cfg) != 1 or params["tail"]:
        raise ValueError(f"{cfg.name}: the reference takes one layer kind")
    u, e = params["unit"][0], params["embed"]
    a, f = u["attn"], u["ffn"]
    layers = [{"ln1": (a["ln"][i], a["ln_b"][i]),
               "wq": a["wq"]["w"][i], "wk": a["wk"]["w"][i],
               "wv": a["wv"]["w"][i], "wo": a["wo"]["w"][i],
               "ln2": (f["ln"][i], f["ln_b"][i]),
               "w_up": f["wi"]["w"][i], "w_down": f["wo"]["w"][i]}
              for i in range(cfg.n_layers)]
    return {"emb": e["emb"], "head": e["head"]["w"],
            "ln_f": (e["ln_f"], e["ln_f_b"]), "layers": layers}


def reference_dims(cfg) -> dict:
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.dh, "rotary_pct": cfg.rotary_pct,
            "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps}


def setup(rec: dict) -> None:
    import jax

    from repro.models import registry as reg
    from repro.serving import ServingEngine
    from repro.serving.engine import Request

    config, traffic, rng = rec["config"], rec["traffic"], rec["rng"]
    cfg = model_config(config)            # an unknown field fails here
    rec["model_cfg"] = cfg
    rec["matmul_precision"] = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision",
                      config["precision"]["matmul"])
    bundle = reg.build_bundle(cfg)
    k_init, k_norm = jax.random.split(
        jax.random.PRNGKey(int(rng.integers(2**31))))
    params = jax.jit(bundle.init_params)(k_init)
    rec["params"] = jax.block_until_ready(
        randomize_norms(params, k_norm, config["norm_init_std"]))
    spec = config["control_substrate"] if rec["control"] else config["substrate"]
    rec["spec"] = spec
    eng = ServingEngine(bundle, rec["params"], seed=rec["seed"],
                        substrate=spec, **config["engine"])
    rec["engine"] = eng
    rec["engine_max_len"] = eng.max_len
    # compile the one decode-step shape, and warm the fetch and sampling
    eng.generate([Request(prompt=[1], max_tokens=3,
                          temperature=traffic["temperature"])
                  for _ in range(eng.batch)])
    arrivals = importlib.import_module(f"bench.arrivals.{traffic['kind']}")
    rec["schedule"] = [
        [(rng.integers(1, cfg.vocab, p).tolist(), o) for p, o in wave]
        for wave in arrivals.schedule(traffic, rec["seconds"], rng)]
    eng.metrics.reset()


def measure(rec: dict, trace_dir) -> None:
    import jax

    from repro.obs.trace import Tracer, tracing_scope
    from repro.serving.engine import Request

    eng, seconds = rec["engine"], rec["seconds"]
    temperature = rec["traffic"]["temperature"]
    tracer = Tracer() if trace_dir else None
    waves, marks = [], []                 # marks: (time, tokens emitted)
    starts = []                           # the start of every step run
    inner = eng._step
    t_end = float("inf")

    def step(state, tokens, cache_len):
        now = time.perf_counter()
        marks.append((now, eng.metrics.lm_tokens))
        if now >= t_end:
            raise WindowOver
        starts.append(now)
        logits, state = inner(state, tokens, cache_len)
        waves[-1]["tokens"].append(tokens.copy())
        waves[-1]["logits"].append(logits)
        waves[-1]["state"] = state          # its KV caches, read after the window
        return logits, state

    eng._step = step
    try:
        with contextlib.ExitStack() as stack:
            if trace_dir:
                stack.enter_context(tracing_scope(tracer))
                jax.profiler.start_trace(trace_dir)
            t0 = time.perf_counter()
            t_end = t0 + seconds
            with jax.profiler.TraceAnnotation("bench.window"):
                for wave in rec["schedule"]:
                    if time.perf_counter() >= t_end:
                        break
                    reqs = [Request(prompt=p, max_tokens=o,
                                    temperature=temperature) for p, o in wave]
                    waves.append({"requests": reqs, "tokens": [], "logits": []})
                    try:
                        eng.generate(reqs)
                    except WindowOver:
                        break
            marks.append((time.perf_counter(), eng.metrics.lm_tokens))
            if trace_dir:
                jax.profiler.stop_trace()
    finally:
        eng._step = inner
    rec["waves"] = waves
    rec["lm_tokens_in_window"] = max(n for t, n in marks if t <= t_end)
    rec["lm_prompt_tokens"] = eng.metrics.lm_prompt_tokens
    rec["spans"] = tracer.events() if tracer else []
    rec["attempted"] = sum(len(w["requests"]) for w in waves)
    rec["failed"] = 0
    # the last step ends where the next was refused or the waves ran out
    rec["end_to_end"] = frame_metrics(starts, t0, marks[-1][0])
    steps = sum(len(w["tokens"]) for w in waves)
    rec["notes"].append(
        f"window: {len(waves)} waves, {steps} steps, "
        f"{rec['lm_tokens_in_window']} tokens emitted by its end "
        f"({rec['lm_tokens_in_window'] / seconds:.3f} tokens/s)")


def frame_metrics(starts, t0: float, t_stop: float) -> dict:
    """``frames_per_s`` and ``frame_p95_ms`` of a window that opened at
    ``t0``, ran decode steps from each time in ``starts``, and whose last
    step ended by ``t_stop``."""
    if not starts:
        return {}
    gaps = np.diff(np.append(starts, t_stop))
    return {"frames_per_s": len(starts) / (t_stop - t0),
            "frame_p95_ms": float(np.percentile(gaps, 95)) * 1e3}


def engine_first_kv(state) -> tuple:
    """The first layer's (keys, values) of the engine's KV caches, each
    (rows, positions, kv heads, head dim)."""
    (keys, values), = state["unit"]
    return keys[0], values[0]


def rel_l2(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.square(got - want).sum() / np.square(want).sum()))


def compare(rec: dict) -> dict:
    """Per request seated in the window: the logits the program produced
    at every position it was fed, against the reference's. Returns the
    worst relative L2 error of a request (over its positions and the
    vocabulary), the worst position (its largest |error| over the
    reference's RMS logit there), and the worst relative L2 error of a
    wave's first-layer KV cache (every row, every position written), with
    what was compared."""
    cfg = rec["model_cfg"]
    rparams = reference_params(cfg, rec["params"])
    dims = reference_dims(cfg)
    linear = rec["config"]["reference"]["linear"]
    rel = worst = kv = 0.0
    n_req = n_waves = n_pos = 0
    for w in rec["waves"]:
        if not w["tokens"]:
            continue
        fed = np.stack(w["tokens"], axis=1)              # (rows, steps)
        rows, steps = fed.shape
        # every wave at the cache's length, so the reference compiles once;
        # later positions change nothing before them
        padded = np.pad(fed, ((0, 0), (0, rec["engine_max_len"] - steps)))
        want = ref_lm.first_layer_kv(rparams, padded, dims, linear)
        kv = max(kv, rel_l2([x[:, :steps] for x in engine_first_kv(w["state"])],
                            [x[:, :steps] for x in want]))
        npos = [min(steps, len(r.prompt) + len(r.output) - 1)
                for r in w["requests"]]
        for i, (r, n) in enumerate(zip(w["requests"], npos)):
            if fed[i, :n].tolist() != (r.prompt + r.output)[:n]:
                raise AssertionError(f"slot {i} was not fed its request")
        sq_d = np.zeros((rows, steps))
        sq_r = np.zeros((rows, steps))
        mx = np.zeros((rows, steps))
        for lo, hi, ref in ref_lm.logits(rparams, padded, dims, linear):
            ref = np.asarray(ref[:, :steps], np.float64)
            got = np.stack([x[:, lo:hi] for x in w["logits"]], axis=1)
            d = got - ref
            sq_d += np.square(d).sum(-1)
            sq_r += np.square(ref).sum(-1)
            mx = np.maximum(mx, np.abs(d).max(-1))
        for i, n in enumerate(npos):
            if n <= 0:
                continue
            rel = max(rel, float(np.sqrt(sq_d[i, :n].sum() / sq_r[i, :n].sum())))
            rms = np.sqrt(sq_r[i, :n] / cfg.vocab)
            worst = max(worst, float((mx[i, :n] / rms).max()))
            n_req += 1
            n_pos += n
        n_waves += 1
        w["logits"] = w["state"] = None      # judged; let the memory go
    return {"logit_rel_l2": rel, "logit_worst_position": worst,
            "kv_layer0_rel_l2": kv,
            "requests": n_req, "waves": n_waves, "positions": n_pos}


def check(rec: dict) -> list:
    """[(name, value, limit)] of the logit and first-layer cache
    comparisons."""
    import gc

    import jax

    del rec["engine"]
    gc.collect()
    try:
        got = compare(rec)
    finally:
        del rec["params"]
        gc.collect()
        jax.config.update("jax_default_matmul_precision",
                          rec["matmul_precision"])
    rec["notes"].append(
        f"compared {got['requests']} requests of {got['waves']} waves at "
        f"{got['positions']} positions")
    lim = rec["config"]["limits"]
    return [(n, got[n], lim[n]) for n in ("logit_rel_l2", "logit_worst_position",
                                          "kv_layer0_rel_l2")]
