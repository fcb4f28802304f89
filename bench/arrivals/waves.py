"""Closed-loop waves of LM requests: a wave of ``batch`` requests is seated
together, and the next when the last of it has ended.

Traffic keys: ``batch``; ``waves``, how many are offered (the window serves
those that fit); ``prompt_tokens`` and ``output_tokens``, inclusive
``[lo, hi]`` ranges. Every wave holds the same lengths, the ``batch``
stratified quantiles of each uniform range, paired in an order drawn from
``rng``. Returns ``[[(prompt_len, output_len), ...] per wave]``.
"""
import numpy as np


def quantiles(lo: int, hi: int, n: int) -> np.ndarray:
    """The (i + 1/2)/n quantiles of the uniform integers lo..hi."""
    return lo + ((np.arange(n) + 0.5) / n * (hi - lo + 1)).astype(int)


def schedule(traffic, seconds, rng):
    b = int(traffic["batch"])
    prompts = quantiles(*traffic["prompt_tokens"], b)
    outputs = quantiles(*traffic["output_tokens"], b)
    return [[(int(p), int(o)) for p, o in zip(rng.permutation(prompts),
                                              rng.permutation(outputs))]
            for _ in range(int(traffic["waves"]))]
