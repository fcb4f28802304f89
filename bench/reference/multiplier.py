"""The paper's proposed 8-bit approximate signed multiplier, from its PPM.

Built bit by bit from the Baugh-Wooley partial-product matrix of two signed
8-bit operands, as the paper describes it:

* the N-1 = 7 least significant columns are truncated and replaced by the
  compensation constant (N-2)·2^(N-3) = 192;
* column 7 holds two sign-focused compressors: an approximate A+B+C+D+1
  (A = ¬(a0·b7), B, C, D = a1·b6, a2·b5, a3·b4, its "+1" the 2^7 bit of the
  compensation) with carry = A|B|C|D and sum = ¬(A·¬B·¬C·¬D), and an exact
  A+B+C+1 whose "+1" replaces ¬(a7·b0) (NAND converted to constant 1);
* column 8 holds an exact A+B+C+D+1 whose "+1" is the Baugh-Wooley 2^N
  constant; every other bit is reduced exactly.

``TABLE[a + 128, b + 128]`` is the product of ``a`` and ``b``.
:func:`approx_dot` contracts int8 matrices under it on the MXU, exactly,
through a decomposition of ``f(a, b)`` into products of small integer
factors (:func:`terms`).
"""
from __future__ import annotations

import functools

import numpy as np

N = 8
COMPENSATION = (N - 2) << (N - 3)  # 192


def _bit(x, i):
    return (x >> i) & 1


def _prop4(a, b, c, d):
    """Approximate A+B+C+D+1: 2·carry + sum."""
    carry = a | b | c | d
    s = 1 - (a & (1 - b) & (1 - c) & (1 - d))
    return 2 * carry + s


def product(a, b):
    """Proposed approximate product of signed 8-bit ints (numpy, int64)."""
    a = np.asarray(a, np.int64)
    b = np.asarray(b, np.int64)
    s = N - 1
    p = lambda i, j: _bit(a, i) & _bit(b, j)  # noqa: E731
    c1a = {(1, 6), (2, 5), (3, 4)}
    total = np.zeros(np.broadcast(a, b).shape, np.int64)
    for i in range(s):
        for j in range(s):
            if i + j >= s and (i, j) not in c1a:
                total += p(i, j) << (i + j)
    for i in range(1, s):
        total += (1 - (_bit(a, i) & _bit(b, s))) << (i + s)
    for j in range(1, s):
        total += (1 - (_bit(a, s) & _bit(b, j))) << (j + s)
    total += p(s, s) << (2 * s)
    neg0 = 1 - (_bit(a, 0) & _bit(b, s))
    total += _prop4(neg0, p(1, 6), p(2, 5), p(3, 4)) << s  # C1a, its +1 incl.
    total += 1 << s                      # ¬(a7·b0) converted to constant 1
    total += (1 << N) + (1 << (2 * N - 1))  # Baugh-Wooley constants
    total += COMPENSATION - (1 << s)     # compensation beyond C1a's +1
    total &= (1 << 2 * N) - 1
    return np.where(total >= 1 << (2 * N - 1), total - (1 << 2 * N), total)


@functools.lru_cache(maxsize=None)
def table() -> np.ndarray:
    """(256, 256) int32 product table indexed by operand + 128."""
    v = np.arange(-128, 128)
    return product(v[:, None], v[None, :]).astype(np.int32)


@functools.lru_cache(maxsize=None)
def _e1a() -> np.ndarray:
    """(16, 16) error of the approximate compressor, indexed by the low four
    bits of a and the high four bits of b."""
    u = np.arange(16)[:, None]
    v = np.arange(16)[None, :]
    a0, bits_a = u & 1, [(u >> k) & 1 for k in (1, 2, 3)]
    b7, bits_b = (v >> 3) & 1, [(v >> k) & 1 for k in (2, 1, 0)]  # b6, b5, b4
    neg0 = 1 - (a0 & b7)
    pb, pc, pd = (x & y for x, y in zip(bits_a, bits_b))
    return (_prop4(neg0, pb, pc, pd) - (neg0 + pb + pc + pd + 1)).astype(np.int8)


def terms(a, b):
    """The product as ``Σ_c c·Σ_r u_r(a)·v_r(b) + 192``, for int arrays
    ``a`` and ``b``: ``[(c, [u_r(a)], [v_r(b)])]`` with every u_r and v_r in
    int8 range, so each group is one int8 matmul over the stacked factors."""
    xp = np if isinstance(a, np.ndarray) else __import__("jax.numpy").numpy
    # weight 1: the exact product, less the truncated columns,
    # a_i·(b mod 2^(7-i))·2^i, each at most 127
    ones_u, ones_v = [a], [b]
    for i in range(N - 1):
        ones_u.append(_bit(a, i))
        ones_v.append(-((b & ((1 << (N - 1 - i)) - 1)) << i))
    # weight 2^7: the converted NAND a7·b0 and the approximate compressor's
    # error, a function of a's low and b's high four bits
    e = xp.asarray(_e1a())
    lo, hi = a & 15, (b >> 4) & 15
    c_u, c_v = [_bit(a, N - 1)], [_bit(b, 0)]
    for u in range(16):
        c_u.append((lo == u).astype(a.dtype))
        c_v.append(e[u][hi].astype(b.dtype))
    return [(1, ones_u, ones_v), (1 << (N - 1), c_u, c_v)]


def approx_dot(qa, qb):
    """Σ_k f(qa[m, k], qb[k, n]) for int8 (M, K) and (K, N), as int32.

    Two int8 matmuls with int32 accumulation over the stacked factors of
    :func:`terms`, which are exact.
    """
    import jax
    import jax.numpy as jnp

    a = qa.astype(jnp.int32)
    b = qb.astype(jnp.int32)
    acc = COMPENSATION * a.shape[1]
    for c, us, vs in terms(a, b):
        u = jnp.concatenate(us, axis=1).astype(jnp.int8)
        v = jnp.concatenate(vs, axis=0).astype(jnp.int8)
        acc = acc + c * jax.lax.dot(u, v, preferred_element_type=jnp.int32)
    return acc
