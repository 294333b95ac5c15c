"""Threefry-2x32 counter-based PRNG + Box-Muller, in pure jnp uint32 ops
(add / xor / rotate only — TPU-friendly, works inside Pallas kernel bodies
and in interpret mode, bit-identical between the kernel and the oracle).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_ROT_A = (13, 15, 26, 6)
_ROT_B = (17, 29, 16, 24)
_PARITY = 0x1BD11BDA  # kept as a Python int: jnp constants would be captured
TWO_PI = 6.283185307179586

# Degree-7 (8-term) Chebyshev-fitted polynomials for one turn of sin/cos:
# with x = 2u - 1 and t = x^2,
#   cos(2*pi*u) = -sum_k COS_COEF[k] * t^k
#   sin(2*pi*u) = -x * sum_k SIN_COEF[k] * t^k
# Max abs error ~5e-7 in f32 (the f32 rounding floor). Pure mul/add, so the
# result is bit-identical across XLA CPU, Pallas interpret mode and TPU —
# which libm-backed jnp.cos/jnp.sin do NOT guarantee — and ~10x faster than
# scalar libm trig on CPU, where it is the dominant cost of every noise
# stream this repo draws.
COS_COEF = (1.000000000e+00, -4.934802055e+00, 4.058712006e+00,
            -1.335262775e+00, 2.353304178e-01, -2.580626495e-02,
            1.928504556e-03, -1.035682435e-04)
SIN_COEF = (3.141592741e+00, -5.167712688e+00, 2.550163984e+00,
            -5.992645025e-01, 8.214584738e-02, -7.370326202e-03,
            4.661239800e-04, -2.173679604e-05)


def _poly(t, coef):
    acc = jnp.float32(coef[-1])
    for c in coef[-2::-1]:
        acc = acc * t + jnp.float32(c)
    return acc


def cos_turn(u):
    """cos(2*pi*u) for u in [0, 1], polynomial (deterministic bits)."""
    x = 2.0 * u - 1.0
    return -_poly(x * x, COS_COEF)


def sin_turn(u):
    """sin(2*pi*u) for u in [0, 1], polynomial (deterministic bits)."""
    x = 2.0 * u - 1.0
    return -(x * _poly(x * x, SIN_COEF))


def _rotl(x, r: int):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def threefry2x32(k0, k1, x0, x1):
    """All args uint32 (broadcastable). Returns (y0, y1) uint32."""
    k0 = jnp.uint32(k0)
    k1 = jnp.uint32(k1)
    x0 = x0.astype(jnp.uint32)
    x1 = x1.astype(jnp.uint32)
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_PARITY))
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(5):
        rots = _ROT_A if i % 2 == 0 else _ROT_B
        for r in rots:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x1 ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


def uniform01(bits):
    """uint32 -> float32 uniform in (0, 1], by bit manipulation only (the
    TPU's Mosaic compiler has no uint32 -> float32 convert): the top 23 bits
    become the mantissa of a float in [1, 2), and ``2 - x`` is exact and lies
    in (0, 1], which the Box-Muller ``log`` needs."""
    x = jax.lax.bitcast_convert_type(
        (bits >> jnp.uint32(9)) | jnp.uint32(0x3F800000), jnp.float32)
    return 2.0 - x


def normal_pair(k0, k1, c0, c1):
    """One Box-Muller pair of standard normals from counters (c0, c1).

    The angular terms use the polynomial :func:`cos_turn`/:func:`sin_turn`
    (not libm ``jnp.cos``): every stream family in the repo draws through
    this one function, so the substitution shifts noise bits uniformly and
    every cross-tier bit-parity contract holds unchanged."""
    b0, b1 = threefry2x32(k0, k1, c0, c1)
    u1 = uniform01(b0)
    u2 = uniform01(b1)
    rad = jnp.sqrt(-2.0 * jnp.log(u1))
    return rad * cos_turn(u2), rad * sin_turn(u2)


def normal_stream(k0, k1, idx, stream):
    """Standard normal per element: idx (counter, uint32 array), stream id."""
    z0, _ = normal_pair(k0, k1, idx, jnp.uint32(stream) + jnp.zeros_like(idx))
    return z0
