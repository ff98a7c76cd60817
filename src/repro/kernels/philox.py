"""Philox4x32-10 counter-based RNG + Box-Muller, in pure jnp uint32 ops.

Written so the SAME functions run (a) inside Pallas kernel bodies and
(b) as the pure-jnp oracle — which makes the kernel-vs-ref comparison
bit-exact rather than statistical.

TPU note: there is no 64-bit integer multiply on the VPU, so the 32x32
mulhilo is decomposed into 16-bit partial products (uint32 only).  This is
the TPU-native port of the usual CUDA ``__umulhi`` trick.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# numpy scalars (not jnp arrays) so Pallas kernel bodies don't capture
# device constants at trace time.
_PHILOX_M0 = np.uint32(0xD2511F53)
_PHILOX_M1 = np.uint32(0xCD9E8D57)
_WEYL_0 = np.uint32(0x9E3779B9)
_WEYL_1 = np.uint32(0xBB67AE85)
_U16 = np.uint32(0xFFFF)


def mulhilo32(a: jnp.ndarray, b: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(hi, lo) 32-bit halves of a*b using only uint32 arithmetic."""
    a = a.astype(jnp.uint32)
    b = b.astype(jnp.uint32)
    al, ah = a & _U16, a >> 16
    bl, bh = b & _U16, b >> 16
    lo = a * b
    t = al * bl
    k = t >> 16
    t = ah * bl + k
    w1 = t & _U16
    w2 = t >> 16
    t = al * bh + w1
    k2 = t >> 16
    hi = ah * bh + w2 + k2
    return hi, lo


def philox_round(c0, c1, c2, c3, k0, k1):
    hi0, lo0 = mulhilo32(_PHILOX_M0, c0)
    hi1, lo1 = mulhilo32(_PHILOX_M1, c2)
    return (hi1 ^ c1 ^ k0, lo1, hi0 ^ c3 ^ k1, lo0)


def philox4x32(c0, c1, c2, c3, k0, k1, rounds: int = 10):
    """Philox4x32 with the given counter/key words (all uint32 arrays).

    Keys are usually static (python/numpy ints): the per-round Weyl bumps
    are then folded at trace time, so the kernel sees literal constants.
    """
    c0, c1, c2, c3 = (x.astype(jnp.uint32) for x in (c0, c1, c2, c3))
    if hasattr(k0, "astype") and not isinstance(k0, np.generic):
        k0 = k0.astype(jnp.uint32)
        k1 = k1.astype(jnp.uint32)
        for _ in range(rounds):
            c0, c1, c2, c3 = philox_round(c0, c1, c2, c3, k0, k1)
            k0 = k0 + _WEYL_0
            k1 = k1 + _WEYL_1
        return c0, c1, c2, c3
    k0i, k1i = int(k0), int(k1)
    for _ in range(rounds):
        c0, c1, c2, c3 = philox_round(c0, c1, c2, c3,
                                      np.uint32(k0i), np.uint32(k1i))
        k0i = (k0i + 0x9E3779B9) & 0xFFFFFFFF
        k1i = (k1i + 0xBB67AE85) & 0xFFFFFFFF
    return c0, c1, c2, c3


def uniform01(bits: jnp.ndarray) -> jnp.ndarray:
    """uint32 -> float32 in (0, 1]: (bits >> 8) * 2^-24, zero mapped up.

    Using the top 24 bits keeps the conversion exact in float32; the +1ulp
    shift avoids log(0) in Box-Muller.
    """
    # via int32 (exact below 2^24): Mosaic lowers no uint32 -> float cast
    top = jax.lax.bitcast_convert_type(bits >> 8, jnp.int32)
    u = top.astype(jnp.float32) * np.float32(1.0 / (1 << 24))
    return u + np.float32(1.0 / (1 << 25))


def box_muller(u1: jnp.ndarray, u2: jnp.ndarray):
    """Two independent N(0,1) draws from two U(0,1] draws."""
    r = jnp.sqrt(np.float32(-2.0) * jnp.log(u1))
    theta = np.float32(2.0 * 3.141592653589793) * u2
    return r * jnp.cos(theta), r * jnp.sin(theta)


def normal_pair(c0, c1, c2, c3, k0, k1):
    """Four counter words -> two N(0,1) float32 arrays (z0, z1)."""
    r0, r1, r2, r3 = philox4x32(c0, c1, c2, c3, k0, k1)
    z0, z1 = box_muller(uniform01(r0), uniform01(r1))
    return z0, z1
