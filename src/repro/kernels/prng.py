"""Counter-based in-kernel PRNG (xxhash-style avalanche).

``pltpu.prng_random_bits`` has no CPU interpret lowering, so the Phase-I
noise kernel derives its randomness from a stateless integer hash of
(global element index, seed). The same function runs inside the Pallas
kernel and in the pure-jnp oracle, so kernel vs. ref comparisons are exact,
and the kernel is bit-identical between interpret mode and real TPU.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

_C1 = np.uint32(0x9E3779B1)
_C2 = np.uint32(0x85EBCA77)
_C3 = np.uint32(0xC2B2AE3D)


def hash_u32(idx, seed):
    """Avalanche hash: uint32 index x uint32 seed -> uint32."""
    h = idx.astype(jnp.uint32) * _C1 + jnp.asarray(seed, jnp.uint32)
    h = h ^ (h >> np.uint32(15))
    h = h * _C2
    h = h ^ (h >> np.uint32(13))
    h = h * _C3
    h = h ^ (h >> np.uint32(16))
    return h


def uniform_pm1(idx, seed):
    """Deterministic U[-1, 1) from (index, seed), float32."""
    bits = hash_u32(idx, seed) >> np.uint32(8)       # 24 mantissa-safe bits
    # Through int32 (exact below 2^24): Mosaic has no uint32 -> f32 cast.
    return bits.astype(jnp.int32).astype(jnp.float32) \
        * np.float32(2.0 / (1 << 24)) - 1.0
