"""Fused Phase-I perturbation kernel:  w <- clip(w + sigma(s)*eps, +-(2-sigma)).

eps ~ U(-1, 1) is generated *inside* the kernel from a counter-based hash of
the global element index (kernels/prng.py) — no HBM round-trip for the noise
tensor, which is what makes Phase I's extra memory traffic ~zero vs. plain
training (the GPU-paper analogue materializes eps; this is the TPU-native
fusion). Grid (K/bk, N/bn); pure VPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.qtypes import GROUP_SIZE
from . import prng


def _kernel(seed_ref, w_ref, s_ref, o_ref, *, bk: int, bn: int, n_total: int):
    i, j = pl.program_id(0), pl.program_id(1)
    w = w_ref[...].astype(jnp.float32)
    sig = jax.nn.sigmoid(s_ref[...].astype(jnp.float32))      # [bk//16, 1]
    sig = jnp.repeat(sig, GROUP_SIZE, axis=0)                 # [bk, 1]
    rows = jax.lax.broadcasted_iota(jnp.uint32, (bk, bn), 0) \
        + jnp.uint32(i * bk)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (bk, bn), 1) \
        + jnp.uint32(j * bn)
    idx = rows * jnp.uint32(n_total) + cols                   # global index
    eps = prng.uniform_pm1(idx, seed_ref[0])
    out = w + sig * eps
    lim = 2.0 - sig
    o_ref[...] = jnp.clip(out, -lim, lim).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "block_n",
                                             "interpret"))
def noise_inject(w, s, seed, *, interpret: bool, block_k: int = 256,
                 block_n: int = 256):
    """w [K, N], s [K//16] -> perturbed + clipped w (same dtype as w)."""
    from .packed_matmul import LANE, fit_block
    k, n = w.shape
    bk = fit_block(k, block_k, LANE)   # bk//16 scale rows: a multiple of 8
    bn = fit_block(n, block_n, LANE)
    s2d = jnp.asarray(s, jnp.float32).reshape(-1, 1)
    seed_arr = jnp.asarray([seed], jnp.uint32)
    kern = functools.partial(_kernel, bk=bk, bn=bn, n_total=n)
    return pl.pallas_call(
        kern,
        grid=(k // bk, n // bn),
        in_specs=[
            pl.BlockSpec((1,), lambda i, j: (0,)),   # seed (SMEM-sized)
            pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bk // GROUP_SIZE, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((k, n), w.dtype),
        interpret=interpret,
    )(seed_arr, w, s2d)
