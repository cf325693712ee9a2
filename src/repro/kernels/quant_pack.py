"""Fused SMOL quantize + bit-pack (deploy-time weight conversion, and
on-the-fly activation packing for the serve path).

w [K, N] f32 (optionally per-group-scaled) -> packed uint8 [K*p//8, N].
Grid (K/bk, N/bn); pure VPU work (no MXU): round to grid codes, then fold
8/p consecutive K rows into one byte with shifts — the inverse of
packed_matmul's in-register unpack.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.qtypes import GROUP_SIZE


def _quantize_pack_kernel(w_ref, s_ref, o_ref, *, p: int, bk: int,
                          use_scales: bool):
    w = w_ref[...].astype(jnp.float32)
    if use_scales:
        sig = jnp.repeat(s_ref[...].astype(jnp.float32), GROUP_SIZE, axis=0)
        w = w / sig
    h = float(2.0 ** (1 - p))
    two_p = float(2 ** p)
    u = jnp.clip(jnp.round((w / h + (two_p - 1.0)) / 2.0), 0.0, two_p - 1.0)
    # Codes are built in int32 and narrowed once at the store: Mosaic has
    # no float32 -> uint8 cast.
    u = u.astype(jnp.int32)
    vpb = 8 // p
    u = u.reshape(bk // vpb, vpb, w.shape[-1])
    out = u[:, 0]
    for j in range(1, vpb):
        out = out | (u[:, j] << (p * j))
    o_ref[...] = out.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=(
    "p", "block_k", "block_n", "interpret"))
def quantize_pack(w, scales, *, p: int, interpret: bool, block_k: int = 256,
                  block_n: int = 256):
    """w [K, N] -> uint8 [K*p//8, N] SMOL codes (packed little-endian on K)."""
    from .packed_matmul import LANE, fit_block
    k, n = w.shape
    bk = fit_block(k, block_k, LANE)   # bk//16 scale rows: a multiple of 8
    bn = fit_block(n, block_n, LANE)
    use_scales = scales is not None
    if not use_scales:
        scales = jnp.ones((k // GROUP_SIZE,), jnp.float32)
    s2d = scales.reshape(-1, 1).astype(jnp.float32)
    kern = functools.partial(_quantize_pack_kernel, p=p, bk=bk,
                             use_scales=use_scales)
    return pl.pallas_call(
        kern,
        name="quantize_pack",
        grid=(k // bk, n // bn),
        in_specs=[
            pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bk // GROUP_SIZE, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bk * p // 8, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((k * p // 8, n), jnp.uint8),
        interpret=interpret,
    )(w, s2d)
