"""Fused clipped-STE fake-quantization forward (QAT hot path).

x [M, K] is quantize-dequantized along K with per-16-channel-group
precisions ``pbits`` [K//16] and either a per-row scale [M, 1] (dynamic
activation scaling) or a per-group scale [K//16] (weight scaling) — the
two shapes ``core.quant.fake_quant`` actually receives from the QAT phase
rules. Grid (M/bm, K/bk); pure VPU (round/clip/multiply), no MXU.

Element-wise arithmetic is kept identical to
``core.quant._fake_quant_fwd_impl`` (branchless in p: h = 2^(1-p),
u = clip(round((x/s/h + 2^p - 1) / 2)), back through (2u - (2^p-1))·h·s,
rounded through the input dtype), so the kernel is bit-exact against the
jnp reference — the backward pass (clipped STE) recomputes the in-range
mask in jnp through the shared custom VJP in ``repro.backend.base``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.qtypes import GROUP_SIZE


def _fake_quant_kernel(x_ref, pb_ref, s_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    p = pb_ref[...]                       # [1, bk] per-channel precision
    s = s_ref[...]                        # [bm, 1] per row or [1, bk]
    xs = x / s
    h = jnp.exp2(1.0 - p)                 # 2^(1-p): half-step
    two_p = 2.0 / h                       # 2^p
    u = jnp.clip(jnp.round((xs / h + (two_p - 1.0)) / 2.0), 0.0,
                 two_p - 1.0)
    q = (2.0 * u - (two_p - 1.0)) * h
    o_ref[...] = (q * s).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "row_scale", "block_m", "block_k", "interpret"))
def fake_quant(x, pbits, scale, *, row_scale: bool, interpret: bool,
               block_m: int = 256, block_k: int = 256):
    """x [M, K], pbits [K//16] -> quantize-dequantized x (same dtype).

    ``scale`` is [M, 1] when ``row_scale`` (per-token activation scaling)
    else [K//16] (per-group weight scaling). Per-group operands are
    expanded to one value per channel here, so their blocks are lane-dense
    [1, bk] rows that Mosaic tiles like x's.
    """
    from .packed_matmul import LANE, SUBLANE, fit_block
    m, k = x.shape
    bm = fit_block(m, block_m, SUBLANE)
    bk = fit_block(k, block_k, LANE)

    def per_channel(v):
        return jnp.repeat(jnp.asarray(v, jnp.float32), GROUP_SIZE,
                          total_repeat_length=k).reshape(1, k)

    k_spec = pl.BlockSpec((1, bk), lambda i, j: (0, j))
    if row_scale:
        s_op = jnp.asarray(scale, jnp.float32).reshape(m, 1)
        s_spec = pl.BlockSpec((bm, 1), lambda i, j: (i, 0))
    else:
        s_op, s_spec = per_channel(scale), k_spec
    return pl.pallas_call(
        _fake_quant_kernel,
        name="fake_quant",
        grid=(m // bm, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            k_spec,
            s_spec,
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, k), x.dtype),
        interpret=interpret,
    )(x, per_channel(pbits), s_op)
