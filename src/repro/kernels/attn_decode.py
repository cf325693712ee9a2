"""Fused quantized-KV flash-decode attention (serve hot path, DESIGN §12).

One (batch row, kv-head) per program: q [SG, D] (S decode/prefill tokens x
G grouped query heads) attends over packed 4-bit K/V entries (2 codes per
byte — ``serve/kv_quant``'s carrier convention) with per-(entry, head)
scales. Each tile is unpacked (shift/mask) and affine SMOL-dequantized
``v = (2u - 15) * 2^-3`` **inside the attention loop** — the [T, D]
floating-point K/V tensor never exists in HBM.

Both kernels share one body, an online (flash-decode) softmax over tiles:
a running (m, l, acc) carry replaces a full [SG, T] score row. They differ
only in where tile i lives:

    ring    tile i of the slot's own ring ([B, Hk, T/bt, bt, ...] staged
            per program)
    paged   page ``page_table[b, i]`` of the global pool ([Hk, P, ps, ...]
            staged per program; the page table is scalar-prefetched)

Layout rules that let Mosaic lower it: the tile index is a leading
(untiled) dim, so a dynamic tile pick is a plain address offset; the
per-entry scales and positions are lane-dense [1, bt] rows, so the K
scale multiplies the score tile and the V scale the probability tile
(``(q . u) * s == q . (u * s)`` up to rounding); scales travel as f32
(Mosaic loads no f16 vectors). Numerics follow
``backend.base.qkv_attn_jnp`` (1/sqrt(D) scaling, -1e30 mask fill, fp32
throughout) up to f32 reassociation, which is why parity is a tolerance
plus token-identical greedy decode, not bitwise logits. An all-masked
tile contributes exp(0) = 1 weights at m = -1e30; the first real tile's
rescale exp(-1e30 - m_real) = 0 flushes them, and a row that stays fully
masked divides to the uniform average — what the oracle's softmax over
an all--1e30 row gives.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30                        # matches models.attention.NEG_INF


def _dequant_tile(codes):
    """[bt, D//2] uint8 codes -> [bt, D] f32 on the 4-bit SMOL grid,
    unscaled (channel 2j in the low nibble, 2j+1 in the high). The codes
    widen to int32 first: Mosaic has no uint8 -> f32 cast."""
    c = codes.astype(jnp.int32)
    lo = (c & 0xF).astype(jnp.float32)
    hi = ((c >> 4) & 0xF).astype(jnp.float32)
    u = jnp.stack([lo, hi], axis=-1).reshape(c.shape[0], c.shape[1] * 2)
    return (2.0 * u - 15.0) * 0.125


def _flash(q, qcol, ntiles, load_tile, window: Optional[int]):
    """Online-softmax attention of q [SG, D] (query positions qcol [SG, 1])
    over ``ntiles`` tiles; ``load_tile(i)`` returns (k codes [bt, D//2],
    k scales [1, bt], v codes, v scales, key positions [1, bt], valid)."""
    sg, d = q.shape

    def tile(i, carry):
        m, l, acc = carry
        kc, ks, vc, vs, kpos, valid = load_tile(i)
        sc = jax.lax.dot_general(q, _dequant_tile(kc),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        sc = sc * ks * (1.0 / np.sqrt(d))                  # [SG, bt]
        mask = (qcol >= kpos) & (kpos >= 0) & valid
        if window is not None:
            mask &= (qcol - kpos) < window
        sc = jnp.where(mask, sc, NEG_INF)
        m2 = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m2)                            # [SG, 1]
        p = jnp.exp(sc - m2)                               # [SG, bt]
        l2 = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc2 = acc * alpha + jax.lax.dot(
            p * vs, _dequant_tile(vc), preferred_element_type=jnp.float32)
        return m2, l2, acc2

    m0 = jnp.full((sg, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((sg, 1), jnp.float32)
    a0 = jnp.zeros((sg, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, ntiles, tile, (m0, l0, a0))
    return acc / jnp.maximum(l, 1e-30)


def _ring_kernel(q_ref, qpos_ref, kc_ref, vc_ref, ks_ref, vs_ref, kpos_ref,
                 o_ref, *, window: Optional[int]):
    def load_tile(i):
        return (kc_ref[0, 0, i], ks_ref[0, 0, i], vc_ref[0, 0, i],
                vs_ref[0, 0, i], kpos_ref[0, i], True)

    o_ref[0, 0] = _flash(q_ref[0, 0].astype(jnp.float32), qpos_ref[0],
                         kc_ref.shape[2], load_tile, window)


def _paged_kernel(tbl_ref, q_ref, qpos_ref, kc_ref, vc_ref, ks_ref, vs_ref,
                  pos_ref, o_ref, *, window: Optional[int]):
    b = pl.program_id(0)

    def load_tile(i):
        pid = tbl_ref[b, i]                                # SMEM scalar
        page = jnp.maximum(pid, 0)         # unmapped -> null page 0
        return (kc_ref[0, page], ks_ref[0, page], vc_ref[0, page],
                vs_ref[0, page], pos_ref[page], pid >= 0)

    o_ref[0, 0] = _flash(q_ref[0, 0].astype(jnp.float32), qpos_ref[0],
                         tbl_ref.shape[1], load_tile, window)


def _head_major_q(q, q_pos):
    """q [B,S,Hk,G,D] -> [B,Hk,S*G,D] (s-major rows) and q_pos [B,S] ->
    per-row positions [B,S*G,1]."""
    b, s, hk, g, d = q.shape
    qh = jnp.transpose(q, (0, 2, 1, 3, 4)).reshape(b, hk, s * g, d)
    qpos = jnp.repeat(jnp.asarray(q_pos, jnp.int32), g, axis=1)
    return qh, qpos.reshape(b, s * g, 1)


@functools.partial(jax.jit, static_argnames=("window", "block_t",
                                             "interpret"))
def qkv_attn_decode(q, k_codes, v_codes, k_scale, v_scale, kv_pos, q_pos,
                    *, interpret: bool, window: Optional[int] = None,
                    block_t: int = 256):
    """Fused decode attention over the packed 4-bit ring-KV cache.

    q [B,S,Hk,G,D] (RoPE applied); k_codes/v_codes [B,T,Hk,D//2] uint8;
    k_scale/v_scale [B,T,Hk,1] f16; kv_pos [B,T] ring positions (< 0 =
    empty entry); q_pos [B,S] (< 0 = masked lane). -> [B,S,Hk,G,D] f32.
    """
    from .packed_matmul import LANE, fit_block
    b, s, hk, g, d = q.shape
    t = k_codes.shape[1]
    bt = fit_block(t, block_t, LANE)
    nb, sg = t // bt, s * g
    # Head-major relayout with the tile index as a leading dim: one
    # contiguous (b, h) slab per program. The transposed operands are
    # *bytes* (codes) and scalars — 4x+ less traffic than a dequantized
    # fp cache would move.
    qh, qpos = _head_major_q(q, q_pos)

    def codes(c):                      # [B,T,Hk,c] -> [B,Hk,nb,bt,c]
        return jnp.swapaxes(c, 1, 2).reshape(b, hk, nb, bt, d // 2)

    def scales(x):                     # [B,T,Hk,1] -> [B,Hk,nb,1,bt]
        return jnp.swapaxes(x[..., 0].astype(jnp.float32), 1, 2).reshape(
            b, hk, nb, 1, bt)

    kpos = jnp.asarray(kv_pos, jnp.int32).reshape(b, nb, 1, bt)
    bh = lambda i, j: (i, j, 0, 0, 0)
    out = pl.pallas_call(
        functools.partial(_ring_kernel, window=window),
        grid=(b, hk),
        in_specs=[
            pl.BlockSpec((1, 1, sg, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, sg, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, nb, bt, d // 2), bh),
            pl.BlockSpec((1, 1, nb, bt, d // 2), bh),
            pl.BlockSpec((1, 1, nb, 1, bt), bh),
            pl.BlockSpec((1, 1, nb, 1, bt), bh),
            pl.BlockSpec((1, nb, 1, bt), lambda i, j: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, sg, d), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hk, sg, d), jnp.float32),
        interpret=interpret,
    )(qh, qpos, codes(k_codes), codes(v_codes), scales(k_scale),
      scales(v_scale), kpos)
    return jnp.transpose(out.reshape(b, hk, s, g, d), (0, 2, 1, 3, 4))


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def qkv_attn_decode_paged(q, k_codes, v_codes, k_scale, v_scale, pool_pos,
                          page_table, q_pos, *, interpret: bool,
                          window: Optional[int] = None):
    """Paged flash-decode attention over the packed 4-bit page pool
    (DESIGN.md §13). One tile is one page.

    q [B,S,Hk,G,D] (RoPE applied); k_codes/v_codes [P,ps,Hk,D//2] uint8
    pool pages; k_scale/v_scale [P,ps,Hk,1] f16; pool_pos [P,ps] absolute
    position stamps (< 0 = empty); page_table [B,NP] physical page per
    logical page (< 0 = unmapped, read as the null page 0 and masked);
    q_pos [B,S] (< 0 = masked lane). -> [B,S,Hk,G,D] f32."""
    b, s, hk, g, d = q.shape
    npages, ps = pool_pos.shape
    sg = s * g
    qh, qpos = _head_major_q(q, q_pos)

    # Head-major pool streams, [P, ps, Hk, c] -> [Hk, P, ps, c]. They carry
    # no batch dim: every program of one head stages the whole pool and
    # reads it through its row of the page table (ROADMAP S2).
    def codes(c):
        return jnp.transpose(c, (2, 0, 1, 3))

    def scales(x):                     # [P,ps,Hk,1] -> [Hk,P,1,ps] f32
        return jnp.transpose(x[..., 0].astype(jnp.float32),
                             (2, 0, 1))[:, :, None, :]

    pos = jnp.asarray(pool_pos, jnp.int32).reshape(npages, 1, ps)
    h_only = lambda i, j, tbl: (j, 0, 0, 0)
    out = pl.pallas_call(
        functools.partial(_paged_kernel, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hk),
            in_specs=[
                pl.BlockSpec((1, 1, sg, d), lambda i, j, tbl: (i, j, 0, 0)),
                pl.BlockSpec((1, sg, 1), lambda i, j, tbl: (i, 0, 0)),
                pl.BlockSpec((1, npages, ps, d // 2), h_only),
                pl.BlockSpec((1, npages, ps, d // 2), h_only),
                pl.BlockSpec((1, npages, 1, ps), h_only),
                pl.BlockSpec((1, npages, 1, ps), h_only),
                pl.BlockSpec((npages, 1, ps), lambda i, j, tbl: (0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, sg, d),
                                   lambda i, j, tbl: (i, j, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, hk, sg, d), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(page_table, jnp.int32), qh, qpos, codes(k_codes),
      codes(v_codes), scales(k_scale), scales(v_scale), pos)
    return jnp.transpose(out.reshape(b, hk, s, g, d), (0, 2, 1, 3, 4))
