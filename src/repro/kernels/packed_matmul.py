"""Mixed ultra-low-precision packed GEMM — the TPU `vmac_Pn` (paper §IV-B).

One uniform-precision segment per pallas_call (the paper's sorted-run
execution, Obs. 4): x [M, Kp] @ Wpacked [Kp*p//8, N] -> [M, N] f32, with
in-register unpack (shift/mask), affine SMOL dequant
``v = (2u - (2^p - 1)) * 2^(1-p)``, optional per-16-channel-group scales,
optional activation snap-to-grid (input-weight consistency, Obs. 3), and
fp32 MXU accumulation (the paper's 16.6 accumulator widened to TPU-native).

Grid (M/bm, N/bn, Kp/bk), K innermost (accumulation). VMEM working set per
step at defaults (bm=bk=256, bn=128, f32):
    x 256x256x4 = 256 KiB, wp <= 256x128 = 32 KiB, out 256x128x4 = 128 KiB,
    unpacked w 256x128x4 = 128 KiB  ->  ~0.6 MiB of ~16 MiB VMEM.
MXU dims (bm, bk, bn) are multiples of 128/8 as required.

These kernels are segment-oblivious by design: the draft (low-slice)
forward of self-speculative decoding (DESIGN.md §14) is NOT a new kernel —
the shared ``Backend.packed_matmul`` driver simply invokes the same
segment GEMMs over only the segments whose precision is within
``QuantConfig.draft_slice_bits``, skipping the high-bit carriers. Weight
traffic drops with the skipped bytes; per-segment arithmetic is unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.qtypes import GROUP_SIZE
from repro.core.quant import ACT_SCALE_EPS

_GRID_TOP_4 = 2.0 - 2.0 ** (1 - 4)      # quant._static_grid_max(4) = 1.875


def _tpu_compiler_params(grid_rank: int = 3):
    """K is the innermost (accumulation) grid dim — mark it 'arbitrary' so
    Mosaic may not reorder/parallelize it; every outer dim (M/N tiles, and
    the expert dim of the grouped kernel) is 'parallel'. Ignored in
    interpret mode."""
    return pltpu.CompilerParams(dimension_semantics=("parallel",)
                                * (grid_rank - 1) + ("arbitrary",))


# Mosaic tiles the last two dims of every block by (8, 128); a block dim
# must be a multiple of that tile or the whole array dim. K sits on the
# lanes of the x block, so K blocks are multiples of 128 (which also keeps
# the per-group scale block bk//16 a multiple of 8).
SUBLANE, LANE = 8, 128


def fit_block(total: int, want: int, multiple: int = 1) -> int:
    """Largest divisor of ``total`` that is <= ``want`` and a multiple of
    ``multiple``, or ``total`` itself when no such divisor exists. With
    ``multiple`` = SUBLANE (second-minor dims) or LANE (minor dims) the
    result is always a legal Mosaic block: tile-aligned or the full dim."""
    d = min(want, total) // multiple * multiple
    while d > 0:
        if total % d == 0:
            return d
        d -= multiple
    return total


def _unpack_dequant(wp, p: int, bk: int):
    """[bk*p//8, bn] uint8 -> [bk, bn] f32 on the SMOL grid (no scale).
    The codes widen to int32 first: Mosaic has no uint8 -> f32 cast."""
    vpb = 8 // p
    w = wp.astype(jnp.int32)
    mask = (1 << p) - 1
    parts = [(w >> (p * j)) & mask for j in range(vpb)]
    u = jnp.stack(parts, axis=1).reshape(bk, wp.shape[-1])
    u = u.astype(jnp.float32)
    return (2.0 * u - float(2 ** p - 1)) * float(2.0 ** (1 - p))


def _snap(x, p: int):
    """Snap (already scale-normalized) activations to the p-bit grid."""
    h = float(2.0 ** (1 - p))
    two_p = float(2 ** p)
    u = jnp.clip(jnp.round((x / h + (two_p - 1.0)) / 2.0), 0.0, two_p - 1.0)
    return (2.0 * u - (two_p - 1.0)) * h


def _accumulate(xq, wp_ref, s_ref, o_ref, *, p: int, bk: int,
                use_scales: bool):
    """Shared GEMM tail of both segment kernels: zero the accumulator on
    the first K step, unpack-dequant the weight tile, apply per-group
    scales, accumulate the MXU dot. One implementation so the fused and
    plain kernels cannot drift apart."""
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    wd = _unpack_dequant(wp_ref[...], p, bk)
    if use_scales:
        sig = s_ref[...].astype(jnp.float32)            # [bk//16, 1]
        sig = jnp.repeat(sig, GROUP_SIZE, axis=0)       # [bk, 1]
        wd = wd * sig
    o_ref[...] += jax.lax.dot(xq, wd, preferred_element_type=jnp.float32)


def _segment_kernel(x_ref, wp_ref, s_ref, o_ref, *, p: int, bk: int,
                    act_quant: bool, use_scales: bool):
    x = x_ref[...].astype(jnp.float32)
    if act_quant:
        x = _snap(x, p)
    _accumulate(x, wp_ref, s_ref, o_ref, p=p, bk=bk, use_scales=use_scales)


def _fused_kernel(x_ref, sx_ref, wp_ref, s_ref, o_ref, *, p: int, bk: int,
                  use_scales: bool):
    """Segment GEMM with the activation fake-quant fused into the prologue:
    divide by the per-token scale, snap to the p-bit grid, rescale, and
    round through the activation dtype — the exact element-wise arithmetic
    of ``core.quant.fake_quant`` — before the MXU dot. One HBM read of x,
    no materialized quantized-activation tensor."""
    x = x_ref[...].astype(jnp.float32)
    sx = sx_ref[...].astype(jnp.float32)                # [bm, 1] per token
    xq = (_snap(x / sx, p) * sx).astype(x_ref.dtype).astype(jnp.float32)
    _accumulate(xq, wp_ref, s_ref, o_ref, p=p, bk=bk, use_scales=use_scales)


def _fused_selfscale_kernel(x_ref, wp_ref, s_ref, o_ref, *, p: int,
                            bk: int, use_scales: bool):
    """Single-segment fused GEMM that computes the per-token abs-max scale
    *in-kernel* (the ROADMAP "in-kernel per-token abs-max" item): the x
    block spans the FULL K row (its index map pins the K grid dim to 0, so
    the tile stays resident across K steps), making the [bm, 1] reduction
    available in the prologue — the last [M, K] -> [M, 1] jnp pass over
    the activations disappears. Legal only when one uniform-precision
    segment spans the whole row: a row crossing segment boundaries would
    need the reduction across kernel invocations (DESIGN.md §11), which is
    exactly why the multi-segment form keeps the scale in the driver.

    Mirrors ``core.quant.abs_max_scale`` (fp32 abs-max over the full row,
    ``ACT_SCALE_EPS`` clamp, divide by the 4-bit grid top 1.875). A
    compiler may lower that divide by a constant as a reciprocal multiply,
    so the scale matches the driver-side one to an ulp, not bitwise."""
    x = x_ref[...].astype(jnp.float32)                  # [bm, K] full row
    m = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    sx = jnp.maximum(m, ACT_SCALE_EPS) / _GRID_TOP_4    # [bm, 1]
    k0 = pl.multiple_of(pl.program_id(2) * bk, bk)
    xk = x_ref[:, pl.ds(k0, bk)].astype(jnp.float32)
    xq = (_snap(xk / sx, p) * sx).astype(x_ref.dtype).astype(jnp.float32)
    _accumulate(xq, wp_ref, s_ref, o_ref, p=p, bk=bk, use_scales=use_scales)


def _segment_call(name, kern, x, wp, s2d, *extra, bm, bn, bk, p,
                  extra_specs=(), interpret, x_spec=None):
    """Shared pallas_call assembly of the segment GEMMs: (M/bm, N/bn,
    Kp/bk) grid with K innermost, x/wp/per-group-scale block specs (any
    ``extra`` operands slot between x and wp), f32 output. ``x_spec``
    overrides the default K-tiled x block (the self-scale kernel pins the
    full K row instead). ``name`` is the kernel's stable name: the HLO
    custom call, and so the profiler's op, carries it."""
    m, kp = x.shape
    n = wp.shape[1]
    return pl.pallas_call(
        kern,
        name=name,
        grid=(m // bm, n // bn, kp // bk),
        in_specs=[
            x_spec or pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            *extra_specs,
            pl.BlockSpec((bk * p // 8, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // GROUP_SIZE, 1), lambda i, j, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=_tpu_compiler_params(),
        interpret=interpret,
    )(x, *extra, wp, s2d)


def _fit_segment_blocks(x, wp, p, block_m, block_n, block_k):
    m, kp = x.shape[-2:]
    assert wp.shape[-2] * (8 // p) == kp, (wp.shape, kp, p)
    return (fit_block(m, block_m, SUBLANE), fit_block(wp.shape[-1], block_n,
                                                      LANE),
            fit_block(kp, block_k, LANE))


def _prep_scales(scales, kp):
    use_scales = scales is not None
    if not use_scales:  # dummy operand keeps one kernel signature
        scales = jnp.ones((kp // GROUP_SIZE,), jnp.float32)
    return use_scales, scales.reshape(-1, 1).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "p", "block_m", "block_n", "block_k", "act_quant", "interpret"))
def packed_segment_matmul(x, wp, scales, *, p: int, interpret: bool,
                          block_m: int = 256, block_n: int = 128,
                          block_k: int = 256, act_quant: bool = False):
    """x [M, Kp] @ unpack(wp [Kp*p//8, N]) -> [M, N] f32.

    scales: [Kp//16] per-group f32 or None. Pre-divide x by the activation
    scale (and rescale the output) when act_quant=True.
    """
    bm, bn, bk = _fit_segment_blocks(x, wp, p, block_m, block_n, block_k)
    use_scales, s2d = _prep_scales(scales, x.shape[1])
    kern = functools.partial(_segment_kernel, p=p, bk=bk,
                             act_quant=act_quant, use_scales=use_scales)
    return _segment_call("packed_segment_matmul", kern, x, wp, s2d, bm=bm,
                         bn=bn, bk=bk, p=p, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "p", "block_m", "block_n", "block_k", "interpret"))
def fused_act_segment_matmul(x, sx, wp, scales, *, p: int, interpret: bool,
                             block_m: int = 256, block_n: int = 128,
                             block_k: int = 256):
    """Fused-prologue segment GEMM: quantize the activations to the p-bit
    grid with per-token scales ``sx`` [M, 1] *inside* the kernel, then
    x @ unpack(wp) -> [M, N] f32.

    Numerically this is fake_quant(x, p, sx) followed by
    ``packed_segment_matmul(..., act_quant=False)``: the in-kernel prologue
    runs the same element-wise arithmetic (divide, snap, rescale,
    round-trip through x.dtype), up to the lowering of the f32 divide (a
    few ulp of the output scale), but without writing the quantized
    activation tensor back to HBM between the two. The per-token
    abs-max reduction itself stays in the driver: the scale spans the full
    permuted K row, which crosses segment (and therefore kernel) boundaries.
    """
    assert sx.shape == (x.shape[0], 1), (sx.shape, x.shape)
    bm, bn, bk = _fit_segment_blocks(x, wp, p, block_m, block_n, block_k)
    use_scales, s2d = _prep_scales(scales, x.shape[1])
    kern = functools.partial(_fused_kernel, p=p, bk=bk,
                             use_scales=use_scales)
    sx_spec = pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0))
    return _segment_call("fused_act_segment_matmul", kern, x, wp, s2d,
                         jnp.asarray(sx, jnp.float32),
                         bm=bm, bn=bn, bk=bk, p=p, extra_specs=(sx_spec,),
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "p", "block_m", "block_n", "block_k", "interpret"))
def fused_act_selfscale_matmul(x, wp, scales, *, p: int, interpret: bool,
                               block_m: int = 256, block_n: int = 128,
                               block_k: int = 256):
    """Single-segment fused-prologue GEMM with the per-token abs-max scale
    computed *inside* the kernel: for a uniform-precision layer (one
    segment spans the whole K row) this removes the remaining [M, K] ->
    [M, 1] jnp reduction pass — activations are read once, scaled,
    snapped and multiplied without ever leaving VMEM.

    Equal to ``fused_act_segment_matmul(x, act_scale(x), ...)`` (and so to
    the two-pass reference) within a few ulp of the output scale: the
    in-kernel reduction runs the same fp32 abs-max / ``ACT_SCALE_EPS``
    clamp / grid-top divide as ``core.quant.abs_max_scale``, and the
    abs-max is row-permutation-invariant so driver-side channel reordering
    does not perturb it.
    """
    bm, bn, bk = _fit_segment_blocks(x, wp, p, block_m, block_n, block_k)
    use_scales, s2d = _prep_scales(scales, x.shape[1])
    kern = functools.partial(_fused_selfscale_kernel, p=p, bk=bk,
                             use_scales=use_scales)
    x_spec = pl.BlockSpec((bm, x.shape[1]), lambda i, j, k: (i, 0))
    return _segment_call("fused_act_selfscale_matmul", kern, x, wp, s2d,
                         bm=bm, bn=bn, bk=bk, p=p, interpret=interpret,
                         x_spec=x_spec)


def _grouped_kernel(x_ref, wp_ref, s_ref, o_ref, *, p: int, bk: int,
                    act_quant: bool, use_scales: bool):
    """Grouped (stacked-expert) segment GEMM body. Block refs carry a
    leading size-1 expert dim (the E grid axis maps one expert per step);
    K moves to ``program_id(3)`` — otherwise the arithmetic is the
    per-segment kernel's, via the same ``_unpack_dequant``/``_snap``
    helpers, so the grouped and single forms cannot drift apart."""
    @pl.when(pl.program_id(3) == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[0].astype(jnp.float32)
    if act_quant:
        x = _snap(x, p)
    wd = _unpack_dequant(wp_ref[0], p, bk)
    if use_scales:
        sig = s_ref[0].astype(jnp.float32)              # [bk//16, 1]
        sig = jnp.repeat(sig, GROUP_SIZE, axis=0)       # [bk, 1]
        wd = wd * sig
    o_ref[0] += jax.lax.dot(x, wd, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "p", "block_m", "block_n", "block_k", "act_quant", "interpret"))
def packed_segment_matmul_grouped(x, wp, scales, *, p: int, interpret: bool,
                                  block_m: int = 256, block_n: int = 128,
                                  block_k: int = 256,
                                  act_quant: bool = False):
    """Grouped uniform-precision segment GEMM over a stacked expert bank:
    x [E, M, Kp] @ unpack(wp [E, Kp*p//8, N]) -> [E, M, N] f32, one
    pallas_call for all E experts (the MoE serve fast path — and the
    grouped-segment substrate the PrecisionBatching-style bit-sliced
    backend builds on).

    scales: [E, Kp//16] per-(expert, group) f32 or None. Grid is
    (E, M/bm, N/bn, Kp/bk) with K innermost (accumulation) and the expert
    dim outermost-parallel; each grid step touches exactly one expert's
    carrier tile, so VMEM working set matches the single-expert kernel.
    """
    e, m, kp = x.shape
    assert wp.shape[0] == e and wp.shape[1] * (8 // p) == kp, \
        (x.shape, wp.shape, p)
    bm, bn, bk = _fit_segment_blocks(x, wp, p, block_m, block_n, block_k)
    n = wp.shape[2]
    use_scales = scales is not None
    if not use_scales:  # dummy operand keeps one kernel signature
        scales = jnp.ones((e, kp // GROUP_SIZE), jnp.float32)
    s3d = jnp.asarray(scales, jnp.float32).reshape(e, -1, 1)
    kern = functools.partial(_grouped_kernel, p=p, bk=bk,
                             act_quant=act_quant, use_scales=use_scales)
    return pl.pallas_call(
        kern,
        name="packed_segment_matmul_grouped",
        grid=(e, m // bm, n // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda ei, i, j, k: (ei, i, k)),
            pl.BlockSpec((1, bk * p // 8, bn), lambda ei, i, j, k: (ei, k, j)),
            pl.BlockSpec((1, bk // GROUP_SIZE, 1),
                         lambda ei, i, j, k: (ei, k, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda ei, i, j, k: (ei, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, m, n), jnp.float32),
        compiler_params=_tpu_compiler_params(4),
        interpret=interpret,
    )(x, wp, s3d)
