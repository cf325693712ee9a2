"""``pallas_interpret`` / ``pallas_mosaic`` — the Pallas kernel backends.

Both route the per-segment ops through the fused TPU kernels in
``repro.kernels`` (in-register unpack + dequant + MXU GEMM — with the
serve activation quantization fused into its prologue —, fused SMOL
quantize+pack, in-kernel-PRNG noise, fused QAT fake_quant forward).
``pallas_interpret`` runs them under
the Pallas interpreter (any platform — the CI parity leg);
``pallas_mosaic`` compiles through Mosaic and is only available on a real
TPU. Selection between them is a registry concern ("pallas" alias);
``interpret`` is an implementation detail that no public API exposes.

Geometry the kernels cannot express (a K narrower than the 16-channel
group, carrier rows that do not tile) falls back per-call to the jnp
reference math. Every fallback is counted per op
(:func:`fallback_counts`), beside the kernel dispatch counts, so a run
can show that it took none.

Block shapes come from :mod:`repro.backend.autotune`: an on-disk cache
keyed by (op, shape, dtype, platform), falling back to the static defaults
the kernels shipped with. Lookup is trace-time-safe (no timing inside a
trace); measurement is explicit (``autotune.autotune_op`` /
``benchmarks/runtime_proxy.py --autotune``).
"""
from __future__ import annotations

import functools
import importlib
from collections import Counter
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qtypes import GROUP_SIZE

# The kernels package still answers the legacy function names (with a
# DeprecationWarning); import the kernel modules by their dotted paths.
_pm = importlib.import_module("repro.kernels.packed_matmul")
_qp = importlib.import_module("repro.kernels.quant_pack")
_ni = importlib.import_module("repro.kernels.noise_inject")
_fq = importlib.import_module("repro.kernels.fake_quant")
_ad = importlib.import_module("repro.kernels.attn_decode")

from . import autotune
from .base import Backend, over_mesh_rows
from .registry import register
from .xla_ref import XLA_REF as _REF   # per-call geometry fallback

# Trace-time dispatch counters, per op: how often a Pallas backend traced
# its kernel, and how often it gave way to the jnp reference math for
# geometry the kernel cannot express. CI's SONIQ_BACKEND=pallas_interpret
# leg and the chip smoke assert that the serve path engaged the kernels
# and took no fallback.
_KERNEL_CALLS: Counter = Counter()
_FALLBACKS: Counter = Counter()


def kernel_call_counts() -> Dict[str, int]:
    """Kernel dispatches per op, counted at trace time (not per step)."""
    return dict(_KERNEL_CALLS)


def fallback_counts() -> Dict[str, int]:
    """Reference-math fallbacks per op, counted at trace time."""
    return dict(_FALLBACKS)


def fused_act_call_count() -> int:
    """Dispatches of the fused activation-quant GEMM kernel (either
    prologue form)."""
    return _KERNEL_CALLS["fused_act_segment_matmul"]


def grouped_matmul_call_count() -> int:
    """Dispatches of the grouped (stacked-expert) segment-GEMM kernel."""
    return _KERNEL_CALLS["packed_segment_matmul_grouped"]


def fake_quant_kernel_call_count() -> int:
    """Dispatches of the fused fake_quant forward kernel."""
    return _KERNEL_CALLS["fake_quant"]


def qkv_attn_call_count() -> int:
    """Dispatches of the ring quantized-KV flash-decode kernel
    (DESIGN.md §12)."""
    return _KERNEL_CALLS["qkv_attn_decode"]


def qkv_attn_paged_call_count() -> int:
    """Dispatches of the paged flash-decode kernel (DESIGN.md §13)."""
    return _KERNEL_CALLS["qkv_attn_decode_paged"]


class PallasBackend(Backend):
    """Shared Pallas plumbing; ``interpret`` (set by each subclass) picks
    the execution mode."""

    interpret: bool

    def _blocks(self, op: str, shape, p, dtype, blocks):
        """Explicit caller blocks win; else the autotune cache; else the
        kernel defaults (autotune returns {} on a miss)."""
        if blocks:
            return blocks
        return autotune.lookup(op, shape=shape, p=p, dtype=dtype,
                               backend=self.name)

    def packed_segment_matmul(self, x, wp, scales=None, *, p: int,
                              act_quant: bool = False,
                              group_size: int = GROUP_SIZE, **blocks):
        if group_size != GROUP_SIZE or x.ndim != 2 \
                or x.shape[1] == 0 or x.shape[1] % GROUP_SIZE:
            _FALLBACKS["packed_segment_matmul"] += 1
            return _REF.packed_segment_matmul(
                x, wp, scales, p=p, act_quant=act_quant,
                group_size=group_size)
        _KERNEL_CALLS["packed_segment_matmul"] += 1
        m, kp = x.shape
        blocks = self._blocks("packed_segment_matmul", (m, kp, wp.shape[1]),
                              p, x.dtype, blocks)
        return _pm.packed_segment_matmul(x, wp, scales, p=p,
                                         act_quant=act_quant,
                                         interpret=self.interpret, **blocks)

    def packed_segment_matmul_grouped(self, x, wp, scales=None, *, p: int,
                                      act_quant: bool = False,
                                      group_size: int = GROUP_SIZE,
                                      **blocks):
        if group_size != GROUP_SIZE or x.ndim != 3 \
                or x.shape[2] == 0 or x.shape[2] % GROUP_SIZE \
                or x.shape[0] == 0 or x.shape[1] == 0:
            _FALLBACKS["packed_segment_matmul_grouped"] += 1
            return _REF.packed_segment_matmul_grouped(
                x, wp, scales, p=p, act_quant=act_quant,
                group_size=group_size)
        _KERNEL_CALLS["packed_segment_matmul_grouped"] += 1
        e, m, kp = x.shape
        blocks = self._blocks("packed_segment_matmul_grouped",
                              (e, m, kp, wp.shape[2]), p, x.dtype, blocks)
        return _pm.packed_segment_matmul_grouped(
            x, wp, scales, p=p, act_quant=act_quant,
            interpret=self.interpret, **blocks)

    def fused_act_segment_matmul(self, x, wp, scales=None, act_scales=None,
                                 *, p: int, group_size: int = GROUP_SIZE,
                                 in_kernel_scale: bool = False, **blocks):
        if group_size != GROUP_SIZE or x.ndim != 2 \
                or x.shape[1] == 0 or x.shape[1] % GROUP_SIZE:
            _FALLBACKS["fused_act_segment_matmul"] += 1
            return _REF.fused_act_segment_matmul(
                x, wp, scales, act_scales, p=p, group_size=group_size,
                in_kernel_scale=in_kernel_scale)
        _KERNEL_CALLS["fused_act_segment_matmul"] += 1
        m, kp = x.shape
        blocks = self._blocks("fused_act_segment_matmul",
                              (m, kp, wp.shape[1]), p, x.dtype, blocks)
        if in_kernel_scale:
            # Single-segment fast path: the kernel reduces the per-token
            # abs-max itself (full-K x block) — no [M, 1] jnp pass.
            return _pm.fused_act_selfscale_matmul(
                x, wp, scales, p=p, interpret=self.interpret, **blocks)
        if act_scales is None:
            act_scales = jnp.ones((m, 1), jnp.float32)
        return _pm.fused_act_segment_matmul(
            x, act_scales, wp, scales, p=p, interpret=self.interpret,
            **blocks)

    def qkv_attn_decode(self, q, cache, q_pos, *, window=None, **blocks):
        """Fused quantized-KV flash-decode (kernels/attn_decode.py): the
        packed codes and fp16 scales are unpacked/applied inside the
        attention inner loop, never materialized as a [B,T,Hk,D] fp
        buffer. Falls back to the jnp oracle for geometry the kernel does
        not cover (odd head_dim, empty ring, mismatched carrier
        shapes)."""
        b, s, hk, g, d = q.shape
        kc = cache["k_codes"]
        if d % 2 or kc.ndim != 4 or kc.shape != (b, kc.shape[1], hk, d // 2) \
                or kc.shape[1] == 0:
            _FALLBACKS["qkv_attn_decode"] += 1
            return _REF.qkv_attn_decode(q, cache, q_pos, window=window)
        _KERNEL_CALLS["qkv_attn_decode"] += 1
        t = kc.shape[1]
        blocks = self._blocks("qkv_attn_decode", (b * hk * s * g, t, d),
                              4, q.dtype, blocks)
        return _ad.qkv_attn_decode(
            q, kc, cache["v_codes"], cache["k_scale"], cache["v_scale"],
            cache["pos"], q_pos, window=window, interpret=self.interpret,
            **blocks)

    def qkv_attn_decode_paged(self, q, cache, q_pos, *, window=None):
        """Paged flash-decode (kernels/attn_decode.py): walks the slot's
        page table over the global pool with an online softmax — no dense
        gather, no [SG, T] score row. The kernel covers the packed-q4
        pool; the fp paged family and geometry the kernel cannot express
        (odd head_dim, empty pool) fall back to the gather oracle."""
        _b, _s, hk, _g, d = q.shape
        kc = cache.get("k_codes")
        if kc is None or d % 2 or kc.ndim != 4 \
                or kc.shape[2:] != (hk, d // 2) or kc.shape[0] == 0 \
                or kc.shape[1] == 0:
            _FALLBACKS["qkv_attn_decode_paged"] += 1
            return _REF.qkv_attn_decode_paged(q, cache, q_pos,
                                              window=window)
        _KERNEL_CALLS["qkv_attn_decode_paged"] += 1
        return _ad.qkv_attn_decode_paged(
            q, kc, cache["v_codes"], cache["k_scale"], cache["v_scale"],
            cache["pos"], cache["page_table"], q_pos, window=window,
            interpret=self.interpret)

    def quantize_pack(self, w, scales=None, *, p: int,
                      group_size: int = GROUP_SIZE, **blocks):
        if group_size != GROUP_SIZE or w.ndim != 2 \
                or w.shape[0] % GROUP_SIZE:
            _FALLBACKS["quantize_pack"] += 1
            return _REF.quantize_pack(w, scales, p=p, group_size=group_size)
        _KERNEL_CALLS["quantize_pack"] += 1
        blocks = self._blocks("quantize_pack", tuple(w.shape), p, w.dtype,
                              blocks)
        return _qp.quantize_pack(w, scales, p=p, interpret=self.interpret,
                                 **blocks)

    def _noise_inject_fwd(self, w, s, seed, group_size, blocks):
        if group_size != GROUP_SIZE or w.ndim != 2 \
                or w.shape[0] % GROUP_SIZE:
            _FALLBACKS["noise_inject"] += 1
            return super()._noise_inject_fwd(w, s, seed, group_size, blocks)
        _KERNEL_CALLS["noise_inject"] += 1
        blocks = self._blocks("noise_inject", tuple(w.shape), 0, w.dtype,
                              blocks)
        # ε hashes the global element index, so under a mesh every device
        # runs the whole tensor (no operand is row-split).
        kern = functools.partial(_ni.noise_inject, interpret=self.interpret,
                                 **blocks)
        return over_mesh_rows(kern, w, s, jnp.asarray(seed, jnp.uint32),
                              rows=(False, False, False))

    def _fake_quant_fwd(self, x, pbits, scale, group_size):
        """Fused QAT quantize-dequantize forward. Falls back to the jnp
        reference (numerically identical element-wise math) for geometry
        the kernel does not cover: non-16 groups, K not a multiple of the
        group, or a scale layout that is neither per-row nor per-group."""
        pb = jnp.asarray(pbits)
        k = x.shape[-1] if x.ndim else 0
        if (group_size != GROUP_SIZE or x.ndim < 1 or k == 0
                or k % GROUP_SIZE or pb.ndim != 1
                or pb.shape[0] * GROUP_SIZE != k):
            _FALLBACKS["fake_quant"] += 1
            return super()._fake_quant_fwd(x, pbits, scale, group_size)
        ng = k // GROUP_SIZE
        lead = x.shape[:-1]
        m = int(np.prod(lead, dtype=np.int64)) if lead else 1
        if m == 0:
            _FALLBACKS["fake_quant"] += 1
            return super()._fake_quant_fwd(x, pbits, scale, group_size)
        s = jnp.asarray(scale, jnp.float32)
        if s.ndim == 0 or (s.shape[-1] == 1
                           and all(d == 1 for d in s.shape[:-1])):
            s_op, row = jnp.broadcast_to(s.reshape(-1, 1), (m, 1)), True
        elif s.shape == lead + (1,):
            s_op, row = s.reshape(m, 1), True
        elif s.ndim == 1 and s.shape[0] == ng:
            s_op, row = s, False
        else:
            _FALLBACKS["fake_quant"] += 1
            return super()._fake_quant_fwd(x, pbits, scale, group_size)
        _KERNEL_CALLS["fake_quant"] += 1
        blocks = self._blocks("fake_quant", (m, k), 0, x.dtype, {})
        kern = functools.partial(_fq.fake_quant, row_scale=row,
                                 interpret=self.interpret, **blocks)
        y2 = over_mesh_rows(kern, x.reshape(m, k), pb, s_op,
                            rows=(True, False, row))
        return y2.reshape(x.shape)


class PallasInterpretBackend(PallasBackend):

    name = "pallas_interpret"
    priority = 10                      # correct everywhere, fast nowhere
    interpret = True


class PallasMosaicBackend(PallasBackend):

    name = "pallas_mosaic"
    priority = 100                     # the point of the whole exercise
    interpret = False

    def is_available(self) -> bool:
        return jax.default_backend() == "tpu"

    def why_unavailable(self) -> str:
        return (f"requires a TPU (jax default backend is "
                f"{jax.default_backend()!r})")


PALLAS_INTERPRET = register(PallasInterpretBackend())
PALLAS_MOSAIC = register(PallasMosaicBackend())
