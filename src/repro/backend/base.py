"""The kernel-backend protocol (DESIGN.md §11).

A :class:`Backend` owns the implementations of the eight SONIQ hot-path
ops — the operations every lifecycle phase's forward rule is built from:

    packed_segment_matmul   x @ unpack_dequant(wp) for one uniform-p segment
    fused_act_segment_matmul  the same GEMM with the activation fake-quant
                            fused into its prologue (serve fast path)
    packed_matmul           full mixed [K4|K2|K1] serve-mode linear
    quantize_pack           SMOL quantize + bit-pack one uniform-p weight
    noise_inject            Phase-I fused perturbation  clip(w + σ(s)·ε)
    fake_quant              straight-through quantize-dequantize (QAT)
    qkv_attn_decode         decode attention over the packed 4-bit ring-KV
                            cache (serve fast path, DESIGN.md §12)
    qkv_attn_decode_paged   the same attention over the paged block-pool
                            cache (page-table walk, DESIGN.md §13)

Backends register with :mod:`repro.backend.registry`; the phase rules in
``repro.core.smol`` resolve one at trace time (``QuantConfig.backend`` /
``SONIQ_BACKEND`` / ``soniq.use_backend``) and never touch a kernel module
directly — the dependency points from backend implementations *down* into
``repro.kernels``/``repro.core``, not from core up into kernels.

Two template methods keep cross-backend numerics aligned:

* :meth:`Backend.packed_matmul` — the shared mixed-precision driver:
  channel permutation, activation scaling per ``QuantConfig.act_scale_mode``
  (per_token / per_tensor / none), one ``fake_quant`` over the full K, then
  one ``packed_segment_matmul`` per non-empty segment
  (``core.pack.iter_packed_segments``) accumulated in fp32. Backends only
  override the per-segment GEMM, so greedy decode is token-identical
  across backends at fp32 (pinned by ``tests/test_backend_dispatch.py``).
* :meth:`Backend.noise_inject` — wraps the backend's forward kernel in a
  shared ``custom_vjp``: ε is a counter-based hash of (element index, seed)
  (``kernels.prng``), so the backward pass recomputes it in jnp and every
  backend gets exact Phase-I gradients even when its forward is a
  non-differentiable Pallas call.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import pack as pack_lib
from repro.core import quant
from repro.core.qtypes import GROUP_SIZE

# The op vocabulary of the protocol (capability negotiation keys).
OPS: Tuple[str, ...] = ("packed_matmul", "packed_segment_matmul",
                        "packed_segment_matmul_grouped",
                        "fused_act_segment_matmul", "quantize_pack",
                        "noise_inject", "fake_quant", "qkv_attn_decode",
                        "qkv_attn_decode_paged")

# Name-stack tag the shared driver wraps every per-segment GEMM call in
# (``jax.named_scope`` — free at run time, visible in each traced eqn's
# source_info). ``repro.analysis.jaxpr_checks`` walks serve-step jaxprs
# and holds everything under this scope to the quantized-GEMM dtype
# contract: fp32 accumulation, no narrowing float converts, no f64.
SEGMENT_GEMM_SCOPE = "soniq_segment_gemm"

# Where each op's backend-specific implementation actually lives (defaults
# to the op name itself): noise_inject's and fake_quant's public entry
# points are the shared custom-VJP wrappers, so their capability hooks are
# the forward methods.
_OP_IMPL_HOOK = {"noise_inject": "_noise_inject_fwd",
                 "fake_quant": "_fake_quant_fwd"}

# Trace-time dispatch counter for the draft (low-slice) serve forward:
# incremented by the shared ``packed_matmul`` driver whenever
# ``qcfg.draft_slice_bits`` filtered the segment list — CI's speculative
# leg asserts the draft path actually engaged (mirrors the kernel
# counters in ``repro.backend.pallas``, but lives here because the slice
# happens in the driver, identically on every backend).
_DRAFT_MATMUL_CALLS = 0


def draft_matmul_call_count() -> int:
    """How many packed matmuls were traced in draft (low-slice) mode —
    the high-bit carriers skipped per ``QuantConfig.draft_slice_bits``
    (DESIGN.md §14). Counted at trace time, not per executed step."""
    return _DRAFT_MATMUL_CALLS


class BackendUnavailable(RuntimeError):
    """An explicitly selected backend cannot run here (wrong platform,
    missing toolchain). Explicit selection never falls back silently —
    callers that want negotiation pass no name at all."""


# Floor on the dynamic abs-max before it becomes a divisor. A padding /
# freshly-reset batch row is exactly zero, and 0-abs-max would make both
# the shared driver's fake_quant and the fused kernel prologue divide by
# zero (NaN/Inf logits for *every* row once they mix in the matmul).
# tests/test_backend_dispatch.py pins the zero-row regression. The value
# itself lives in ``core.quant`` (the bottom layer — kernels and the serve
# KV quantizer share it without importing this module); this re-export is
# the documented operational name.
ACT_SCALE_EPS = quant.ACT_SCALE_EPS


def act_scale(x, act_scale_mode: str, eps: float = ACT_SCALE_EPS):
    """Dynamic activation scale per the config policy. ``per_token``
    reduces over the last dim only (row-independent — what continuous
    batching requires); ``per_tensor`` over the whole tensor; ``none`` is
    the paper-faithful pre-scaled setting. The abs-max is clamped to
    ``eps`` so all-zero rows yield a tiny finite scale, never a 0
    divisor."""
    if act_scale_mode == "none":
        return jnp.asarray(1.0, jnp.float32)
    if act_scale_mode == "per_token":
        return quant.abs_max_scale(x, axis=-1, eps=eps).astype(jnp.float32)
    return quant.abs_max_scale(x, eps=eps).astype(jnp.float32)


def hash_eps(shape: Tuple[int, ...], seed):
    """The shared Phase-I noise draw: ε ~ U(-1, 1) from the counter-based
    hash of the global element index — identical in every backend (and on
    TPU vs interpret), which is what makes noise_inject backend-exact."""
    from repro.kernels import prng
    k, n = shape
    idx = (jnp.arange(k, dtype=jnp.uint32)[:, None] * jnp.uint32(n)
           + jnp.arange(n, dtype=jnp.uint32)[None, :])
    return prng.uniform_pm1(idx, seed)


def over_mesh_rows(kernel, *args, rows: Tuple[bool, ...]):
    """Call ``kernel(*args)`` so that it runs under a sharded jit.

    The compiler cannot partition a Mosaic kernel: inside a jit over a
    mesh (``jax.set_mesh``, as the sharded train step runs) it refuses
    one whose operands are sharded. Under an ambient mesh this runs the
    kernel in ``shard_map``, each device on its own block of rows: operand
    ``i`` is split on its first dim across every mesh axis where
    ``rows[i]``, replicated otherwise, and the output is split like the
    row operands. That is right for a kernel that treats rows
    independently (``fake_quant``). Where the rows do not divide over the
    mesh, or no operand is row-split, every device runs the whole call.
    Without a mesh the kernel is called directly."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return kernel(*args)
    split = any(rows) and all(a.shape[0] % mesh.size == 0
                              for a, r in zip(args, rows) if r)
    row = P(tuple(mesh.axis_names)) if split else P()
    return jax.shard_map(
        kernel, in_specs=tuple(row if r else P() for r in rows),
        out_specs=row, check_vma=False)(*args)


# --------------------------------------------------------------------------
# noise_inject: shared custom_vjp over the backend-specific forward.
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 4, 5))
def _noise_inject(backend, w, s, seed, group_size, blocks):
    return backend._noise_inject_fwd(w, s, seed, group_size, dict(blocks))


def _noise_inject_fwd(backend, w, s, seed, group_size, blocks):
    out = backend._noise_inject_fwd(w, s, seed, group_size, dict(blocks))
    return out, (w, s, seed)


def _noise_inject_bwd(backend, group_size, blocks, res, g):
    w, s, seed = res
    k = w.shape[0]
    sig_g = jax.nn.sigmoid(jnp.asarray(s, jnp.float32))
    sig = jnp.repeat(sig_g, group_size, total_repeat_length=k)[:, None]
    eps = hash_eps(w.shape, seed)
    z = jnp.asarray(w, jnp.float32) + sig * eps
    lim = 2.0 - sig
    inside = jnp.abs(z) <= lim
    g32 = jnp.asarray(g, jnp.float32)
    dw = jnp.where(inside, g32, 0.0).astype(w.dtype)
    # ∂out/∂σ: ε inside the clip; at the clamp the limit ±(2-σ) itself
    # moves with σ, d(±(2-σ))/dσ = ∓1. Chain through σ'(s) = σ(1-σ) and
    # sum each group's K×N block.
    dsig_elem = jnp.where(inside, eps, -jnp.sign(z))
    per_k = jnp.sum(g32 * dsig_elem, axis=tuple(range(1, w.ndim)))
    per_group = per_k.reshape(sig_g.shape[0], group_size).sum(axis=1)
    ds = (per_group * sig_g * (1.0 - sig_g)).astype(
        jnp.asarray(s).dtype)
    dseed = np.zeros(np.shape(seed), dtype=jax.dtypes.float0)
    return dw, ds, dseed


_noise_inject.defvjp(_noise_inject_fwd, _noise_inject_bwd)


# --------------------------------------------------------------------------
# fake_quant: shared clipped-STE custom_vjp over the backend forward.
# --------------------------------------------------------------------------
# Same pattern as noise_inject: the public op is one custom_vjp whose
# forward is the backend hook (a fused Pallas kernel on the Pallas
# backends, the jnp reference elsewhere) and whose backward recomputes the
# in-range mask in jnp — so QAT differentiates through every backend with
# gradients identical to core.quant.fake_quant's STE.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 4))
def _fake_quant(backend, x, pbits, scale, group_size):
    return backend._fake_quant_fwd(x, pbits, scale, group_size)


def _fake_quant_fwd(backend, x, pbits, scale, group_size):
    out = backend._fake_quant_fwd(x, pbits, scale, group_size)
    return out, (x, pbits, scale)


def _fake_quant_bwd(backend, group_size, res, g):
    x, pbits, scale = res
    _, in_range = quant._fake_quant_fwd_impl(x, pbits, scale, group_size)
    return g * in_range, jnp.zeros_like(pbits), jnp.zeros_like(scale)


_fake_quant.defvjp(_fake_quant_fwd, _fake_quant_bwd)


# Score mask fill for decode attention — matches models.attention.NEG_INF
# so the oracle and the fp cache path produce identical masked softmaxes.
_ATTN_NEG_INF = -1e30


def qkv_attn_jnp(q, k, v, k_pos, q_pos, window: Optional[int] = None):
    """Masked GQA decode attention in fp32 — the element-exact reference
    the fused quantized-KV flash-decode kernel is gated against.

    q [B,S,Hk,G,D] (RoPE applied), k/v [B,T,Hk,D] (dequantized), k_pos
    [B,T] ring positions (< 0 = empty/evicted entry), q_pos [B,S] (< 0 =
    masked lane). Causal-by-position mask, optional sliding window; scores,
    softmax and the value contraction all run in fp32. Returns
    [B,S,Hk,G,D] fp32.
    """
    dh = q.shape[-1]
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", jnp.asarray(q, jnp.float32),
                        jnp.asarray(k, jnp.float32),
                        preferred_element_type=jnp.float32)
    scores = scores * (1.0 / np.sqrt(dh))
    m = (q_pos[:, :, None] >= k_pos[:, None, :]) \
        & (k_pos[:, None, :] >= 0)                        # [B, S, T]
    if window is not None:
        m &= (q_pos[:, :, None] - k_pos[:, None, :]) < window
    scores = jnp.where(m[:, None, None], scores, _ATTN_NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p,
                      jnp.asarray(v, jnp.float32),
                      preferred_element_type=jnp.float32)


def noise_inject_jnp(w, s, seed, group_size: int = GROUP_SIZE):
    """Reference forward (pure jnp, counter-hash ε): clip(w + σ(s)·ε,
    ±(2-σ)). Matches ``kernels.ref.noise_inject_ref`` bit-for-bit."""
    w32 = jnp.asarray(w, jnp.float32)
    k = w.shape[0]
    sig = jnp.repeat(jax.nn.sigmoid(jnp.asarray(s, jnp.float32)),
                     group_size, total_repeat_length=k)[:, None]
    eps = hash_eps(w.shape, seed)
    out = w32 + sig * eps
    return jnp.clip(out, -(2.0 - sig), 2.0 - sig).astype(w.dtype)


class Backend:
    """Base class / protocol for kernel backends.

    Subclasses set ``name``/``priority``, implement the per-segment ops
    they accelerate, and inherit the shared drivers. ``priority`` orders
    auto-negotiation (highest available wins); ``is_available`` gates it.
    """

    name: str = "abstract"
    priority: int = 0

    # ---------------------------------------------------- availability ----
    def is_available(self) -> bool:
        return True

    def why_unavailable(self) -> str:
        return "available"

    def supports(self, op: str) -> bool:
        """Capability probe: does this backend carry its own implementation
        of ``op``, vs inheriting the shared/reference one? (Ops route
        through template hooks where the shared wrapper must stay — e.g.
        noise_inject's custom VJP — so the probe checks the hook.)"""
        assert op in OPS, op
        attr = _OP_IMPL_HOOK.get(op, op)
        return getattr(type(self), attr, None) is not getattr(
            Backend, attr, None)

    # ------------------------------------------------------ primitive ops --
    def packed_segment_matmul(self, x, wp, scales=None, *, p: int,
                              act_quant: bool = False,
                              group_size: int = GROUP_SIZE, **blocks):
        """x [M, Kp] @ unpack_dequant(wp [Kp*p//8, N]) -> [M, N] f32.
        ``scales``: per-group [Kp//group_size] f32 or None. ``act_quant``
        snaps x (already in scale units) to the p-bit grid first."""
        raise NotImplementedError(self.name)

    def packed_segment_matmul_grouped(self, x, wp, scales=None, *, p: int,
                                      act_quant: bool = False,
                                      group_size: int = GROUP_SIZE,
                                      **blocks):
        """Grouped (stacked-expert) uniform-p segment GEMM:
        x [E, M, Kp] @ unpack_dequant(wp [E, Kp*p//8, N]) -> [E, M, N] f32,
        one dispatch for all E experts. ``scales``: per-(expert, group)
        [E, Kp//group_size] f32 or None. The base implementation is the
        jnp oracle — batched unpack/dequant then one batched fp32
        contraction — which is what ``xla_ref`` runs and what the Pallas
        grouped kernel is parity-pinned against."""
        del blocks                     # block shapes are a kernel concern
        e, _m, kp = x.shape
        vpb = 8 // p
        assert wp.shape[0] == e and wp.shape[1] * vpb == kp, \
            (x.shape, wp.shape, p)
        wp8 = jnp.asarray(wp, jnp.uint8)
        parts = [(wp8 >> np.uint8(p * j)) & np.uint8((1 << p) - 1)
                 for j in range(vpb)]
        u = jnp.stack(parts, axis=2).reshape(e, kp, wp.shape[2])
        wd = (2.0 * u.astype(jnp.float32) - float(2 ** p - 1)) \
            * float(2.0 ** (1 - p))
        if scales is not None:
            s_full = jnp.repeat(scales.astype(jnp.float32), group_size,
                                axis=1, total_repeat_length=kp)
            wd = wd * s_full[:, :, None]
        xs = jnp.asarray(x, jnp.float32)
        if act_quant:
            xs = quant.snap_to_grid(xs, p)
        return jax.lax.dot_general(
            xs, wd, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    def quantize_pack(self, w, scales=None, *, p: int,
                      group_size: int = GROUP_SIZE, **blocks):
        """w [K, N] f32 -> packed uint8 [K*p//8, N] SMOL codes."""
        raise NotImplementedError(self.name)

    def fake_quant(self, x, pbits, scale, group_size: int = GROUP_SIZE):
        """Clipped-STE quantize-dequantize along the last dim with
        per-group precisions. Differentiable in ``x`` on every backend via
        the shared custom VJP (the STE backward recomputes the in-range
        mask in jnp); backends accelerate the forward by overriding
        ``_fake_quant_fwd``, never this entry point."""
        return _fake_quant(self, x, jnp.asarray(pbits, jnp.float32),
                           jnp.asarray(scale, jnp.float32), group_size)

    def _fake_quant_fwd(self, x, pbits, scale, group_size: int):
        """Forward-only quantize-dequantize (wrapped by the custom VJP)."""
        return quant._fake_quant_fwd_impl(x, pbits, scale, group_size)[0]

    def fused_act_segment_matmul(self, x, wp, scales=None, act_scales=None,
                                 *, p: int, group_size: int = GROUP_SIZE,
                                 in_kernel_scale: bool = False, **blocks):
        """``packed_segment_matmul`` with the activation quantization fused
        into its prologue: quantize-dequantize x at the segment's uniform
        ``p`` with per-token scales ``act_scales`` [M, 1] (None = the
        paper-faithful unscaled grid), then the segment GEMM.

        ``in_kernel_scale``: the segment spans the full K row (single-
        segment layer) and the caller asks the kernel to compute the
        per-token abs-max scale itself instead of receiving ``act_scales``
        — the last jnp pass over the activations disappears on backends
        with a self-scale kernel. Only legal with ``act_scales=None`` for
        a whole-row segment under ``per_token`` scaling; the driver gates
        it.

        The base implementation is the two-pass reference composition —
        equal to a fused kernel up to the lowering of the prologue's f32
        divide (a few ulp of the output scale), since fusion only removes
        the HBM round-trip of the quantized activations (and, for the
        self-scale form, of the [M, 1] reduction), not any arithmetic.
        Backends that carry a real fused kernel override this; the shared
        ``packed_matmul`` driver only takes the fused path when they do
        (``supports("fused_act_segment_matmul")``)."""
        if in_kernel_scale:
            assert act_scales is None, "in_kernel_scale computes the scale"
            act_scales = act_scale(x, "per_token")
        kp = x.shape[-1]
        pb = jnp.full((max(kp // group_size, 1),), float(p), jnp.float32)
        s = jnp.asarray(1.0 if act_scales is None else act_scales,
                        jnp.float32)
        xq = quant.fake_quant(x, pb, s, group_size)
        return self.packed_segment_matmul(xq, wp, scales, p=p,
                                          act_quant=False,
                                          group_size=group_size, **blocks)

    def qkv_attn_decode(self, q, cache: Dict, q_pos, *,
                        window: Optional[int] = None, **blocks):
        """Decode attention over one layer's packed 4-bit ring-KV cache
        (DESIGN.md §12). q [B,S,Hk,G,D] with RoPE applied; ``cache`` is a
        quantized ring dict (``k_codes``/``v_codes`` [B,T,Hk,D//2] uint8,
        ``k_scale``/``v_scale`` [B,T,Hk,1] f16, ``pos`` [B,T]); ``q_pos``
        [B,S] absolute positions (< 0 = masked lane). Returns [B,S,Hk,G,D]
        fp32.

        The base implementation is the jnp oracle — dequantize the whole
        cache (``kv_quant.read_qkv_cache``) then masked SDPA — which is
        what ``xla_ref`` runs. Backends carrying a fused kernel that
        unpacks the 2-per-byte codes and applies the per-(slot, head)
        scales inside the attention inner loop (no materialized
        [B,T,Hk,D] dequant buffer) override this; their numerics must stay
        within the pinned KV parity bound of the oracle
        (tests/test_qkv_decode.py)."""
        del blocks                     # block shapes are a kernel concern
        from repro.serve import kv_quant   # lazy: serve imports backend
        k, v, k_pos = kv_quant.read_qkv_cache(cache, jnp.float32)
        return qkv_attn_jnp(q, k, v, k_pos, q_pos, window)

    def qkv_attn_decode_paged(self, q, cache: Dict, q_pos, *,
                              window: Optional[int] = None, **blocks):
        """Decode attention over one layer's *paged* KV cache (DESIGN.md
        §13). Same contract as :meth:`qkv_attn_decode` except the cache is
        a ``serve/kv_pool.py`` paged dict: pool-shaped payload leaves
        (q4 codes/scales or fp k/v, ``[P, page_size, Hk, ...]``), pool
        ``pos [P, page_size]`` stamps and per-slot ``page_table [B, NP]``
        (-1 / null page 0 = unmapped hole). Returns [B,S,Hk,G,D] fp32.

        The base implementation is the gather oracle — ``jnp.take`` each
        slot's pages into a dense [B, NP*page_size, ...] ring view, then
        the same masked SDPA — which is what ``xla_ref`` runs. Backends
        with a real paged kernel walk the page table tile-by-tile with an
        online softmax instead (no dense gather, no [SG, T] score row);
        parity is the same token-identical-greedy bound as the ring op."""
        del blocks
        from repro.serve import kv_pool    # lazy: serve imports backend
        k, v, k_pos = kv_pool.gather_paged(cache, jnp.float32)
        return qkv_attn_jnp(q, k, v, k_pos, q_pos, window)

    def noise_inject(self, w, s, seed, *, group_size: int = GROUP_SIZE,
                     **blocks):
        """Phase-I fused perturbation, differentiable in (w, s) via the
        shared custom VJP (ε recomputed from the hash in the backward)."""
        return _noise_inject(self, w, s, jnp.asarray(seed, jnp.uint32),
                             group_size, tuple(sorted(blocks.items())))

    def _noise_inject_fwd(self, w, s, seed, group_size: int, blocks: Dict):
        """Forward-only noise kernel (wrapped by the custom VJP)."""
        return noise_inject_jnp(w, s, seed, group_size)

    # ------------------------------------------------- shared drivers ------
    def packed_matmul(self, serve_params: Dict, x, qcfg, **blocks):
        """Full serve-mode SmolLinear over a packed leaf: channel perm,
        activation quantization per ``qcfg.act_scale_mode``, one
        per-segment GEMM per non-empty [K4|K2|K1] segment, fp32
        accumulation, bias, cast back to x.dtype.

        The driver is shared so every backend applies *identical*
        activation scaling (the whole-batch-abs-max magnitude leak the
        old kernel wrapper had cannot reappear per-backend) and identical
        segment/accumulation order. Activation quantization has two forms,
        equal to a few ulp (DESIGN.md §11 "Fused activation quantization"):
        the two-pass reference (one whole-K ``fake_quant``, then plain
        segment GEMMs — what ``xla_ref`` always runs) and the fused form
        (the epsilon-clamped per-token scale is still computed here, since
        it spans the full permuted row across segment boundaries, but the
        snap-to-grid moves into the segment kernel's prologue) taken when
        the backend carries ``fused_act_segment_matmul`` and
        ``qcfg.fuse_act_quant`` allows it.

        Draft mode (``qcfg.draft_slice_bits`` — DESIGN.md §14): segments
        whose precision exceeds the bound are skipped, so the GEMM reads
        only the low-bit carriers of the SAME packed buffers — the
        embedded draft model of self-speculative decoding. Nothing is
        renormalized and the activation path is untouched (the per-token
        scale spans the full permuted row either way); a layer holding
        only high-bit segments (e.g. the narrow all-4-bit single-group
        layers) keeps its full mix — it has no cheap slice, and a
        bias-only output would wreck the draft signal downstream of it.
        Skipping happens here, before the in-kernel-scale gate, so a
        filtered single segment that no longer spans K cannot take the
        self-scale path.
        """
        bufs = {name: serve_params[name] for name, _p, _v in
                pack_lib.SEGMENTS}
        k = sum(serve_params[name].shape[0] * v
                for name, _p, v in pack_lib.SEGMENTS)
        g = qcfg.eff_group_size(k)
        segs = list(pack_lib.iter_packed_segments(bufs, g))
        draft_bits = getattr(qcfg, "draft_slice_bits", None)
        if draft_bits is not None:
            global _DRAFT_MATMUL_CALLS
            _DRAFT_MATMUL_CALLS += 1
            low = [s for s in segs if s[1] <= draft_bits]
            if low:
                segs = low
        x = jnp.take(x, serve_params["perm"], axis=-1)
        fused = False
        self_scale = False
        sx = None
        if qcfg.quantize_activations:
            fused = (getattr(qcfg, "fuse_act_quant", True)
                     and self.supports("fused_act_segment_matmul"))
            # Uniform-precision layer (one segment spans the whole K row)
            # under per-token scaling: the [M, K] -> [M, 1] abs-max moves
            # into the fused kernel's prologue too (it no longer crosses a
            # segment boundary). The abs-max is permutation-invariant, so
            # in-kernel reduction over the permuted row matches the
            # driver-side scale to an ulp (DESIGN.md §11).
            self_scale = (fused and qcfg.act_scale_mode == "per_token"
                          and len(segs) == 1 and segs[0][3] == k)
            if not self_scale:
                sx = act_scale(x, qcfg.act_scale_mode)
            if not fused:
                pbits = serve_params.get("pbits_sorted")
                if pbits is None:
                    # Legacy packed dicts may omit the metadata leaf; the
                    # sorted per-group precisions are fully determined by
                    # the carrier shapes.
                    pbits = jnp.asarray(np.concatenate(
                        [np.full(ng, p, np.float32)
                         for _n, p, _o, _kp, _go, ng
                         in pack_lib.iter_packed_segments(bufs, g)]))
                x = self.fake_quant(x, pbits.astype(jnp.float32), sx, g)
        lead = x.shape[:-1]
        x2 = x.reshape(-1, k)
        if fused and not self_scale:
            # One [M, 1] per-token scale operand for every segment kernel
            # (per_tensor / "none" broadcast the same value to each row —
            # bit-identical to the two-pass division by a scalar).
            sx2 = jnp.broadcast_to(
                jnp.asarray(sx, jnp.float32).reshape(-1, 1),
                (x2.shape[0], 1))
        wscale = serve_params.get("wscale")
        n = max(serve_params[name].shape[1]
                for name, _p, _v in pack_lib.SEGMENTS)
        y = jnp.zeros((x2.shape[0], n), jnp.float32)
        for name, p, off, kp, goff, ng in segs:
            seg_scales = None if wscale is None else \
                jax.lax.dynamic_slice_in_dim(wscale, goff, ng)
            # Tag the per-segment GEMM subtree for the static analyzer
            # (repro.analysis.jaxpr_checks): everything traced inside this
            # scope must keep the quantized arithmetic exact — fp32
            # accumulate, no narrowing float converts, no f64.
            with jax.named_scope(SEGMENT_GEMM_SCOPE):
                if self_scale:
                    y = y + self.fused_act_segment_matmul(
                        x2[:, off:off + kp], serve_params[name], seg_scales,
                        None, p=p, group_size=g, in_kernel_scale=True,
                        **blocks)
                elif fused:
                    y = y + self.fused_act_segment_matmul(
                        x2[:, off:off + kp], serve_params[name], seg_scales,
                        sx2, p=p, group_size=g, **blocks)
                else:
                    y = y + self.packed_segment_matmul(
                        x2[:, off:off + kp], serve_params[name], seg_scales,
                        p=p, act_quant=False, group_size=g, **blocks)
        b = serve_params.get("b")
        if b is not None:
            y = y + b.astype(y.dtype)
        return y.reshape(lead + (n,)).astype(x.dtype)

    def packed_matmul_grouped(self, serve_params: Dict, x, qcfg, **blocks):
        """Grouped serve-mode SmolLinear over a *stacked* packed expert
        bank ([E, ...] leaves, as ``api.transforms.convert_tree`` emits for
        MoE expert banks): per-expert channel perm, activation quantization
        per ``qcfg.act_scale_mode``, one grouped GEMM per non-empty
        [K4|K2|K1] segment, fp32 accumulation, bias, cast back to x.dtype.

        x is [E, M, K] (one token block per expert — MoE's static-capacity
        dispatch buffer). The per-expert arithmetic mirrors
        :meth:`packed_matmul` element-for-element, so the grouped path is
        parity-pinned against ``vmap(smol.linear_apply)`` over the same
        stacked bank (tests/test_backend_dispatch.py). Activation
        quantization always takes the two-pass form here (one whole-K
        ``fake_quant``, then plain grouped segment GEMMs) — there is no
        fused grouped prologue yet.

        The stacked layout only exists because ``rebudget`` forces every
        expert onto the identical [K4|K2|K1] split (stacked leaves must
        share packed shapes), which is also what makes one grid cover all
        experts: ``pbits_sorted`` rows are identical across E, while
        ``perm``/``wscale`` stay per-expert.

        Draft mode (``qcfg.draft_slice_bits``) slices segments exactly as
        the single-leaf driver does — same counter, same keep-full-mix
        fallback for layers with no low-bit slice."""
        bufs = {name: serve_params[name] for name, _p, _v in
                pack_lib.SEGMENTS}
        e = x.shape[0]
        k = sum(serve_params[name].shape[1] * v
                for name, _p, v in pack_lib.SEGMENTS)
        g = qcfg.eff_group_size(k)
        # Segment geometry from expert 0's carriers — identical across E
        # by the stacked-shape invariant.
        segs = list(pack_lib.iter_packed_segments(
            {name: bufs[name][0] for name in bufs}, g))
        draft_bits = getattr(qcfg, "draft_slice_bits", None)
        if draft_bits is not None:
            global _DRAFT_MATMUL_CALLS
            _DRAFT_MATMUL_CALLS += 1
            low = [s for s in segs if s[1] <= draft_bits]
            if low:
                segs = low
        perm = serve_params["perm"]                       # [E, K]
        x = jnp.take_along_axis(x, jnp.broadcast_to(
            perm[:, None, :], x.shape).astype(jnp.int32), axis=-1)
        if qcfg.quantize_activations:
            if qcfg.act_scale_mode == "none":
                sx = jnp.asarray(1.0, jnp.float32)
            elif qcfg.act_scale_mode == "per_token":
                sx = quant.abs_max_scale(x, axis=-1).astype(jnp.float32)
            else:
                # per_tensor under vmap reduces each expert's slice — keep
                # that per-expert semantics for oracle parity.
                sx = quant.abs_max_scale(x, axis=(1, 2)).astype(jnp.float32)
            pbits = serve_params.get("pbits_sorted")
            if pbits is None:
                pbits = jnp.asarray(np.concatenate(
                    [np.full(ng, p, np.float32)
                     for _n, p, _o, _kp, _go, ng in segs]))
            else:
                pbits = pbits[0]       # identical rows across experts
            x = self.fake_quant(x, pbits.astype(jnp.float32), sx, g)
        wscale = serve_params.get("wscale")               # [E, NG] or None
        n = max(serve_params[name].shape[2]
                for name, _p, _v in pack_lib.SEGMENTS)
        y = jnp.zeros((e, x.shape[1], n), jnp.float32)
        for name, p, off, kp, goff, ng in segs:
            seg_scales = None if wscale is None else \
                jax.lax.dynamic_slice_in_dim(wscale, goff, ng, axis=1)
            with jax.named_scope(SEGMENT_GEMM_SCOPE):
                y = y + self.packed_segment_matmul_grouped(
                    x[:, :, off:off + kp], serve_params[name], seg_scales,
                    p=p, act_quant=False, group_size=g, **blocks)
        b = serve_params.get("b")
        if b is not None:
            y = y + b[:, None, :].astype(y.dtype)
        return y.astype(x.dtype)

    def quantize_pack_mixed(self, w, pbits, scales=None,
                            group_size: int = GROUP_SIZE) -> Dict:
        """Mixed-precision deploy packing: quantize + bit-pack each
        uniform-precision segment of a [K, N] weight whose sorted
        per-group ``pbits`` define the [K4|K2|K1] split. Same contract as
        ``core.pack.quantize_pack_weight`` (which remains the pure-jnp
        reference); the per-segment packing runs through this backend's
        ``quantize_pack`` op."""
        w = jnp.asarray(w, jnp.float32)
        k, n = w.shape
        pbits = np.asarray(pbits)
        assert pbits.ndim == 1 and pbits.shape[0] * group_size == k, \
            (pbits.shape, k, group_size)
        order = {4: 0, 2: 1, 1: 2}
        ranks = np.array([order[int(p)] for p in pbits])
        assert np.all(np.diff(ranks) >= 0), "pbits must be sorted 4 -> 2 -> 1"
        segs = tuple(int((pbits == p).sum()) * group_size for p in (4, 2, 1))
        if scales is not None:
            scales = jnp.asarray(scales, jnp.float32)
        out = {"segments": segs, "scales": scales, "n": n,
               "group_size": group_size}
        off = goff = 0
        for (name, p, _vpb), kp in zip(pack_lib.SEGMENTS, segs):
            if kp == 0:
                out[name] = jnp.zeros((0, n), jnp.uint8)
                continue
            ng = max(kp // group_size, 1)
            seg_scales = None if scales is None else scales[goff:goff + ng]
            out[name] = self.quantize_pack(w[off:off + kp], seg_scales,
                                           p=p, group_size=group_size)
            off += kp
            goff += ng
        return out

    def __repr__(self) -> str:
        return f"<Backend {self.name}>"
