"""Configuration dataclasses for the SONIQ quantization stack.

Terminology (see DESIGN.md §2):
  * group        — 16 consecutive input channels; the minimum precision-control
                   unit (the TPU analog of the paper's 16-bit SIMD lane).
  * block        — 8 groups = 128 channels; one "vector" in the paper's sense
                   (one TPU vreg lane row). A *pattern* assigns each of the 8
                   groups in a block a precision from {1, 2, 4}.
  * segment      — after PatternMatch + channel reordering, the K (input
                   channel) dim of a weight splits into three contiguous runs
                   [K4 | K2 | K1] of uniform precision.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import numpy as np

from .phases import Phase, PhaseSpec

GROUP_SIZE = 16          # channels per precision group (paper Obs. 5)
GROUPS_PER_BLOCK = 8     # groups per 128-channel block (paper's 128-bit vector)
BLOCK_SIZE = GROUP_SIZE * GROUPS_PER_BLOCK
ALLOWED_BITS = (1, 2, 4)  # paper Obs. 2


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """How SONIQ is applied to the linear layers of a model."""

    # "fp"    : no quantization (full-precision baseline)
    # "noise" : Phase I  — noise-injected precision search (trainable s)
    # "qat"   : Phase II — fixed per-group precisions, STE fine-tuning
    # "serve" : deployment — packed low-bit weights, dequant-in-kernel
    mode: str = "fp"

    group_size: int = GROUP_SIZE
    # Fraction of input-channel groups held at 4 / 2 / 1 bits. Used to size
    # the static [K4|K2|K1] segments for "qat"/"serve" (the trained
    # distribution replaces this at deploy time; the fractions give the
    # dry-run its static shapes). Must sum to 1.
    mix: Tuple[float, float, float] = (0.5, 0.375, 0.125)

    # "none"       : paper-faithful — values live directly on the ±2 SMOL grid
    # "per_group"  : one scale per 16-channel group on K (beyond-paper; needed
    #                for LLM weight distributions)
    scale_mode: str = "per_group"
    # Quantize activations entering each quantized matmul to the same
    # per-group precision (paper Obs. 3 "input-weight consistency").
    quantize_activations: bool = True
    # Dynamic abs-max scaling of activations. "per_tensor" reduces over the
    # whole tensor (training default); "per_token" reduces over the last dim
    # only — row-independent, which the continuous-batching serve engines
    # require (a request's tokens must not depend on batch composition —
    # DESIGN.md §10). Paper-faithful mode ("none") assumes pre-scaled
    # activations in ±2.
    act_scale_mode: str = "per_tensor"

    # Phase-I hyperparameters.
    p_init: int = 4
    lam: float = 1e-7          # λ of the bit-count regularizer

    # Number of hardware-supported patterns (paper's np design knob: 4/8/45).
    num_patterns: int = 4

    # Layers never quantized (paper excludes first/last in practice).
    skip: Tuple[str, ...] = ("embed", "lm_head", "router", "frontend")

    # Kernel backend executing the quantized hot-path ops (packed matmul,
    # quantize+pack, noise inject, fake quant). A registry name
    # ("xla_ref", "pallas_interpret", "pallas_mosaic"), an alias
    # ("pallas" — the best available Pallas flavor for this platform), or
    # None: defer to the SONIQ_BACKEND env var, else negotiate the best
    # available backend for the platform (see repro.backend.registry).
    backend: Optional[str] = None

    # Let a backend that carries a fused activation-quant GEMM prologue
    # (``fused_act_segment_matmul``) use it on the serve path. The fused
    # and two-pass forms agree to a few ulp (DESIGN.md §11), so this stays
    # on;
    # False forces the two-pass reference form everywhere — benchmarks use
    # it to measure the fusion delta, parity tests to pin the exactness.
    fuse_act_quant: bool = True

    # Self-speculative draft forward (DESIGN.md §14). None = the full
    # packed mix (status quo). An int (2 being the natural SONIQ cut)
    # makes every serve-phase packed matmul read ONLY the segments whose
    # precision is <= this bound — the [K2|K1] slice of the same packed
    # carriers, zero extra weight bytes. The high-bit carriers are simply
    # skipped (no renormalization: it is the same kernel over fewer
    # segments), so the output is a cheap approximation of the full-mix
    # forward at a fraction of the weight traffic. Used by the engine's
    # draft steps; verification always runs the full mix, which is what
    # keeps speculative greedy decode token-identical.
    draft_slice_bits: Optional[int] = None

    # DEPRECATED — legacy boolean knob, superseded by ``backend``.
    # use_pallas=True is interpreted as backend="pallas" when ``backend``
    # is unset.
    use_pallas: bool = False

    # Weights arrive already fake-quantized (set by the hoisted-quantization
    # train path: quantize once per step, not once per microbatch — §Perf).
    prequantized: bool = False

    def __post_init__(self):
        if isinstance(self.mode, PhaseSpec):   # accept QuantConfig(mode=Phase.QAT)
            object.__setattr__(self, "mode", self.mode.name)
        assert self.mode in ("fp", "noise", "qat", "serve"), self.mode
        assert self.scale_mode in ("none", "per_group"), self.scale_mode
        assert self.act_scale_mode in ("none", "per_tensor", "per_token"), \
            self.act_scale_mode
        assert abs(sum(self.mix) - 1.0) < 1e-6, self.mix
        assert self.group_size % 2 == 0
        assert self.backend is None or isinstance(self.backend, str), \
            self.backend  # names are validated by the registry at resolve
        assert self.draft_slice_bits is None \
            or self.draft_slice_bits in ALLOWED_BITS, self.draft_slice_bits

    @property
    def backend_name(self) -> Optional[str]:
        """The backend selector the dispatch registry should resolve:
        ``backend`` if set, the "pallas" alias for the legacy
        ``use_pallas`` flag, else None (env var / auto-negotiation)."""
        if self.backend is not None:
            return self.backend
        return "pallas" if self.use_pallas else None

    # ----------------------------------------------------------- phases ----
    @property
    def phase(self) -> PhaseSpec:
        """The typed lifecycle phase this config selects (Phase.FP/NOISE/
        QAT/SERVE)."""
        return Phase.from_mode(self.mode)

    def with_mode(self, mode) -> "QuantConfig":
        """Copy of this config in another phase (string or Phase object)."""
        return dataclasses.replace(self, mode=Phase.from_mode(mode).name)

    # --------------------------------------------------- group geometry ----
    def eff_group_size(self, k: int) -> int:
        """Effective precision-group size for a K-dim of ``k`` channels: a
        layer narrower than ``group_size`` forms one whole group."""
        return k if k < self.group_size else self.group_size

    def num_groups(self, k: int) -> int:
        g = self.eff_group_size(k)
        assert k % g == 0, f"K={k} not a multiple of group size {g}"
        return k // g

    def group_counts(self, k: int) -> Tuple[int, int, int]:
        """(#4-bit, #2-bit, #1-bit) groups implementing ``mix`` over the
        ``num_groups(k)`` groups of a K-dim (4s first — segment order).
        A layer narrower than ``group_size`` is a single group held at 4
        bits: the sub-byte carriers of the low-precision segments need not
        divide such a k, and a narrow layer is too small to be worth the
        risk of mix-rounding it to 1 bit."""
        if k < self.group_size:
            return 1, 0, 0
        n = self.num_groups(k)
        g4 = min(int(round(self.mix[0] * n)), n)
        g2 = min(int(round(self.mix[1] * n)), n - g4)
        return g4, g2, n - g4 - g2

    def group_pbits(self, k: int) -> np.ndarray:
        """Static per-group precisions implementing ``mix``, sorted 4->2->1
        (segment-contiguous). Replaced by trained precisions after Phase I."""
        g4, g2, g1 = self.group_counts(k)
        return np.array([4] * g4 + [2] * g2 + [1] * g1, np.int8)

    def segments(self, k: int) -> Tuple[int, int, int]:
        """Split ``k`` input channels into (K4, K2, K1) — contiguous runs of
        uniform precision, each a multiple of ``eff_group_size(k)`` (and the
        total exactly ``k``). Mirrors the paper's post-training channel
        reordering. ``k < group_size`` forms a single group and lands
        entirely in one segment (consistent with ``group_pbits``).
        """
        g = self.eff_group_size(k)
        g4, g2, g1 = self.group_counts(k)
        return g4 * g, g2 * g, g1 * g

    def bits_per_param(self, k: Optional[int] = None) -> float:
        """Average bits per parameter implied by the mix (ignoring metadata,
        which is 3 ints per segment — paper Obs. 4)."""
        if k is None:
            f4, f2, f1 = self.mix
            return 4 * f4 + 2 * f2 + 1 * f1
        k4, k2, k1 = self.segments(k)
        return (4 * k4 + 2 * k2 + 1 * k1) / k


# Convenience presets matching the paper's design points (§V-A).
FP32 = QuantConfig(mode="fp")
U4 = QuantConfig(mode="qat", mix=(1.0, 0.0, 0.0))
U2 = QuantConfig(mode="qat", mix=(0.0, 1.0, 0.0))
P4 = QuantConfig(mode="qat", mix=(0.5, 0.375, 0.125), num_patterns=4)
P8 = QuantConfig(mode="qat", mix=(0.5, 0.375, 0.125), num_patterns=8)
P45 = QuantConfig(mode="qat", mix=(0.5, 0.375, 0.125), num_patterns=45)
