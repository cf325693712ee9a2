"""Property tests for the fused activation-quantization paths (DESIGN.md
§11 "Fused activation quantization").

The contract under test: fusing the activation fake-quant into the Pallas
segment-GEMM prologue (serve) removes HBM traffic, not arithmetic — so
fused outputs equal the two-pass ``act_scale`` + ``fake_quant`` + matmul
reference on the same backend to a few ulp of the output scale, across
every segment layout (all-4 / all-2 / all-1 / mixed, K narrower than a
group) and every ``act_scale_mode`` (per_token / per_tensor / none),
including degenerate all-zero and outlier rows. Not bitwise: the
interpret-mode lowering of the installed JAX, and Mosaic on the chip,
may turn the prologue's f32 divide by the scale into a reciprocal
multiply, one ulp off the two-pass form's divide. The QAT fake_quant
kernel has no such divide-by-constant and stays bit-exact.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.api import transforms
from repro.backend import resolve
from repro.core import quant
from repro.core.qtypes import QuantConfig


# A few ulp of the row's output scale (its largest |y|). A one-ulp change
# in the activation scale moves every quantized activation by an ulp; over
# K terms with cancellation that reached 5 ulp of max|y| in 300 drawn
# cases.
ULPS = 16


def _assert_within_ulps(got, want):
    scale = np.abs(want).max(axis=-1, keepdims=True)
    tol = ULPS * np.finfo(np.float32).eps * np.maximum(scale, 1e-30)
    assert (np.abs(got - want) <= tol).all(), np.abs(got - want).max()


def _packed_leaf(pbits, k, n, seed):
    qcfg = QuantConfig(mode="qat")
    w = jax.random.normal(jax.random.PRNGKey(seed), (k, n)) * 0.7
    return transforms.pack_linear(
        {"w": w, "pbits": np.asarray(pbits, np.int8)}, qcfg)


@st.composite
def _serve_cases(draw):
    if draw(st.booleans(), label="narrow"):
        k = draw(st.sampled_from([4, 8, 12]))    # K < group: one 4-bit group
        pbits = [4]
    else:
        ngroups = draw(st.integers(1, 8))
        pbits = draw(st.lists(st.sampled_from([4, 2, 1]),
                              min_size=ngroups, max_size=ngroups))
        k = 16 * ngroups
    m = draw(st.integers(1, 6))
    seed = draw(st.integers(0, 2 ** 16))
    mode = draw(st.sampled_from(["per_token", "per_tensor", "none"]))
    zero_row = draw(st.booleans())
    outlier_row = draw(st.booleans())
    return pbits, k, m, seed, mode, zero_row, outlier_row


@settings(max_examples=25, deadline=None)
@given(_serve_cases())
def test_fused_prologue_equals_two_pass_bit_exact(case):
    pbits, k, m, seed, mode, zero_row, outlier_row = case
    sp = _packed_leaf(pbits, k, 32, seed)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (m, k)) * 1.5
    if zero_row:
        x = x.at[0].set(0.0)                     # padding / fresh slot row
    if outlier_row:
        x = x.at[m - 1].multiply(100.0)
    b = resolve("pallas_interpret")
    q_fused = QuantConfig(mode="serve", act_scale_mode=mode)
    q_two = dataclasses.replace(q_fused, fuse_act_quant=False)
    y_fused = np.asarray(b.packed_matmul(sp, x, q_fused))
    y_two = np.asarray(b.packed_matmul(sp, x, q_two))
    _assert_within_ulps(y_fused, y_two)
    assert np.isfinite(y_fused).all()
    # and so does the xla_ref two-pass oracle. (An absolute 1e-5 is below
    # one f32 ulp of an outlier row's outputs, ~6e-5 at |y| ~ 700, so it
    # cannot hold where such a row's outputs cancel to near zero.)
    y_ref = np.asarray(resolve("xla_ref").packed_matmul(sp, x, q_fused))
    _assert_within_ulps(y_fused, y_ref)


@st.composite
def _selfscale_cases(draw):
    ngroups = draw(st.integers(1, 8))
    k = 16 * ngroups
    p = draw(st.sampled_from([4, 2, 1]))     # uniform precision: 1 segment
    m = draw(st.integers(1, 6))
    n = draw(st.sampled_from([8, 32]))
    seed = draw(st.integers(0, 2 ** 16))
    zero_row = draw(st.booleans())
    outlier_row = draw(st.booleans())
    return p, k, m, n, seed, zero_row, outlier_row


@settings(max_examples=25, deadline=None)
@given(_selfscale_cases())
def test_in_kernel_selfscale_equals_driver_scale_bit_exact(case):
    """For a uniform-precision (single-segment) layer the per-token abs-max
    moves into the fused kernel's prologue (``in_kernel_scale=True``). It
    equals the driver-scale fused form — and therefore the two-pass
    reference — to a few ulp of the output scale, zero rows
    (ACT_SCALE_EPS clamp) and outliers included. The grid-top divide may
    lower as a reciprocal multiply, so not bitwise (module docstring)."""
    p, k, m, n, seed, zero_row, outlier_row = case
    sp = _packed_leaf([p] * (k // 16), k, n, seed)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (m, k)) * 1.5
    if zero_row:
        x = x.at[0].set(0.0)
    if outlier_row:
        x = x.at[m - 1].multiply(100.0)
    from repro.backend.base import act_scale
    b = resolve("pallas_interpret")
    name = {4: "w4", 2: "w2", 1: "w1"}[p]
    wp = sp[name]
    scales = sp.get("wscale")
    sx = jnp.broadcast_to(act_scale(x, "per_token").reshape(-1, 1), (m, 1))
    y_self = np.asarray(b.fused_act_segment_matmul(
        x, wp, scales, None, p=p, in_kernel_scale=True))
    y_driver = np.asarray(b.fused_act_segment_matmul(
        x, wp, scales, sx, p=p))
    y_two = np.asarray(resolve("xla_ref").fused_act_segment_matmul(
        x, wp, scales, None, p=p, in_kernel_scale=True))
    _assert_within_ulps(y_self, y_driver)
    _assert_within_ulps(y_self, y_two)
    assert np.isfinite(y_self).all()


@st.composite
def _fake_quant_cases(draw):
    ngroups = draw(st.integers(1, 8))
    pbits = draw(st.lists(st.sampled_from([4, 2, 1]),
                          min_size=ngroups, max_size=ngroups))
    m = draw(st.integers(1, 8))
    seed = draw(st.integers(0, 2 ** 16))
    scale_kind = draw(st.sampled_from(["per_row", "per_group", "scalar"]))
    return pbits, m, seed, scale_kind


@settings(max_examples=25, deadline=None)
@given(_fake_quant_cases())
def test_pallas_fake_quant_matches_jnp_bit_exact(case):
    pbits, m, seed, scale_kind = case
    k = 16 * len(pbits)
    x = jax.random.normal(jax.random.PRNGKey(seed), (m, k)) * 1.3
    pb = jnp.asarray(np.asarray(pbits, np.float32))
    if scale_kind == "per_row":
        scale = quant.abs_max_scale(x, axis=-1)
    elif scale_kind == "per_group":
        scale = quant.per_group_weight_scale(x.T, 16)
    else:
        scale = 1.0
    got = resolve("pallas_interpret").fake_quant(x, pb, scale, 16)
    want = quant.fake_quant(x, pb, scale, 16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
