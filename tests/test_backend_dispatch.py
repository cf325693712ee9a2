"""Kernel-backend dispatch registry: selection semantics, a backend × op
parity matrix against the ``kernels/ref.py`` oracles, activation-scale-mode
parity between the kernel and jnp paths, the block-size autotune cache, and
an end-to-end DecodeEngine smoke run that must be token-identical across
selectable backends (DESIGN.md §11)."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backend import (BackendUnavailable, autotune, available,
                           current_backend, registry, resolve, use_backend)
from repro.configs.base import ArchConfig
from repro.core import pack as pack_lib
from repro.core import quant, smol
from repro.core.qtypes import QuantConfig
from repro.kernels import ref
from repro.models import lm
from repro.serve import engine

# Every backend that can run in this environment (on CPU: xla_ref +
# pallas_interpret; on TPU also pallas_mosaic).
BACKENDS = available()


def _rand_packed(key, kp, n, p):
    u = jax.random.randint(key, (kp, n), 0, 2 ** p).astype(jnp.uint8)
    return pack_lib.pack_codes(u, p)


def _serve_leaf(k=256, n=128, key=0):
    qcfg = QuantConfig(mode="qat", mix=(0.5, 0.25, 0.25))
    params = smol.linear_init(jax.random.PRNGKey(key), k, n, qcfg)
    params["pbits"] = jnp.asarray(
        np.array([4, 1, 2, 4, 2, 1, 4, 4, 1, 2, 4, 2, 1, 4, 4, 2], np.int8))
    from repro.api import transforms
    return transforms.pack_linear(params, qcfg), qcfg


def test_single_segment_routes_in_kernel_scale(monkeypatch):
    """The shared driver hands the per-token abs-max to the kernel
    (``in_kernel_scale=True``) exactly when one uniform-precision segment
    spans the whole K row under per_token scaling — never for mixed
    segment layouts or non-per-token modes (the scale then spans kernel
    boundaries / isn't a row reduction)."""
    from repro.api import transforms
    b = resolve("pallas_interpret")
    seen = []
    orig = type(b).fused_act_segment_matmul     # pre-patch, via the MRO

    def spy(self, x, wp, scales=None, act_scales=None, *,
            in_kernel_scale=False, **kw):
        seen.append(in_kernel_scale)
        return orig(self, x, wp, scales, act_scales,
                    in_kernel_scale=in_kernel_scale, **kw)

    monkeypatch.setattr(type(b), "fused_act_segment_matmul", spy)
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 64))
    qat = QuantConfig(mode="qat")
    uni = transforms.pack_linear(
        {"w": jax.random.normal(jax.random.PRNGKey(1), (64, 32)),
         "pbits": np.full((4,), 4, np.int8)}, qat)
    mixed = transforms.pack_linear(
        {"w": jax.random.normal(jax.random.PRNGKey(2), (64, 32)),
         "pbits": np.asarray([4, 4, 2, 1], np.int8)}, qat)
    for sp, mode, want in ((uni, "per_token", [True]),
                           (uni, "per_tensor", [False]),
                           (uni, "none", [False]),
                           (mixed, "per_token", [False] * 3)):
        seen.clear()
        y = b.packed_matmul(sp, x, QuantConfig(mode="serve",
                                               act_scale_mode=mode))
        assert seen == want, (mode, seen)
        assert np.isfinite(np.asarray(y)).all()


# ---------------------------------------------------------- registry ----
def test_builtin_backends_registered():
    assert {"xla_ref", "pallas_interpret", "pallas_mosaic"} <= set(
        registry.names())
    assert "xla_ref" in BACKENDS and "pallas_interpret" in BACKENDS


def test_unknown_backend_raises():
    with pytest.raises(BackendUnavailable, match="unknown backend"):
        resolve("triton_gpu")


def test_explicit_unavailable_backend_never_falls_back():
    """Naming a backend that cannot run here must raise, not silently
    degrade — the CI matrix depends on this."""
    if jax.default_backend() == "tpu":
        pytest.skip("pallas_mosaic is available on TPU")
    with pytest.raises(BackendUnavailable, match="never"):
        resolve("pallas_mosaic")


def test_env_var_selection(monkeypatch):
    monkeypatch.setenv(registry.ENV_VAR, "pallas_interpret")
    assert resolve().name == "pallas_interpret"
    assert current_backend().name == "pallas_interpret"
    monkeypatch.setenv(registry.ENV_VAR, "no_such_backend")
    with pytest.raises(BackendUnavailable):
        resolve()


def test_env_var_matrix_honored():
    """Whatever SONIQ_BACKEND the harness set (the CI two-way matrix) is
    exactly what unpinned dispatch resolves to."""
    env = os.environ.get(registry.ENV_VAR, "").strip()
    if not env:
        pytest.skip("SONIQ_BACKEND not set")
    assert resolve().name == env


def test_use_backend_context_wins_and_restores(monkeypatch):
    monkeypatch.delenv(registry.ENV_VAR, raising=False)
    before = resolve().name
    with use_backend("pallas_interpret") as b:
        assert b.name == "pallas_interpret"
        # the context outranks explicit config names too
        assert resolve("xla_ref").name == "pallas_interpret"
    assert resolve().name == before


def test_supports_capability_probe():
    from repro.backend import OPS
    assert set(autotune.DEFAULT_BLOCKS) <= set(OPS)
    pal = resolve("pallas_interpret")
    for op in ("packed_segment_matmul", "fused_act_segment_matmul",
               "quantize_pack", "noise_inject", "fake_quant",
               "qkv_attn_decode"):
        assert pal.supports(op), op          # own Pallas kernels
    assert not pal.supports("packed_matmul")  # shared driver
    xla = resolve("xla_ref")
    assert xla.supports("packed_segment_matmul")
    assert not xla.supports("noise_inject")  # shared hash implementation
    assert not xla.supports("fake_quant")    # shared STE implementation
    # xla_ref must stay on the two-pass activation-quant form — it is the
    # exactness oracle the fused Pallas prologue is gated against; same
    # for the dequantize-everything quantized-KV decode oracle.
    assert not xla.supports("fused_act_segment_matmul")
    assert not xla.supports("qkv_attn_decode")


def test_pallas_alias_negotiates():
    b = resolve("pallas")
    expect = "pallas_mosaic" if jax.default_backend() == "tpu" \
        else "pallas_interpret"
    assert b.name == expect


def test_quantconfig_backend_flows_to_dispatch(monkeypatch):
    monkeypatch.delenv(registry.ENV_VAR, raising=False)
    qcfg = QuantConfig(mode="serve", backend="pallas_interpret")
    assert qcfg.backend_name == "pallas_interpret"
    legacy = QuantConfig(mode="serve", use_pallas=True)
    assert legacy.backend_name == "pallas"
    assert resolve(legacy.backend_name).name.startswith("pallas_")


# ------------------------------------------- backend x op parity matrix ----
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("p", [1, 2, 4])
@pytest.mark.parametrize("m,kp,n", [(8, 128, 128), (16, 512, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matrix_packed_segment_matmul(backend, p, m, kp, n, dtype):
    key = jax.random.PRNGKey(p * 1000 + m + kp + n)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (m, kp), dtype)
    wp = _rand_packed(k2, kp, n, p)
    scales = jax.random.uniform(k3, (kp // 16,), jnp.float32, 0.5, 2.0)
    got = resolve(backend).packed_segment_matmul(x, wp, scales, p=p)
    want = ref.packed_segment_matmul_ref(x, wp, scales, p)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("p", [1, 2, 4])
def test_matrix_quantize_pack(backend, p):
    key = jax.random.PRNGKey(p)
    w = jax.random.normal(key, (128, 128)) * 0.8
    scales = jax.random.uniform(jax.random.PRNGKey(1), (8,),
                                jnp.float32, 0.5, 1.5)
    got = resolve(backend).quantize_pack(w, scales, p=p)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.quantize_pack_ref(
                                      w, p, scales)))


@pytest.mark.parametrize("backend", BACKENDS)
def test_matrix_noise_inject(backend):
    key = jax.random.PRNGKey(3)
    w = jax.random.normal(key, (128, 256)) * 0.5
    s = jax.random.normal(jax.random.PRNGKey(1), (8,))
    got = resolve(backend).noise_inject(w, s, 1234)
    want = ref.noise_inject_ref(w, s, 1234)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("backend", BACKENDS)
def test_matrix_noise_inject_grad(backend):
    """Phase-I training must work under every backend: the shared custom
    VJP makes the (w, s) gradient exact even where the forward is a
    Pallas call."""
    b = resolve(backend)
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 128)) * 0.5
    s = jnp.zeros((4,))

    def loss(w, s):
        return jnp.sum(b.noise_inject(w, s, jnp.uint32(7)) ** 2)

    gw, gs = jax.jit(jax.grad(loss, argnums=(0, 1)))(w, s)
    assert np.isfinite(np.asarray(gw)).all()
    assert np.isfinite(np.asarray(gs)).all()
    assert float(jnp.abs(gs).max()) > 0
    gw_ref, gs_ref = jax.grad(loss_ref := lambda w, s: jnp.sum(
        resolve("xla_ref").noise_inject(w, s, jnp.uint32(7)) ** 2),
        argnums=(0, 1))(w, s)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gs_ref),
                               atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_matrix_fake_quant(backend):
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
    pbits = jnp.asarray(np.array([4, 2, 1, 4, 2, 1, 4, 4], np.float32))
    scale = quant.abs_max_scale(x, axis=-1)
    got = resolve(backend).fake_quant(x, pbits, scale, 16)
    want = quant.fake_quant(x, pbits, scale, 16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("backend", BACKENDS)
def test_matrix_full_packed_matmul_vs_serve_rule(backend):
    """The backend driver must match the phase-rule output exactly when
    that rule is pinned to the same backend, and match the xla_ref
    reference to fp32 tolerance regardless."""
    sp, qcfg = _serve_leaf()
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 256))
    qserve = QuantConfig(mode="serve", mix=qcfg.mix, backend=backend)
    y_rule = smol.linear_apply(sp, x, qserve)
    y_drv = resolve(backend).packed_matmul(sp, x, qserve)
    np.testing.assert_array_equal(np.asarray(y_rule), np.asarray(y_drv))
    y_ref = resolve("xla_ref").packed_matmul(
        sp, x, QuantConfig(mode="serve", mix=qcfg.mix))
    np.testing.assert_allclose(np.asarray(y_drv), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_matrix_pack_linear_identical_codes(backend):
    """Deploy-time packing emits identical uint8 carriers on every
    backend (integer outputs leave no tolerance to hide behind)."""
    from repro.api import transforms
    qcfg = QuantConfig(mode="qat", mix=(0.5, 0.25, 0.25), backend=backend)
    params = smol.linear_init(jax.random.PRNGKey(0), 128, 64, qcfg)
    sp = transforms.pack_linear(params, qcfg)
    sp_ref = transforms.pack_linear(
        params, QuantConfig(mode="qat", mix=qcfg.mix, backend="xla_ref"))
    for name in ("w4", "w2", "w1"):
        np.testing.assert_array_equal(np.asarray(sp[name]),
                                      np.asarray(sp_ref[name]))


# ------------------------------------- fused activation-quant prologue ----
@pytest.mark.parametrize("mode", ["per_token", "per_tensor", "none"])
def test_fused_prologue_bit_exact_vs_two_pass(mode):
    """The fused activation-quant prologue must be *bit-exact* against the
    two-pass form on the same backend: fusion removes the HBM round-trip
    of the quantized activations, not any arithmetic (DESIGN.md §11)."""
    sp, qcfg = _serve_leaf()
    b = resolve("pallas_interpret")
    x = jax.random.normal(jax.random.PRNGKey(9), (6, 256)) * 1.3
    q_fused = QuantConfig(mode="serve", mix=qcfg.mix, act_scale_mode=mode)
    q_two = dataclasses.replace(q_fused, fuse_act_quant=False)
    np.testing.assert_array_equal(
        np.asarray(b.packed_matmul(sp, x, q_fused)),
        np.asarray(b.packed_matmul(sp, x, q_two)))


def test_pallas_driver_engages_fused_prologue():
    """Under a Pallas backend the serve driver must dispatch the fused
    kernel (not the jnp fallback, not the two-pass form) — the perf claim
    of the fusion depends on this actually being the hot path."""
    from repro.backend import pallas as pallas_mod
    sp, qcfg = _serve_leaf()
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 256))
    before = pallas_mod.kernel_call_counts()["fused_act_segment_matmul"]
    smol.linear_apply(sp, x, QuantConfig(mode="serve", mix=qcfg.mix,
                                         backend="pallas_interpret"))
    assert pallas_mod.kernel_call_counts()["fused_act_segment_matmul"] > before
    # ...and fuse_act_quant=False really does pin the two-pass form.
    before = pallas_mod.kernel_call_counts()["fused_act_segment_matmul"]
    smol.linear_apply(sp, x, QuantConfig(mode="serve", mix=qcfg.mix,
                                         backend="pallas_interpret",
                                         fuse_act_quant=False))
    assert pallas_mod.kernel_call_counts()["fused_act_segment_matmul"] \
        == before


@pytest.mark.parametrize("backend", BACKENDS)
def test_zero_row_act_scale_is_finite(backend):
    """Regression (satellite fix): an all-zero activation row — a padding
    slot fresh from reset_cache_slots — must not make the per-token
    abs-max a 0 divisor (NaN/Inf logits). The epsilon clamp lives in the
    shared driver's act_scale and therefore also feeds the fused
    prologue."""
    sp, qcfg = _serve_leaf()
    b = resolve(backend)
    q = QuantConfig(mode="serve", mix=qcfg.mix, act_scale_mode="per_token")
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 256))
    xz = x.at[2].set(0.0)
    y = np.asarray(b.packed_matmul(sp, xz, q))
    assert np.isfinite(y).all()
    # the zero row must not perturb the other rows either
    np.testing.assert_array_equal(
        np.asarray(b.packed_matmul(sp, x, q))[[0, 1, 3]], y[[0, 1, 3]])


@pytest.mark.parametrize("backend", BACKENDS)
def test_matrix_fake_quant_grad(backend):
    """QAT must differentiate through every backend's fake_quant forward
    (fused Pallas kernel included) with gradients identical to the jnp
    clipped STE — compared jit-to-jit, since XLA fusion of the *reference*
    differs between eager and jit at the ulp level."""
    b = resolve(backend)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
    pbits = jnp.asarray(np.array([4, 2, 1, 4, 2, 1, 4, 4], np.float32))

    def loss(x, fq):
        sx = quant.abs_max_scale(x, axis=-1)
        return jnp.sum(fq(x, pbits, sx, 16) ** 2)

    got = jax.jit(jax.grad(lambda x: loss(x, b.fake_quant)))(x)
    want = jax.jit(jax.grad(lambda x: loss(x, quant.fake_quant)))(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------- activation scaling (satellite fix) ----
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("mode", ["per_token", "per_tensor", "none"])
def test_act_scale_mode_parity_kernel_vs_jnp(backend, mode):
    """The old kernel wrapper hard-coded a whole-batch abs-max scale; the
    driver must honor every QuantConfig.act_scale_mode and agree with the
    jnp path token-for-token."""
    sp, qcfg = _serve_leaf()
    x = jax.random.normal(jax.random.PRNGKey(5), (6, 256)) * 1.7
    q = QuantConfig(mode="serve", mix=qcfg.mix, act_scale_mode=mode)
    want = resolve("xla_ref").packed_matmul(sp, x, q)
    got = resolve(backend).packed_matmul(sp, x, q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_per_token_scale_is_row_independent(backend):
    """The cross-request magnitude leak (PR 2) must not reappear in any
    backend: with per_token scaling, a row's output cannot depend on what
    else is in the batch."""
    sp, qcfg = _serve_leaf()
    q = QuantConfig(mode="serve", mix=qcfg.mix, act_scale_mode="per_token")
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 256))
    big = x.at[3].set(x[3] * 100.0)         # an outlier row
    b = resolve(backend)
    np.testing.assert_array_equal(
        np.asarray(b.packed_matmul(sp, x, q))[:3],
        np.asarray(b.packed_matmul(sp, big, q))[:3])
    # ...whereas per_tensor (the training default) does couple rows:
    q_t = QuantConfig(mode="serve", mix=qcfg.mix,
                      act_scale_mode="per_tensor")
    assert not np.array_equal(
        np.asarray(b.packed_matmul(sp, x, q_t))[:3],
        np.asarray(b.packed_matmul(sp, big, q_t))[:3])


# ------------------------------------------------- engine smoke matrix ----
@pytest.fixture(scope="module")
def packed_checkpoint():
    cfg = ArchConfig(
        name="t", family="dense", num_layers=2, d_model=64, num_heads=2,
        num_kv_heads=2, d_ff=128, vocab_size=128, head_dim=32,
        dtype="float32", param_dtype="float32", q_block=32,
        quant=QuantConfig(mode="qat"))
    params = jax.device_get(lm.init_params(jax.random.PRNGKey(0), cfg))
    from repro.api import transforms
    serve_cfg = cfg.with_quant_mode("serve")
    packed = transforms.convert_tree(params, serve_cfg.quant,
                                     rebudget=True)
    return cfg, packed


def test_decode_engine_token_identical_across_backends(packed_checkpoint):
    """Acceptance bar: greedy decode over the SAME packed checkpoint is
    token-identical on every selectable backend, with selection flowing
    only through the registry (EngineConfig.backend)."""
    cfg, packed = packed_checkpoint
    prompts = np.array([[5, 9, 2, 71], [33, 4, 17, 8]], np.int32)
    outs = {}
    for name in BACKENDS:
        ecfg = engine.EngineConfig(max_batch=2, cache_len=32,
                                   prefill_chunk=2, backend=name)
        eng = engine.DecodeEngine(packed, cfg, ecfg, already_serve=True)
        outs[name] = eng.generate(prompts, 6)
    base = outs["xla_ref"]
    assert base.shape == (2, 10)
    for name, toks in outs.items():
        np.testing.assert_array_equal(base, toks, err_msg=name)


# ----------------------------------------------------------- autotune ----
def test_autotune_cache_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv(autotune.ENV_CACHE, str(tmp_path / "at.json"))
    autotune.invalidate()
    shape = (8, 128, 128)
    b = resolve("pallas_interpret")
    assert autotune.lookup("packed_segment_matmul", shape=shape, p=4,
                           dtype="float32", backend=b.name) == {}
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (8, 128))
    wp = _rand_packed(key, 128, 128, 4)

    def call(**blocks):
        return b.packed_segment_matmul(x, wp, None, p=4, **blocks)

    cands = [{"block_m": 8, "block_n": 128, "block_k": 128},
             {"block_m": 8, "block_n": 64, "block_k": 64}]
    best = autotune.autotune_op(call, "packed_segment_matmul", shape=shape,
                                p=4, dtype="float32", candidates=cands,
                                iters=1, backend=b.name)
    assert best in cands
    # persisted: a fresh in-memory cache reloads the same entry (keys are
    # per-backend — interpret and mosaic timings must not mix)
    autotune.invalidate()
    assert autotune.lookup("packed_segment_matmul", shape=shape, p=4,
                           dtype="float32", backend=b.name) == best
    assert autotune.lookup("packed_segment_matmul", shape=shape, p=4,
                           dtype="float32", backend="pallas_mosaic") == {}
    # and the backend consults it on the next call (smoke: still correct)
    y = call()
    # atol matters: a split-K winner changes fp32 summation order, so
    # near-zero outputs can carry ~1e-6 absolute error vs the single-dot
    # oracle (same tolerance as the parity matrix above).
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ref.packed_segment_matmul_ref(
            x, wp, None, 4)), rtol=1e-5, atol=1e-5)


def test_autotune_candidates_are_legal():
    for op in ("packed_segment_matmul", "fused_act_segment_matmul"):
        for blocks in autotune.candidates_for(op, (24, 160, 96)):
            assert 24 % blocks["block_m"] == 0
            assert 96 % blocks["block_n"] == 0
            assert 160 % blocks["block_k"] == 0 and \
                blocks["block_k"] % 16 == 0
    for blocks in autotune.candidates_for("fake_quant", (24, 160)):
        assert 24 % blocks["block_m"] == 0
        assert 160 % blocks["block_k"] == 0 and blocks["block_k"] % 16 == 0


def test_autotune_save_merges_concurrent_writers(tmp_path, monkeypatch):
    """Regression (satellite fix): save_entry must read-merge-save against
    the *live* file, not dump its possibly stale in-memory snapshot — two
    concurrent --autotune sweeps used to clobber each other's entries."""
    path = tmp_path / "at.json"
    monkeypatch.setenv(autotune.ENV_CACHE, str(path))
    autotune.invalidate()
    autotune.save_entry("keyA", {"block_m": 8}, 1.0, 1)
    assert autotune._load() == json.loads(path.read_text())
    # Another process persists keyB after our in-memory snapshot loaded.
    data = json.loads(path.read_text())
    data["keyB"] = {"blocks": {"block_m": 16}, "us": 2.0, "candidates": 1}
    path.write_text(json.dumps(data))
    autotune.save_entry("keyC", {"block_m": 32}, 3.0, 1)
    final = json.loads(path.read_text())
    assert set(final) == {"keyA", "keyB", "keyC"}
    # nothing but the cache file is left behind (no orphaned temp files)
    assert [p.name for p in tmp_path.iterdir()] == ["at.json"]
