"""One serving fast path for every registered arch (DESIGN.md §17).

Family x layout x backend greedy-parity matrix: dense / SSM / hybrid /
MoE / enc-dec served through the same ``DecodeEngine`` loop, with

  * greedy tokens identical across ``xla_ref`` and ``pallas_interpret``,
  * chunked prefill (C tokens/step) token-identical to token-level
    prefill (chunk=1) — the SSM/hybrid legs pin the ``ssd_chunked``
    state threading, the MoE leg pins drop-free serve capacity,
  * speculative decoding token-identical to spec-off on every family
    (snapshot/recommit flavor for recurrent state),
  * dispatch counters proving the grouped packed-MoE kernel actually
    ran (not the per-expert vmap fallback),
  * the capability gate raising with the family name when a descriptor
    turns ``spec_capable`` off,
  * quantized cross-attention K/V (enc-dec) accounted separately from
    the ring payload,

plus unit pins for ``ssd_chunked`` against the naive ``ssd_reference``
oracle (carried initial state, non-multiple lengths).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backend import pallas as pallas_backend
from repro.backend import registry as backend_registry
from repro.configs import registry
import repro.configs  # noqa: F401  (populates the registry)
from repro.models import lm, ssm
from repro.serve import engine, kv_quant
from repro.serve.scheduler import Request

FAMILIES = {
    "dense": "starcoder2-7b",
    "ssm": "mamba2-2.7b",
    "hybrid": "jamba-1.5-large-398b",
    "moe": "mixtral-8x22b",
    "encdec": "whisper-medium",
}

# Lazy per-family caches: params init and the xla_ref chunked baseline
# are shared across the backend / chunk-width / spec legs so each
# family pays for one model init and one reference serve.
_SETUP = {}
_BASELINE = {}


def _setup(fam):
    if fam not in _SETUP:
        cfg = registry.get_config(FAMILIES[fam]).reduced()
        params = jax.device_get(lm.init_params(jax.random.PRNGKey(0), cfg))
        enc_out = None
        if cfg.encoder_layers:
            enc_out = np.random.default_rng(0).normal(
                0, 1, (3, 12, cfg.d_model)).astype(np.float32)
        rng = np.random.default_rng(1)
        reqs = [Request(prompt=rng.integers(1, 100, (n,)), max_new_tokens=m,
                        seed=i)
                for i, (n, m) in enumerate(zip((3, 9, 5), (5, 4, 4)))]
        _SETUP[fam] = (cfg, params, enc_out, reqs)
    return _SETUP[fam]


def _ecfg(**kw):
    base = dict(max_batch=3, cache_len=32, prefill_chunk=4,
                backend="xla_ref")
    base.update(kw)
    return engine.EngineConfig(**base)


def _tokens_of(eng, reqs, enc_out=None):
    if enc_out is not None:
        eng.load_cross(enc_out)
    got = {c.request_id: c.tokens for c in eng.serve(
        [dataclasses.replace(r) for r in reqs])}
    return {rid - min(got): toks for rid, toks in got.items()}


def _baseline(fam):
    """xla_ref, chunk=4, spec-off tokens — the parity anchor."""
    if fam not in _BASELINE:
        cfg, params, enc_out, reqs = _setup(fam)
        eng = engine.DecodeEngine(params, cfg, _ecfg())
        assert eng.chunk == 4, "capability descriptor must allow chunking"
        _BASELINE[fam] = _tokens_of(eng, reqs, enc_out)
    return _BASELINE[fam]


# ---------------------------------------------------------------------------
# capability descriptors
# ---------------------------------------------------------------------------

def test_serve_capabilities_every_registered_arch():
    """Every registry arch gets a full descriptor: chunkable and
    spec-capable (the boolean ``supports_chunked_prefill`` gate and the
    recurrent-state spec ban are both gone), with the state kind derived
    from the layer plan."""
    for name in registry.list_archs():
        cfg = registry.get_config(name)
        caps = lm.serve_capabilities(cfg)
        assert caps.chunkable and caps.spec_capable, (name, caps)
        want = ("ssm" if cfg.family == "ssm"
                else "hybrid" if cfg.family == "hybrid" else "kv")
        assert caps.state == want, (name, caps)


def test_supports_chunked_prefill_shim_matches_descriptor():
    cfg = registry.get_config("mamba2-2.7b").reduced()
    with pytest.deprecated_call():
        assert lm.supports_chunked_prefill(cfg) \
            == lm.serve_capabilities(cfg).chunkable


# ---------------------------------------------------------------------------
# the family x backend x layout matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fam", sorted(FAMILIES))
def test_family_backend_parity(fam):
    """Greedy streams identical across xla_ref and pallas_interpret,
    every jitted step compiled exactly once, and (MoE) the grouped
    packed kernel dispatched — per family."""
    cfg, params, enc_out, reqs = _setup(fam)
    ref = _baseline(fam)

    grouped = "packed_segment_matmul_grouped"
    before = pallas_backend.kernel_call_counts()[grouped]
    eng = engine.DecodeEngine(params, cfg,
                              _ecfg(backend="pallas_interpret"))
    got = _tokens_of(eng, reqs, enc_out)

    if fam == "moe":
        assert pallas_backend.kernel_call_counts()[grouped] > before, \
            "MoE serve did not dispatch the grouped packed kernel"
    for name, ent in eng.jit_table.items():
        assert ent.trace_count <= 1, (name, ent.trace_count)

    assert got.keys() == ref.keys()
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid])


@pytest.mark.parametrize("fam", sorted(FAMILIES))
def test_family_chunk_width_parity(fam):
    """Chunked prefill (4 tokens/step) == token-level prefill (chunk=1).

    For SSM/hybrid this pins the ``ssd_chunked(h0=...)`` state threading
    and state-neutral pad lanes; for MoE it pins drop-free serve
    capacity (static-capacity dropping would couple a token's experts to
    the step width)."""
    cfg, params, enc_out, reqs = _setup(fam)
    ref = _baseline(fam)
    eng1 = engine.DecodeEngine(params, cfg, _ecfg(prefill_chunk=1))
    got = _tokens_of(eng1, reqs, enc_out)
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid])


@pytest.mark.parametrize("fam", sorted(FAMILIES))
def test_family_spec_parity(fam):
    """spec_tokens>0 == spec-off greedy on every family — the hybrid leg
    is the regression for speculative decoding on recurrent state
    (snapshot -> draft/verify -> restore -> masked recommit)."""
    cfg, params, enc_out, reqs = _setup(fam)
    ref = _baseline(fam)
    eng = engine.DecodeEngine(params, cfg, _ecfg(spec_tokens=2))
    got = _tokens_of(eng, reqs, enc_out)
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid])
    stats = eng.spec_stats()
    assert stats["rounds"] > 0
    assert stats["drafted"] == 2 * stats["rounds"]


def test_moe_paged_layout_parity():
    """MoE family on the paged q4 KV layout: token-identical to the q4
    ring (grouped expert dispatch composes with paged attention)."""
    cfg, params, enc_out, reqs = _setup("moe")
    ring = engine.DecodeEngine(params, cfg, _ecfg(kv_bits=4))
    paged = engine.DecodeEngine(
        params, cfg, _ecfg(kv_bits=4, kv_layout="paged", page_size=16))
    got_r = _tokens_of(ring, reqs)
    got_p = _tokens_of(paged, reqs)
    for rid in got_r:
        np.testing.assert_array_equal(got_p[rid], got_r[rid])


def test_spec_gate_error_names_family(monkeypatch):
    """A descriptor with spec_capable=False must fail engine
    construction with the family name and note in the message."""
    cfg, params, _, _ = _setup("dense")
    caps = dataclasses.replace(lm.serve_capabilities(cfg),
                               spec_capable=False, note="test ban")
    monkeypatch.setattr(lm, "serve_capabilities", lambda _cfg: caps)
    with pytest.raises(ValueError, match=r"family 'dense'.*test ban"):
        engine.DecodeEngine(params, cfg, _ecfg(spec_tokens=2))


# ---------------------------------------------------------------------------
# quantized cross-attention K/V (enc-dec)
# ---------------------------------------------------------------------------

def test_cross_kv_quantized_serve_and_accounting():
    cfg, params, enc_out, reqs = _setup("encdec")
    fp = engine.DecodeEngine(params, cfg, _ecfg())
    q4 = engine.DecodeEngine(params, cfg, _ecfg(kv_bits=4))
    toks_fp = _tokens_of(fp, reqs, enc_out)
    toks_q4 = _tokens_of(q4, reqs, enc_out)
    for rid in toks_q4:               # q4 serve completes every request
        assert len(toks_q4[rid]) == len(toks_fp[rid])

    fp_cross = kv_quant.cross_payload_bytes(fp.cache)
    q4_cross = kv_quant.cross_payload_bytes(q4.cache)
    # 4-bit codes + per-group scales vs fp32 K/V: a real compression.
    assert 0 < q4_cross < fp_cross / 4
    # Cross K/V is accounted separately from the decoder ring payload,
    # and decoder-only caches report none.
    assert kv_quant.cache_payload_bytes(fp.cache) > 0
    dense_cfg, dense_params, _, _ = _setup("dense")
    deng = engine.DecodeEngine(dense_params, dense_cfg, _ecfg())
    assert kv_quant.cross_payload_bytes(deng.cache) == 0


def test_reset_cache_slots_preserves_cross():
    """Slot recycling between requests must not wipe the per-engine
    cross K/V (it is loaded once per batch via load_cross, not per
    slot)."""
    cfg, params, enc_out, _ = _setup("encdec")
    eng = engine.DecodeEngine(params, cfg, _ecfg(kv_bits=4))
    eng.load_cross(enc_out)
    before = jax.tree.map(np.asarray, eng.cache["cross"])
    eng.cache = lm.reset_cache_slots(eng.cache, jnp.asarray([0, 1, 2]))
    after = jax.tree.map(np.asarray, eng.cache["cross"])
    jax.tree.map(np.testing.assert_array_equal, before, after)


# ---------------------------------------------------------------------------
# ssd_chunked vs the sequential oracle
# ---------------------------------------------------------------------------

def _ssd_inputs(key, b=2, s=37, h=3, p=4, n=8):
    ks = jax.random.split(key, 5)
    xdt = jax.random.normal(ks[0], (b, s, h, p))
    da = -jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    b_mat = jax.random.normal(ks[2], (b, s, n))
    c_mat = jax.random.normal(ks[3], (b, s, n))
    h0 = jax.random.normal(ks[4], (b, h, p, n))
    return xdt, da, b_mat, c_mat, h0


@pytest.mark.parametrize("s,chunk", [(37, 8), (5, 8), (32, 8), (1, 4)])
def test_ssd_chunked_matches_reference_with_h0(s, chunk):
    """Chunked SSD == naive sequential recurrence with a carried initial
    state, including lengths that are not a chunk multiple (the pad
    lanes must be state-neutral) and S < chunk (the degenerate-chunk
    fallback)."""
    xdt, da, b_mat, c_mat, h0 = _ssd_inputs(jax.random.PRNGKey(0), s=s)
    y_ref, h_ref = ssm.ssd_reference(xdt, da, b_mat, c_mat, h0=h0)
    y, h_last = ssm.ssd_chunked(xdt, da, b_mat, c_mat, chunk, h0=h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_last), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-5)


def test_ssd_chunked_state_threading_across_calls():
    """Splitting a sequence across two ssd_chunked calls with the state
    carried through h0 == one pass over the whole sequence — exactly
    what chunked SSM prefill does step to step."""
    xdt, da, b_mat, c_mat, _ = _ssd_inputs(jax.random.PRNGKey(1), s=24)
    y_full, h_full = ssm.ssd_chunked(xdt, da, b_mat, c_mat, 8)
    y_a, h_a = ssm.ssd_chunked(xdt[:, :16], da[:, :16], b_mat[:, :16],
                               c_mat[:, :16], 8)
    y_b, h_b = ssm.ssd_chunked(xdt[:, 16:], da[:, 16:], b_mat[:, 16:],
                               c_mat[:, 16:], 8, h0=h_a)
    np.testing.assert_allclose(
        np.concatenate([np.asarray(y_a), np.asarray(y_b)], axis=1),
        np.asarray(y_full), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_b), np.asarray(h_full),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# every registered arch serves through the one fast path
# ---------------------------------------------------------------------------

def test_every_registered_arch_serves_chunked():
    """Acceptance sweep: each registry arch (reduced) runs the
    continuous engine end to end with chunked prefill > 1 token/step.
    The five-family matrix above pins parity in depth; this pins
    breadth — no registered config falls back to the slow path or
    fails admission. Archs already serving in the matrix are skipped
    (their baseline serve covers this)."""
    rng = np.random.default_rng(2)
    covered = set(FAMILIES.values())
    for name in registry.list_archs():
        if name in covered:
            continue
        cfg = registry.get_config(name).reduced()
        params = jax.device_get(lm.init_params(jax.random.PRNGKey(0), cfg))
        eng = engine.DecodeEngine(params, cfg, _ecfg(max_batch=1))
        assert eng.chunk == 4, (name, eng.chunk)
        enc_out = None
        if cfg.encoder_layers:
            enc_out = rng.normal(0, 1, (1, 8, cfg.d_model)) \
                .astype(np.float32)
            eng.load_cross(enc_out)
        req = Request(prompt=rng.integers(1, 100, (6,)),
                      max_new_tokens=3, seed=0)
        (comp,) = eng.serve([req])
        assert len(comp.new_tokens) == 3, (name, comp)
        assert all(0 <= t < cfg.vocab_size for t in comp.new_tokens), name
