"""Quantized-KV decode (DESIGN.md §12): ring-write semantics, the
``qkv_attn_decode`` backend op, engine/backend parity at ``kv_bits=4``,
and the payload-byte accounting behind the "4x cache bytes" claim."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.backend import base as backend_base
from repro.backend import pallas as pallas_backend
from repro.backend import registry
from repro.configs.base import ArchConfig
from repro.core.qtypes import QuantConfig
from repro.kernels import attn_decode_mod as attn_decode
from repro.models import lm
from repro.serve import engine, kv_pool, kv_quant
from repro.serve.scheduler import Request


def _fill_ring(cache, key, batch, heads, dim, positions):
    for t in positions:
        k_new = jax.random.normal(jax.random.fold_in(key, t),
                                  (batch, 1, heads, dim))
        cache = kv_quant.update_qkv_cache(
            cache, k_new, -k_new, jnp.asarray([t] * batch, jnp.int32))
    return cache


# ------------------------------------------------- masked-lane writes ----
def test_masked_lane_does_not_clobber_full_ring():
    """THE regression (satellite 1): with a full ring, a pos=-1 lane used
    to wrap to slot cache_len-1 (-1 % cache_len), overwriting that entry's
    codes and stamping its pos to -1 — a silent eviction of the oldest
    resident token. Masked lanes must drop, exactly like the fp ring
    write."""
    cache_len, h, d = 8, 2, 16
    key = jax.random.PRNGKey(0)
    cache = _fill_ring(kv_quant.init_qkv_cache(2, cache_len, h, d),
                       key, 2, h, d, range(cache_len))       # ring full
    before = {k: np.asarray(v) for k, v in cache.items()}
    # row 0 decodes position 8 (wraps to slot 0); row 1 is an idle lane
    k_new = jax.random.normal(jax.random.fold_in(key, 99), (2, 1, h, d))
    cache = kv_quant.update_qkv_cache(cache, k_new, -k_new,
                                      jnp.asarray([8, -1], jnp.int32))
    for name in cache:                       # row 1 bitwise untouched
        np.testing.assert_array_equal(np.asarray(cache[name][1]),
                                      before[name][1], err_msg=name)
    assert kv_quant.slot_lengths(cache).tolist() == [cache_len, cache_len]
    assert int(cache["pos"][0, 0]) == 8      # row 0's wrap write landed
    assert int(cache["pos"][1, cache_len - 1]) == cache_len - 1


def test_masked_lane_chunk_padding_drops():
    """S>1 chunks: padding lanes (pos=-1) inside a prefill chunk must not
    write; real lanes of the same chunk must land in their slots."""
    cache = kv_quant.init_qkv_cache(1, 8, 2, 16)
    key = jax.random.PRNGKey(1)
    k_new = jax.random.normal(key, (1, 4, 2, 16))
    pos = jnp.asarray([[0, 1, 2, -1]], jnp.int32)    # 3 real + 1 padding
    cache = kv_quant.update_qkv_cache(cache, k_new, -k_new, pos)
    assert np.asarray(cache["pos"][0]).tolist() == \
        [0, 1, 2, -1, -1, -1, -1, -1]
    assert kv_quant.slot_lengths(cache).tolist() == [3]


def test_chunked_write_equals_token_by_token():
    """A [B, S, H, D] chunk write must land byte-identically to S
    single-token writes (the fp ring's S>1 contract)."""
    key = jax.random.PRNGKey(2)
    kv = jax.random.normal(key, (2, 5, 2, 16))
    pos = jnp.asarray([[3, 4, 5, 6, 7], [0, 1, 2, -1, -1]], jnp.int32)
    chunked = kv_quant.update_qkv_cache(
        kv_quant.init_qkv_cache(2, 8, 2, 16), kv, -kv, pos)
    stepped = kv_quant.init_qkv_cache(2, 8, 2, 16)
    for s in range(5):
        stepped = kv_quant.update_qkv_cache(stepped, kv[:, s:s + 1],
                                            -kv[:, s:s + 1], pos[:, s:s + 1])
    for name in chunked:
        np.testing.assert_array_equal(np.asarray(chunked[name]),
                                      np.asarray(stepped[name]),
                                      err_msg=name)


def test_stacked_layer_write_touches_one_layer():
    """layer_idx: stacked [L, ...] leaves are scattered in place at
    [layer_idx, b, slot]; other layers stay bitwise untouched and the
    written layer matches the non-stacked write."""
    L = 3
    flat = kv_quant.init_qkv_cache(2, 8, 2, 16)
    stacked = {k: jnp.repeat(v[None], L, axis=0) for k, v in flat.items()}
    key = jax.random.PRNGKey(3)
    k_new = jax.random.normal(key, (2, 1, 2, 16))
    pos = jnp.asarray([0, -1], jnp.int32)            # one masked lane too
    got = kv_quant.update_qkv_cache(stacked, k_new, -k_new, pos,
                                    layer_idx=1)
    want_layer = kv_quant.update_qkv_cache(flat, k_new, -k_new, pos)
    for name in got:
        np.testing.assert_array_equal(np.asarray(got[name][1]),
                                      np.asarray(want_layer[name]),
                                      err_msg=name)
        for l in (0, 2):
            np.testing.assert_array_equal(np.asarray(got[name][l]),
                                          np.asarray(stacked[name][l]),
                                          err_msg=f"{name}[{l}]")


# ------------------------------------------------- backend op parity ----
def _toy_cache_and_q(seed=0, b=2, t=16, hk=2, d=32, g=2, s=3):
    key = jax.random.PRNGKey(seed)
    cache = _fill_ring(kv_quant.init_qkv_cache(b, t, hk, d), key, b, hk, d,
                       range(10))
    q = jax.random.normal(jax.random.fold_in(key, 77), (b, s, hk, g, d))
    q_pos = jnp.asarray([[7, 8, 9], [5, -1, 6]], jnp.int32)[:, :s]
    return cache, q, q_pos


def _toy_paged_and_q(seed=0, b=2, hk=2, d=80, g=2, s=3):
    """A 4-bit page pool with slot 0's logical ring full and wrapped
    (pages 1-4) and slot 1 on two pages; q at s query positions."""
    cache = kv_pool.init_paged_cache(7, 4, 4, b, hk, d, kv_bits=4,
                                     dtype=jnp.float32)
    table = np.full((b, 4), -1, np.int32)
    table[0] = [1, 2, 3, 4]
    table[1, :2] = [5, 6]
    cache["page_table"] = jnp.asarray(table)
    key = jax.random.PRNGKey(seed)
    for t in range(20):
        kv = jax.random.normal(jax.random.fold_in(key, t), (b, 1, hk, d))
        pos = jnp.asarray([t, t if t < 7 else -1], jnp.int32)
        cache = kv_pool.update_paged_cache(cache, kv, -kv, pos)
    q = jax.random.normal(jax.random.fold_in(key, 99), (b, s, hk, g, d))
    q_pos = jnp.asarray([[18, -1, 19], [5, 6, -1]], jnp.int32)[:, :s]
    return cache, q, q_pos


_PARITY = pytest.mark.parametrize("window", [None, 4])
_SHAPES = pytest.mark.parametrize("s", [1, 3])


@_PARITY
@_SHAPES
@pytest.mark.parametrize("d", [32, 80, 128])
def test_qkv_attn_kernel_matches_oracle(window, s, d):
    """The Pallas flash-decode kernel (in-loop unpack into even and odd
    channel halves + per-(slot, head) scales) must match the
    dequantize-everything jnp oracle to fp32 tolerance at every head_dim
    the registered models serve, masked lanes and sliding window
    included — and must actually dispatch (trace-time counter)."""
    cache, q, q_pos = _toy_cache_and_q(d=d, s=s)
    ref = registry.get("xla_ref").qkv_attn_decode(q, cache, q_pos,
                                                  window=window)
    before = pallas_backend.kernel_call_counts()["qkv_attn_decode"]
    got = registry.get("pallas_interpret").qkv_attn_decode(
        q, cache, q_pos, window=window)
    assert pallas_backend.kernel_call_counts()["qkv_attn_decode"] == before + 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    assert np.isfinite(np.asarray(got)).all()


@_PARITY
@_SHAPES
def test_qkv_attn_paged_kernel_matches_oracle(window, s):
    """The paged kernel shares the ring kernel's body: at head_dim 80 it
    must match ``xla_ref``'s gather + dense oracle to the same fp32
    tolerance, wrapped ring, unmapped pages and masked lanes included."""
    cache, q, q_pos = _toy_paged_and_q(s=s)
    ref = registry.get("xla_ref").qkv_attn_decode_paged(
        q, cache, q_pos, window=window)
    before = pallas_backend.kernel_call_counts()["qkv_attn_decode_paged"]
    got = registry.get("pallas_interpret").qkv_attn_decode_paged(
        q, cache, q_pos, window=window)
    assert pallas_backend.kernel_call_counts()[
        "qkv_attn_decode_paged"] == before + 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    assert np.isfinite(np.asarray(got)).all()


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else [v]):
            if isinstance(x, jcore.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jcore.Jaxpr):
                yield x


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in _sub_jaxprs(e):
            yield from _eqns(sub)


def _moves_lanes(eqn):
    """An op that moves data across lanes: a concatenate, or a reshape or
    broadcast whose last (lane) dimension changes between two extents
    above 1. A scalar splat or a keepdims column ([SG] -> [SG, 1], the
    softmax's running max and sum) does not."""
    if eqn.primitive.name == "concatenate":
        return True
    if eqn.primitive.name not in ("reshape", "broadcast_in_dim"):
        return False
    src, dst = eqn.invars[0].aval.shape, eqn.outvars[0].aval.shape
    return bool(src) and min(src[-1], dst[-1]) > 1 and src[-1] != dst[-1]


@pytest.mark.parametrize("paged", [False, True])
def test_tile_loop_moves_no_lanes(paged):
    """Guard on the kernels' tile loop at head_dim 80: interleaving the
    nibbles back into channel order inside the loop (a stack and reshape
    to [bt, D]) lowered through Mosaic to some 38k lane and sublane
    shuffles per program. The loop must keep the channel halves as the
    carrier stores them."""
    b, s, t, hk, g, d = 2, 3, 512, 2, 2, 80
    q = jax.ShapeDtypeStruct((b, s, hk, g, d), jnp.float32)
    q_pos = jax.ShapeDtypeStruct((b, s), jnp.int32)
    if paged:
        p, ps = 8, 64
        codes = jax.ShapeDtypeStruct((p, ps, hk, d // 2), jnp.uint8)
        scale = jax.ShapeDtypeStruct((p, ps, hk, 1), jnp.float16)
        fn = functools.partial(attn_decode.qkv_attn_decode_paged,
                               interpret=False, window=4)
        args = (q, codes, codes, scale, scale,
                jax.ShapeDtypeStruct((p, ps), jnp.int32),
                jax.ShapeDtypeStruct((b, t // ps), jnp.int32), q_pos)
    else:
        codes = jax.ShapeDtypeStruct((b, t, hk, d // 2), jnp.uint8)
        scale = jax.ShapeDtypeStruct((b, t, hk, 1), jnp.float16)
        fn = functools.partial(attn_decode.qkv_attn_decode,
                               interpret=False, window=4)
        args = (q, codes, codes, scale, scale,
                jax.ShapeDtypeStruct((b, t), jnp.int32), q_pos)
    calls = [e for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    loops = [e for e in _eqns(calls[0].params["jaxpr"])
             if e.primitive.name in ("scan", "while")]
    assert len(loops) == 1
    body = [e for sub in _sub_jaxprs(loops[0]) for e in _eqns(sub)]
    assert any(e.primitive.name == "dot_general" for e in body)
    moved = [f"{e.primitive.name} {e.invars[0].aval.shape} -> "
             f"{e.outvars[0].aval.shape}" for e in body if _moves_lanes(e)]
    assert not moved, moved


def test_qkv_attn_oracle_matches_fp_attention_closely():
    """Sanity on the numerics: the q4 attention output stays within the
    documented KV round-trip error of full-precision attention."""
    b, t, hk, d, g = 1, 8, 2, 32, 2
    key = jax.random.PRNGKey(5)
    kv = jax.random.normal(key, (b, t, hk, d))
    cache = kv_quant.init_qkv_cache(b, t, hk, d)
    cache = kv_quant.update_qkv_cache(
        cache, kv, -kv, jnp.arange(t, dtype=jnp.int32)[None])
    q = jax.random.normal(jax.random.fold_in(key, 9), (b, 1, hk, g, d))
    q_pos = jnp.full((b, 1), t - 1, jnp.int32)
    got = registry.get("xla_ref").qkv_attn_decode(q, cache, q_pos)
    want = backend_base.qkv_attn_jnp(
        q, kv, -kv, jnp.arange(t, dtype=jnp.int32)[None], q_pos)
    rel = np.linalg.norm(np.asarray(got - want)) / \
        np.linalg.norm(np.asarray(want))
    assert rel < 0.15                        # ~10% norm-relative at 4 bits


# ---------------------------------------------------- engine parity ----
def _tiny_cfg():
    return ArchConfig(
        name="t", family="dense", num_layers=2, d_model=64, num_heads=2,
        num_kv_heads=2, d_ff=128, vocab_size=128, head_dim=32,
        dtype="float32", param_dtype="float32", q_block=32,
        quant=QuantConfig(mode="qat"))


@pytest.fixture(scope="module")
def served():
    cfg = _tiny_cfg()
    params = jax.device_get(lm.init_params(jax.random.PRNGKey(0), cfg))
    return cfg, params


def _mixed_requests(rng, lens=(3, 7, 5, 2), news=(4, 8, 3, 6)):
    return [Request(prompt=rng.integers(1, 100, (l,)), max_new_tokens=n,
                    seed=i) for i, (l, n) in enumerate(zip(lens, news))]


def test_kv4_engine_parity_with_lockstep(served):
    """kv_bits=4 acceptance: DecodeEngine greedy tokens identical to
    LockstepEngine on the same packed checkpoint (runs on whichever
    backend the SONIQ_BACKEND CI matrix pins)."""
    cfg, params = served
    ecfg = engine.EngineConfig(max_batch=3, cache_len=64, prefill_chunk=4,
                               kv_bits=4)
    lock = engine.LockstepEngine(params, cfg, ecfg)
    cont = engine.DecodeEngine(params, cfg, ecfg)
    reqs = _mixed_requests(np.random.default_rng(0))
    ref = {i: lock.generate(r.prompt[None], r.max_new_tokens)[0]
           for i, r in enumerate(reqs)}
    got = {c.request_id: c.tokens for c in cont.serve(reqs)}
    for i in range(len(reqs)):
        np.testing.assert_array_equal(ref[i], got[i])


def test_kv4_cross_backend_token_identity(served):
    """kv_bits=4 acceptance: xla_ref (jnp oracle) and pallas_interpret
    (fused flash-decode kernel) agree token-for-token at temperature 0,
    and the kernel — not the fallback — served the Pallas leg."""
    cfg, params = served
    outs = {}
    for name in ("xla_ref", "pallas_interpret"):
        ecfg = engine.EngineConfig(max_batch=2, cache_len=64,
                                   prefill_chunk=4, backend=name,
                                   kv_bits=4)
        eng = engine.DecodeEngine(params, cfg, ecfg)
        before = pallas_backend.kernel_call_counts()["qkv_attn_decode"]
        got = {c.request_id: c.tokens
               for c in eng.serve(_mixed_requests(np.random.default_rng(1)))}
        outs[name] = {k - min(got): v for k, v in got.items()}
        dispatched = pallas_backend.kernel_call_counts()[
            "qkv_attn_decode"] - before
        assert dispatched == (0 if name == "xla_ref" else 2), dispatched
    assert set(outs["xla_ref"]) == set(outs["pallas_interpret"])
    for k in outs["xla_ref"]:
        np.testing.assert_array_equal(outs["xla_ref"][k],
                                      outs["pallas_interpret"][k])


def test_kv4_reset_cache_slots_wipes_only_target_rows(served):
    """The continuous-batching admission wipe must cover the quantized
    family too: codes/scales zero, pos -1, other rows untouched."""
    cfg, params = served
    cache = lm.init_cache(cfg, 3, 16, np.float32, kv_bits=4)
    step = jax.jit(lambda p, c, t, q: lm.decode_step(p, cfg, c, t, q))
    c = cache
    for t in range(3):
        _, c = step(params, c, np.asarray([t + 1] * 3, np.int32),
                    np.asarray([t] * 3, np.int32))
    c2 = lm.reset_cache_slots(c, [1])
    kv0 = c2["groups"][0]["kv"]
    assert (np.asarray(kv0["pos"][:, 1]) == -1).all()
    for leaf in ("k_codes", "v_codes", "k_scale", "v_scale"):
        assert (np.asarray(kv0[leaf][:, 1]) == 0).all(), leaf
    old = c["groups"][0]["kv"]
    for row in (0, 2):
        for leaf in ("pos", "k_codes", "k_scale"):
            np.testing.assert_array_equal(np.asarray(kv0[leaf][:, row]),
                                          np.asarray(old[leaf][:, row]))


# ------------------------------------------------- byte accounting ----
def test_kv4_payload_bytes_at_least_3p5x_smaller(served):
    """The corrected accounting: K/V payload (codes + scales vs fp16 k/v)
    drops >= 3.5x; ``pos`` bookkeeping is identical in both families and
    excluded from the claim."""
    cfg, _ = served
    fp16 = lm.init_cache(cfg, 4, 64, jnp.float16, specs=True)
    q4 = lm.init_cache(cfg, 4, 64, jnp.float16, specs=True, kv_bits=4)
    fp_payload = kv_quant.cache_payload_bytes(fp16)
    q4_payload = kv_quant.cache_payload_bytes(q4)
    assert fp_payload / q4_payload >= 3.5
    assert kv_quant.cache_meta_bytes(fp16) == kv_quant.cache_meta_bytes(q4)
    # total = payload + meta, and the single-layer helper agrees
    one = kv_quant.init_qkv_cache(2, 8, 2, 16)
    assert kv_quant.cache_bytes(one) == \
        kv_quant.cache_payload_bytes(one) + kv_quant.cache_meta_bytes(one)


# --------------------------------------------- hypothesis properties ----
# Guarded import (not a module-level importorskip, which would skip the
# ring/write/parity tests above too): CI installs hypothesis and fails
# fast if the property tests would silently vanish from the run.
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # pragma: no cover
    HAVE_HYPOTHESIS = False


if not HAVE_HYPOTHESIS:
    def test_property_tests_require_hypothesis():
        pytest.skip("hypothesis not installed — property tests skipped")
else:
    @st.composite
    def _roundtrip_cases(draw):
        b = draw(st.integers(1, 3))
        t = draw(st.integers(1, 6))
        h = draw(st.integers(1, 3))
        d = 2 * draw(st.integers(1, 32))
        seed = draw(st.integers(0, 2 ** 16))
        mag = draw(st.sampled_from([0.05, 1.0, 3.0, 50.0]))
        read_dtype = draw(st.sampled_from(["float32", "bfloat16"]))
        zero_row = draw(st.booleans())
        outlier_head = draw(st.booleans())
        return b, t, h, d, seed, mag, read_dtype, zero_row, outlier_head


    @settings(max_examples=40, deadline=None)
    @given(_roundtrip_cases())
    def test_quantize_kv_roundtrip_property(case):
        """Round-trip bound, property-tested: elementwise error <= the
        stored scale's half-step (+ read-dtype rounding) for every element
        the fp16 scale can represent, saturation (never inf) beyond it,
        zero rows decode to ~eps-scale noise, and outlier heads do not
        leak error into neighbours (per-head scales)."""
        b, t, h, d, seed, mag, read_dtype, zero_row, outlier_head = case
        x = jax.random.normal(jax.random.PRNGKey(seed), (b, t, h, d)) * mag
        if zero_row:
            x = x.at[0, 0].set(0.0)
        if outlier_head:
            x = x.at[:, :, 0].multiply(1000.0)
        codes, scale = kv_quant.quantize_kv(x)
        assert codes.dtype == jnp.uint8 and codes.shape == (b, t, h, d // 2)
        assert scale.dtype == jnp.float16
        y = np.asarray(kv_quant.dequantize_kv(codes, scale,
                                              jnp.dtype(read_dtype)),
                       np.float32)
        assert np.isfinite(y).all()        # fp16 scale saturates, never inf
        x32 = np.asarray(x, np.float32)
        err = np.abs(y - x32)
        s32 = np.asarray(scale, np.float32)
        # half-step * stored scale, widened for the bf16 read rounding
        slack = 1.06 if read_dtype == "bfloat16" else 1.02
        bound = s32 * 2.0 ** (1 - kv_quant.P_BITS) * slack + 1e-6
        in_range = np.abs(x32) <= kv_quant.GRID_MAX * s32 * 1.001
        assert (err <= bound + 0.01 * np.abs(y))[in_range].all()
        # beyond the representable range (abs-max overflowed the fp16
        # scale) values clip to the top of the stored grid
        assert (np.abs(y) <= kv_quant.GRID_MAX * s32 * 1.01).all()
        if zero_row:                   # eps-clamped scale, not NaN/Inf
            assert (np.abs(y[0, 0]) <= 2 * backend_base.ACT_SCALE_EPS).all()


    @st.composite
    def _ring_programs(draw):
        cache_len = draw(st.sampled_from([2, 4, 8]))
        b = draw(st.integers(1, 3))
        n_ops = draw(st.integers(1, 12))
        ops = []
        for _ in range(n_ops):
            kind = draw(st.sampled_from(["write", "mask_some", "reset",
                                         "evict"]))
            if kind in ("reset", "evict"):
                ops.append((kind, draw(st.integers(0, b - 1))))
            else:
                ops.append((kind, None))
        return cache_len, b, ops


    @settings(max_examples=30, deadline=None)
    @given(_ring_programs())
    def test_ring_wraparound_reset_evict_property(case):
        """Random interleavings of (masked) writes, slot resets and evictions
        against a pure-python model of the ring's pos bookkeeping — the
        quantized cache must track the fp cache's slot semantics exactly."""
        cache_len, b, ops = case
        h, d = 2, 8
        cache = kv_quant.init_qkv_cache(b, cache_len, h, d)
        model = [dict() for _ in range(b)]      # slot -> {ring_idx: pos}
        key = jax.random.PRNGKey(0)
        clock = [0] * b
        for step, (kind, arg) in enumerate(ops):
            if kind == "reset" or kind == "evict":
                cache = (kv_quant.evict_slot(cache, arg) if kind == "evict"
                         else kv_quant.reset_slots(cache, [arg]))
                model[arg] = {}
                clock[arg] = 0
            else:
                pos = []
                for row in range(b):
                    if kind == "mask_some" and (row + step) % 2:
                        pos.append(-1)
                    else:
                        pos.append(clock[row])
                        model[row][clock[row] % cache_len] = clock[row]
                        clock[row] += 1
                k_new = jax.random.normal(jax.random.fold_in(key, step),
                                          (b, 1, h, d))
                cache = kv_quant.update_qkv_cache(
                    cache, k_new, -k_new, jnp.asarray(pos, jnp.int32))
        got = np.asarray(cache["pos"])
        for row in range(b):
            want = np.full((cache_len,), -1, np.int64)
            for ring_idx, p in model[row].items():
                want[ring_idx] = p
            np.testing.assert_array_equal(got[row], want, err_msg=f"row {row}")
        np.testing.assert_array_equal(
            np.asarray(kv_quant.slot_lengths(cache)),
            [len(m) for m in model])
