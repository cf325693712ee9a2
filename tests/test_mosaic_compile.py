"""Compile every Pallas kernel of the serve and QAT paths for a TPU v5e
chip, without the chip: the TPU compiler is installed here and compiles
for a described ``v5e:2x2`` topology (Mosaic lowering included). This is
what interpret mode cannot show — block shapes off the (8, 128) tiling,
casts Mosaic has no lowering for, kernels that do not fit — and it costs
no chip time.

Shapes are h2o-danube-1.8b's published widths (d_model 2560, 8 KV heads
of 80, 4 query heads per KV head) split into the [K4|K2|K1] segments a
50/25/25 mix gives, the decode kernels at ``chip_smoke.py``'s cache sizes,
and the grouped GEMM at mixtral-8x22b's expert widths (d_model 6144,
d_ff 16384, 8 experts).

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and a
module that did so while being collected would leave the other test
workers without it.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import attn_decode_mod as ad
from repro.kernels import fake_quant_mod as fq
from repro.kernels import noise_inject_mod as ni
from repro.kernels import packed_matmul_mod as pm
from repro.kernels import quant_pack_mod as qp

HBM_BYTES = 16 * 10 ** 9               # one TPU v5e chip

M, D_MODEL = 8, 2560                   # decode rows, h2o-danube width
HK, G, HD = 8, 4, 80                   # KV heads, queries per KV head, dim
RING_B, RING_T = 4, 1024               # chip_smoke ring leg
PAGES, PAGE, TABLE = 64, 16, 64        # chip_smoke paged leg


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *avals):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in avals]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES, used
    return compiled


def _segment_avals(p, kp, n=D_MODEL):
    return [((M, kp), jnp.float32), ((kp * p // 8, n), jnp.uint8),
            ((kp // 16,), jnp.float32)]


@pytest.mark.parametrize("p,kp", [(4, 1280), (2, 640), (1, 640)])
def test_segment_gemm_compiles(one_chip, p, kp):
    _compile(functools.partial(pm.packed_segment_matmul, p=p,
                               interpret=False),
             one_chip, *_segment_avals(p, kp))


@pytest.mark.parametrize("p,kp", [(4, 1280), (2, 640), (1, 640)])
def test_fused_gemm_compiles(one_chip, p, kp):
    x, wp, s = _segment_avals(p, kp)
    _compile(functools.partial(pm.fused_act_segment_matmul, p=p,
                               interpret=False),
             one_chip, x, ((M, 1), jnp.float32), wp, s)


def test_selfscale_gemm_compiles(one_chip):
    _compile(functools.partial(pm.fused_act_selfscale_matmul, p=4,
                               interpret=False),
             one_chip, *_segment_avals(4, D_MODEL))


def test_grouped_gemm_compiles_at_mixtral_expert_widths(one_chip):
    e, kp, n = 8, 6144, 16384
    _compile(functools.partial(pm.packed_segment_matmul_grouped, p=4,
                               interpret=False),
             one_chip, ((e, M, kp), jnp.float32),
             ((e, kp * 4 // 8, n), jnp.uint8), ((e, kp // 16), jnp.float32))


@pytest.mark.parametrize("p", [4, 2, 1])
def test_quantize_pack_compiles(one_chip, p):
    _compile(functools.partial(qp.quantize_pack, p=p, interpret=False),
             one_chip, ((D_MODEL, D_MODEL), jnp.float32),
             ((D_MODEL // 16,), jnp.float32))


def test_noise_inject_compiles(one_chip):
    _compile(lambda w, s, seed: ni.noise_inject(w, s, seed,
                                                interpret=False),
             one_chip, ((D_MODEL, D_MODEL), jnp.float32),
             ((D_MODEL // 16,), jnp.float32), ((), jnp.uint32))


@pytest.mark.parametrize("row_scale", [True, False])
def test_fake_quant_compiles(one_chip, row_scale):
    scale = (D_MODEL, 1) if row_scale else (D_MODEL // 16,)
    _compile(functools.partial(fq.fake_quant, row_scale=row_scale,
                               interpret=False),
             one_chip, ((D_MODEL, D_MODEL), jnp.float32),
             ((D_MODEL // 16,), jnp.float32), (scale, jnp.float32))


def _q_avals(b, s):
    return [((b, s, HK, G, HD), jnp.float32)]


@pytest.mark.parametrize("s", [1, 32])      # decode step, prefill chunk
def test_ring_q4_decode_compiles(one_chip, s):
    b, t = RING_B, RING_T
    codes = ((b, t, HK, HD // 2), jnp.uint8)
    scale = ((b, t, HK, 1), jnp.float16)
    _compile(functools.partial(ad.qkv_attn_decode, interpret=False),
             one_chip, *_q_avals(b, s), codes, codes, scale, scale,
             ((b, t), jnp.int32), ((b, s), jnp.int32))


@pytest.mark.parametrize("s", [1, 32])
def test_paged_q4_decode_compiles(one_chip, s):
    b = RING_B
    codes = ((PAGES, PAGE, HK, HD // 2), jnp.uint8)
    scale = ((PAGES, PAGE, HK, 1), jnp.float16)
    _compile(functools.partial(ad.qkv_attn_decode_paged, interpret=False),
             one_chip, *_q_avals(b, s), codes, codes, scale, scale,
             ((PAGES, PAGE), jnp.int32), ((b, TABLE), jnp.int32),
             ((b, s), jnp.int32))
