#!/usr/bin/env python3
"""Chip smoke check: the packed serving path of h2o-danube-1.8b end to end
on one TPU chip, at published widths, through the entry points a user
calls (``soniq.DecodeEngine``, as ``launch/serve.py`` drives it).

    python chip_smoke.py             # one chip: pack, serve, compare logits
    python chip_smoke.py --chips 4   # four chips: sharded QAT train step

One chip: random QAT weights from a fixed seed are packed by
``convert_tree`` on the ``pallas_mosaic`` backend, then served on the
continuous engine with the 4-bit KV cache, first on the ring layout
(mixed-length requests) and then on the paged layout (two requests that
share a prompt prefix). The kernel counters must show every serve kernel
dispatched and no reference-math fallback, and inside one prefill step
(ring and paged cache) every kernel op must match ``xla_ref`` at
``highest`` matmul precision on the same operands.

Four chips: ``train.state.train_step`` in the QAT phase at published
widths, 2 layers on a 1-device mesh and on a 2x2 data x model mesh (step-0
loss and grad norm must agree), then all 24 layers on 2x2 for 3 steps.

Earlier lines are a smoke report, not a benchmark. The last line of
standard output is one JSON object naming the device; it is printed only
when every check passed. Without a TPU, or without the repo's ``src/``
next to this file, the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ARCH = "h2o-danube-1.8b"
SEED = 0

# Serve legs: 4 slots, 4-bit KV, prompts of 32-128 tokens, 16 new tokens.
MAX_BATCH, CACHE_LEN, PREFILL_CHUNK, NEW_TOKENS = 4, 1024, 32, 16
PROMPT_LENS = (32, 77, 128, 50, 96, 64)
# Paged leg: a 1024-token pool; two requests share a 64-token prefix, the
# second (16 own tokens) arriving at engine step 4, after the first (32
# own tokens) has prefilled it.
PAGE_SIZE, NUM_PAGES, SHARED_PREFIX = 16, 64, 64
PAGED_TAILS = ((32, 0), (16, 4))       # (own prompt tokens, arrival step)

# Kernel parity inside one prefill step. The model's logits cannot carry
# the comparison at random weights: per-token activation scales set by the
# row's abs-max, and the 1- and 2-bit grids (which have no zero level),
# snap small activations to a sign times a large step, so a rounding-level
# change flips those signs and the next layer's output moves by a large
# fraction. On a v5e, xla_ref alone at default vs highest matmul precision
# gives one-layer logits that differ by a relative RMS of 0.94. So each
# kernel op of the step is compared where it is called: on the same
# operands, the kernel's output against xla_ref's at highest precision,
# and xla_ref's result is carried on, so every op sees the reference
# path's operands. The kernels compute their dots at default precision
# (one bf16 MXU pass, as XLA does on the TPU), which leaves a relative RMS
# of about 2.5e-3 on random operands at these widths (measured on a v5e).
# A layout defect is far larger: one wrong 128-lane block of 2560 outputs
# gives about 0.3, one group scale of 160 off by 2x about 0.08.
OP_REL_RMS_BOUND = 0.02

# Four-chip phase: the 2x2 step reorders the sums of the 1-device step
# (sharded contractions, a cross-device gradient reduction) in f32
# accumulators over bf16 compute; 2e-2 relative is far below what a
# sharding error (a dropped or doubled shard) changes.
TRAIN_BATCH, TRAIN_SEQ, TRAIN_REL_TOL = 8, 256, 2e-2


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def _requests(soniq, vocab: int, rng):
    """Mixed-length requests, arriving a batch at a time."""
    return [soniq.Request(
        prompt=rng.integers(0, vocab, (n,)).astype("int32"),
        max_new_tokens=NEW_TOKENS, seed=i, arrival_step=i // MAX_BATCH)
        for i, n in enumerate(PROMPT_LENS)]


def _serve(eng, reqs, vocab: int):
    """Serve ``reqs`` to completion; check every stream; return
    (tokens generated, seconds, completions by request id)."""
    t0 = time.perf_counter()
    done = {c.request_id: c for c in eng.serve(reqs)}
    dt = time.perf_counter() - t0
    if len(done) != len(reqs):
        _fail(f"{len(reqs)} requests submitted, {len(done)} completed")
    for c in done.values():
        new = c.new_tokens
        if new.size != c.request.max_new_tokens:
            _fail(f"request {c.request_id} generated {new.size} tokens, "
                  f"asked for {c.request.max_new_tokens}")
        if new.min() < 0 or new.max() >= vocab:
            _fail(f"request {c.request_id} emitted a token outside the "
                  f"vocabulary: {new.tolist()}")
    return sum(c.new_tokens.size for c in done.values()), dt, done


def _op_parity_backend(kernel: str):
    """A backend that runs each kernel op of ``kernel`` and of ``xla_ref``
    (at highest matmul precision) on the same operands, records the
    relative RMS of the difference per op, and returns xla_ref's result
    (see ``OP_REL_RMS_BOUND``). Registered as ``op_parity``."""
    import jax
    import jax.numpy as jnp
    from repro.backend import registry
    from repro.backend.base import Backend

    kern, ref = registry.get(kernel), registry.get("xla_ref")

    class OpParity(Backend):
        name = "op_parity"

        def __init__(self):
            self.rel_rms = {}

        def _both(self, op, *args, **kw):
            got = getattr(kern, op)(*args, **kw)
            with jax.default_matmul_precision("highest"):
                want = getattr(ref, op)(*args, **kw)
            err = jnp.sum(jnp.square(got - want))
            norm = jnp.sum(jnp.square(want))
            jax.debug.callback(
                lambda e, n: self.rel_rms.setdefault(op, []).append(
                    float(np.sqrt(e / max(n, 1e-30)))), err, norm)
            return want

        def packed_segment_matmul(self, *args, **kw):
            return self._both("packed_segment_matmul", *args, **kw)

        def fused_act_segment_matmul(self, *args, **kw):
            return self._both("fused_act_segment_matmul", *args, **kw)

        def packed_segment_matmul_grouped(self, *args, **kw):
            return self._both("packed_segment_matmul_grouped", *args, **kw)

        def qkv_attn_decode(self, *args, **kw):
            return self._both("qkv_attn_decode", *args, **kw)

        def qkv_attn_decode_paged(self, *args, **kw):
            return self._both("qkv_attn_decode_paged", *args, **kw)

    return registry.register(OpParity(), overwrite=True)


def prefill_logits(params, cfg, backend: str, tokens, paged: bool = False):
    """Logits of one chunked prefill step from an empty 4-bit cache, with
    the packed ``params`` run on ``backend``. ``paged``: the cache is a
    page pool, each slot's pages mapped in order from page 1 on."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm

    qcfg = dataclasses.replace(cfg.quant, backend=backend)
    cfg = dataclasses.replace(cfg, quant=qcfg)
    b, c = tokens.shape
    if paged:
        cache = lm.init_cache(cfg, b, CACHE_LEN, jnp.float32, kv_bits=4,
                              kv_layout="paged", page_size=PAGE_SIZE,
                              num_pages=NUM_PAGES)
        used = -(-c // PAGE_SIZE)                   # pages a slot fills
        for g in cache["groups"]:
            shape = g["kv"]["page_table"].shape     # [layers, B, NP]
            table = np.full(shape[1:], -1, np.int32)
            table[:, :used] = 1 + np.arange(b * used).reshape(b, used)
            g["kv"]["page_table"] = jnp.broadcast_to(table, shape)
    else:
        cache = lm.init_cache(cfg, b, CACHE_LEN, jnp.float32, kv_bits=4)
    pos = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32), (b, c))
    last = jnp.full((b,), c - 1, jnp.int32)
    step = jax.jit(lambda p, ca: lm.prefill_step(p, cfg, ca, tokens, pos,
                                                 last)[0])
    out = np.asarray(step(params, cache), np.float32)
    jax.effects_barrier()
    return out


def serve_phase(cfg, backend: str):
    """Pack ``cfg`` from a seed on ``backend``, serve the ring and paged
    legs, check each kernel op of a prefill step against ``xla_ref``.
    Returns the report dict."""
    import jax
    import jax.numpy as jnp
    from repro import soniq
    from repro.backend import pallas as pallas_backend
    from repro.models import lm

    report = {}
    cfg = soniq.with_phase(cfg, soniq.Phase.QAT)
    t0 = time.perf_counter()
    params = jax.device_get(lm.init_params(jax.random.PRNGKey(SEED), cfg))
    report["init_s"] = time.perf_counter() - t0

    ring_cfg = soniq.EngineConfig(
        max_batch=MAX_BATCH, cache_len=CACHE_LEN,
        prefill_chunk=PREFILL_CHUNK, backend=backend, kv_bits=4)
    t0 = time.perf_counter()
    eng = soniq.DecodeEngine(params, cfg, ring_cfg)
    jax.block_until_ready(eng.params)
    report["pack_s"] = time.perf_counter() - t0
    del params
    report["packed_bytes"] = soniq.packed_bytes(eng.params)

    # Ring leg, twice over same-shaped traffic: the first pass compiles
    # the engine's step programs, the second must compile nothing.
    rng = np.random.default_rng(SEED)
    n1, t1, _ = _serve(eng, _requests(soniq, cfg.vocab_size, rng),
                       cfg.vocab_size)
    traces = {k: e.trace_count for k, e in eng.jit_table.items()}
    n2, t2, _ = _serve(eng, _requests(soniq, cfg.vocab_size, rng),
                       cfg.vocab_size)
    retraced = {k: e.trace_count - traces.get(k, 0)
                for k, e in eng.jit_table.items()
                if e.trace_count != traces.get(k, 0)}
    if retraced:
        _fail(f"second ring pass recompiled: {retraced}")
    report.update(ring_tokens=n1 + n2, ring_first_pass_s=t1,
                  ring_second_pass_s=t2, ring_compile_s_estimate=t1 - t2)

    # Paged leg: two requests sharing a page-aligned prompt prefix.
    paged = soniq.DecodeEngine(eng.params, cfg, dataclasses.replace(
        ring_cfg, kv_layout="paged", page_size=PAGE_SIZE,
        num_pages=NUM_PAGES), already_serve=True)
    prefix = rng.integers(0, cfg.vocab_size, (SHARED_PREFIX,))
    reqs = [soniq.Request(
        prompt=np.concatenate([prefix, rng.integers(
            0, cfg.vocab_size, (n,))]).astype("int32"),
        max_new_tokens=NEW_TOKENS, seed=i, arrival_step=step)
        for i, (n, step) in enumerate(PAGED_TAILS)]
    n3, t3, _ = _serve(paged, reqs, cfg.vocab_size)
    stats = paged.kv_stats()
    if stats["prefix_hits"] < 1:
        _fail(f"the shared prefix was not reused: {stats}")
    report.update(paged_tokens=n3, paged_s=t3,
                  paged_prefix_hits=stats["prefix_hits"],
                  paged_peak_resident_pages=stats["peak_resident_pages"])

    # Kernel dispatches of the pack and the two legs (the parity step
    # below dispatches the kernels again).
    calls = pallas_backend.kernel_call_counts()
    report.update(kernel_calls=calls,
                  fallbacks=pallas_backend.fallback_counts())
    for op in ("quantize_pack", "fused_act_segment_matmul",
               "qkv_attn_decode", "qkv_attn_decode_paged"):
        if calls.get(op, 0) < 1:
            _fail(f"kernel {op} was never dispatched: {calls}")

    # One prefill step's logits: finite on the kernels; each kernel op
    # within the bound of xla_ref on the ring and on the paged cache.
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                    (MAX_BATCH, PREFILL_CHUNK)), jnp.int32)
    got = prefill_logits(eng.params, eng.cfg, backend, toks)
    if not np.isfinite(got).all():
        _fail("non-finite logits from the packed model")
    with jax.default_matmul_precision("highest"):
        want = prefill_logits(eng.params, eng.cfg, "xla_ref", toks)
    # Reported, not bounded (see OP_REL_RMS_BOUND): how far the whole
    # step's logits drift from the reference.
    report["logits_rel_rms"] = float(np.sqrt(
        np.mean((got - want) ** 2) / np.mean(want ** 2)))
    parity = _op_parity_backend(backend)
    for paged_cache in (False, True):
        prefill_logits(eng.params, eng.cfg, parity.name, toks,
                       paged=paged_cache)
    worst = {op: max(v) for op, v in parity.rel_rms.items()}
    report["op_rel_rms_max"] = worst
    for op in ("fused_act_segment_matmul", "qkv_attn_decode",
               "qkv_attn_decode_paged"):
        if op not in worst:
            _fail(f"the parity step never called {op}: {worst}")
    bad = {op: v for op, v in worst.items() if not v <= OP_REL_RMS_BOUND}
    if bad:
        _fail(f"kernel ops off xla_ref by a relative RMS over "
              f"{OP_REL_RMS_BOUND}: {bad}")

    fallbacks = pallas_backend.fallback_counts()
    if any(fallbacks.values()):
        _fail(f"reference-math fallbacks were taken: {fallbacks}")
    return report


def _train_setup(cfg, mesh, batch):
    """The sharded QAT train step as ``launch/train.py`` builds it: state
    and batch placed by the partition rules, step jitted over the mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch import sharding as sh
    from repro.models import shard as shard_ctx
    from repro.train import state as state_lib

    tcfg = state_lib.TrainConfig(t1=0, t2=100, warmup=1)
    rules = sh.activation_rules(cfg, mesh, batch=batch["tokens"].shape[0])
    key = jax.random.PRNGKey(SEED)
    with jax.set_mesh(mesh), shard_ctx.sharding_rules(rules):
        specs = jax.eval_shape(lambda: state_lib.init_state(key, cfg, tcfg))
        state_sh = sh.tree_shardings(specs, cfg, mesh, serve=False,
                                     rules=rules)
        state = jax.jit(lambda: state_lib.init_state(key, cfg, tcfg),
                        out_shardings=state_sh)()
        dp = NamedSharding(mesh, P(rules["batch"], None))
        step = jax.jit(
            lambda s, b, r: state_lib.train_step(s, b, cfg, tcfg, r),
            in_shardings=(state_sh, {"tokens": dp, "labels": dp},
                          NamedSharding(mesh, P())),
            donate_argnums=(0,))
    batch = {k: jax.device_put(v, dp) for k, v in batch.items()}
    return state, step, batch, rules


def _train_steps(cfg, mesh, batch, n: int):
    import jax
    from repro.models import shard as shard_ctx

    state, step, batch, rules = _train_setup(cfg, mesh, batch)
    out = []
    with jax.set_mesh(mesh), shard_ctx.sharding_rules(rules):
        for i in range(n):
            state, m = step(state, batch, jax.random.PRNGKey(i))
            out.append({"loss": float(m["loss"]),
                        "grad_norm": float(m["grad_norm"])})
    return out


def train_phase(cfg, backend: str, devices, layers: int = 2,
                steps: int = 3):
    """Step-0 agreement of the sharded QAT step on one device vs a 2x2
    data x model mesh at ``layers`` layers, then ``cfg``'s full depth on
    2x2 for ``steps`` steps, with the QAT fake-quant on ``backend``.
    Returns the report dict."""
    from repro import soniq
    from repro.backend import pallas as pallas_backend
    from repro.launch import mesh as mesh_lib

    cfg = soniq.with_phase(cfg, soniq.Phase.QAT)
    cfg = dataclasses.replace(cfg, quant=dataclasses.replace(
        cfg.quant, backend=backend))
    rng = np.random.default_rng(SEED)
    toks = rng.integers(0, cfg.vocab_size, (TRAIN_BATCH, TRAIN_SEQ + 1))
    batch = {"tokens": toks[:, :-1].astype("int32"),
             "labels": toks[:, 1:].astype("int32")}
    axes = ("data", "model")
    one = mesh_lib.make_mesh((1, 1), axes, devices=devices[:1])
    four = mesh_lib.make_mesh((2, 2), axes, devices=devices[:4])
    short = dataclasses.replace(cfg, num_layers=layers)
    report = {}
    t0 = time.perf_counter()
    ref = _train_steps(short, one, batch, 1)[0]
    got = _train_steps(short, four, batch, 1)[0]
    report["short_s"] = time.perf_counter() - t0
    report.update(short_1dev=ref, short_2x2=got)
    for k in ("loss", "grad_norm"):
        rel = abs(got[k] - ref[k]) / max(abs(ref[k]), 1e-12)
        report[f"short_{k}_rel_diff"] = rel
        if not rel <= TRAIN_REL_TOL:
            _fail(f"{layers}-layer step-0 {k}: 1 device {ref[k]} vs 2x2 "
                  f"{got[k]} (relative {rel} > {TRAIN_REL_TOL})")
    t0 = time.perf_counter()
    full = _train_steps(cfg, four, batch, steps)
    report["full_s"] = time.perf_counter() - t0
    report["full_2x2"] = full
    if not all(np.isfinite([s["loss"], s["grad_norm"]]).all()
               for s in full):
        _fail(f"non-finite {cfg.num_layers}-layer losses: {full}")
    report["peak_bytes_per_device"] = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in devices[:4]]
    calls = pallas_backend.kernel_call_counts()
    fallbacks = pallas_backend.fallback_counts()
    report.update(kernel_calls=calls, fallbacks=fallbacks)
    if calls.get("fake_quant", 0) < 1:
        _fail(f"the fake_quant kernel was never dispatched: {calls}")
    if any(fallbacks.values()):
        _fail(f"reference-math fallbacks were taken: {fallbacks}")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serve path on one chip; 4: the sharded "
                         "QAT train step on four")
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        _fail(f"the repo's package is not at {src}/repro")
    sys.path.insert(0, str(src))
    import jax
    from repro.configs import get_config
    from repro.launch import runtime

    runtime.require_tpu("chip_smoke.py")
    cache_dir = runtime.enable_compile_cache()
    devices = jax.devices()
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}")
    dev = runtime.device_summary()
    print(f"device: {dev['kind']} x{dev['count']} ({dev['platform']}), "
          f"compile cache {cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 1:
        report = serve_phase(get_config(ARCH), "pallas_mosaic")
        report["peak_bytes_in_use"] = \
            (devices[0].memory_stats() or {}).get("peak_bytes_in_use")
    else:
        report = train_phase(get_config(ARCH), "pallas_mosaic", devices)
    report["total_s"] = time.perf_counter() - t0
    for k, v in report.items():
        print(f"{k}: {v}")
    print(json.dumps({"ok": True, "device": dev}))
    return 0

if __name__ == "__main__":
    sys.exit(main())
