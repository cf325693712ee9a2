"""Serving launcher: packed-weight continuous batching behind a request
queue.

    PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
        --reduced --requests 8 --new-tokens 16

Initializes (or loads) QAT weights, converts to the packed 1/2/4-bit serve
format, and streams a mixed-length synthetic request workload through the
continuous-batching ``DecodeEngine`` (DESIGN.md §10): requests are admitted
into batch slots as they arrive / as slots free up, prompts prefill in
chunks while other slots decode, and completions stream back as they
finish — the deployment path of the paper's pipeline at production shape.
``--lockstep`` runs the fixed-batch baseline instead (same packed weights)
for an on-box throughput comparison.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import soniq
from repro.backend import registry as backend_registry
from repro.configs import get_config
from repro.launch import runtime
from repro.models import lm
from repro.train import checkpoint as ckpt_lib


def build_requests(args, vocab_size: int, rng) -> list:
    """Mixed-length synthetic workload: prompt lengths in
    [prompt_len/2, prompt_len], generation lengths in [new_tokens/2,
    new_tokens], staggered arrivals."""
    reqs = []
    for i in range(args.requests):
        plen = int(rng.integers(max(args.prompt_len // 2, 1),
                                args.prompt_len + 1))
        new = int(rng.integers(max(args.new_tokens // 2, 1),
                               args.new_tokens + 1))
        reqs.append(soniq.Request(
            prompt=rng.integers(0, vocab_size, (plen,)).astype(np.int32),
            max_new_tokens=new, temperature=args.temperature, seed=i,
            arrival_step=i // max(args.max_batch, 1)))
    return reqs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--lockstep", action="store_true",
                    help="run the fixed-batch baseline engine instead")
    ap.add_argument("--backend", default=None,
                    help="kernel backend for the jitted steps (xla_ref, "
                         "pallas_interpret, pallas_mosaic, or the "
                         "'pallas' alias; default: SONIQ_BACKEND env / "
                         "auto-negotiation)")
    ap.add_argument("--kv-bits", type=int, default=None, choices=[4],
                    help="quantize the decode KV cache to this many bits "
                         "(packed 4-bit ring + fused flash-decode, "
                         "DESIGN.md §12); default: fp cache")
    ap.add_argument("--kv-layout", default="ring",
                    choices=["ring", "paged"],
                    help="KV cache layout: contiguous per-slot ring "
                         "buffers, or the paged block-pool with "
                         "copy-on-write prefix sharing (DESIGN.md §13; "
                         "continuous engine only)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged layout; must divide "
                         "the effective cache length)")
    args = ap.parse_args()

    runtime.enable_compile_cache()
    print(f"device: {runtime.device_summary()}")
    if args.backend == "pallas_mosaic":
        runtime.require_tpu("--backend pallas_mosaic")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = soniq.with_phase(cfg, soniq.Phase.QAT)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    if args.ckpt:
        state, step = ckpt_lib.restore(args.ckpt, {"params": params})
        params = state["params"]
        print(f"loaded checkpoint step {step}")

    ecfg = soniq.EngineConfig(max_batch=args.max_batch,
                              cache_len=args.cache_len,
                              temperature=args.temperature,
                              prefill_chunk=args.prefill_chunk,
                              backend=args.backend,
                              kv_bits=args.kv_bits,
                              kv_layout=args.kv_layout,
                              page_size=args.page_size)
    print(f"kernel backend: {backend_registry.resolve(args.backend).name}"
          f", kv cache: "
          f"{'fp' if args.kv_bits is None else f'q{args.kv_bits}'}"
          f", layout: {args.kv_layout}"
          + (f" (page_size {args.page_size})"
             if args.kv_layout == "paged" else ""))
    rng = np.random.default_rng(0)

    if args.lockstep:
        eng = soniq.LockstepEngine(jax.device_get(params), cfg, ecfg)
        print(f"packed model: {soniq.packed_bytes(eng.params):,} bytes")
        prompts = rng.integers(0, cfg.vocab_size,
                               (args.requests, args.prompt_len)
                               ).astype(np.int32)
        t0 = time.time()
        out = eng.generate(prompts, args.new_tokens,
                           jax.random.PRNGKey(1) if args.temperature > 0
                           else None)
        dt = time.time() - t0
        total_new = args.requests * args.new_tokens
        print(f"[lockstep] {total_new} tokens in {dt:.2f}s "
              f"({total_new / dt:.1f} tok/s)")
        for i, row in enumerate(out):
            print(f"req {i}: {row[:args.prompt_len].tolist()} -> "
                  f"{row[args.prompt_len:].tolist()}")
        return

    eng = soniq.DecodeEngine(jax.device_get(params), cfg, ecfg)
    print(f"packed model: {soniq.packed_bytes(eng.params):,} bytes")
    reqs = build_requests(args, cfg.vocab_size, rng)
    t0 = time.time()
    total_new = 0
    for c in eng.serve(reqs):
        total_new += c.new_tokens.size
        print(f"req {c.request_id} [{c.finish_reason} @ step "
              f"{c.finished_step}]: {c.request.prompt.tolist()} -> "
              f"{c.new_tokens.tolist()}")
    dt = time.time() - t0
    print(f"[continuous] {total_new} tokens in {dt:.2f}s "
          f"({total_new / dt:.1f} tok/s, {eng.sched.step_count} engine "
          f"steps, max_batch {args.max_batch})")
    if args.kv_layout == "paged":
        st = eng.paged_kv_stats()
        print(f"[paged-kv] {st['num_pages']} pages x {st['page_size']} "
              f"tokens, peak resident {st['peak_resident_pages']} pages "
              f"({st['peak_resident_payload_bytes']:,} payload bytes of "
              f"{st['reserved_payload_bytes']:,} reserved), prefix hit "
              f"rate {st['prefix_hit_rate']:.2f}")


if __name__ == "__main__":
    main()
