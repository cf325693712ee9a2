"""Continuous-batching vs lockstep serve throughput (tokens/s).

    PYTHONPATH=src python benchmarks/serve_throughput.py

Mixed-length synthetic workload (skewed prompt and generation lengths —
the traffic shape the ROADMAP's heavy-traffic story cares about) through
both engines over the SAME packed weights:

* lockstep baseline: requests grouped into fixed batches of ``max_batch``,
  prompts right-padded to the batch max, every row decoding until the
  batch's longest request finishes — the pre-PR serve loop;
* continuous batching: request-level admission, slot reuse, chunked
  prefill (DESIGN.md §10).

Reports useful tokens/s (only each request's own ``max_new_tokens`` count
as useful; padded prompt positions and overshoot decode steps are waste)
and the speedup. The PR-2 acceptance bar is >= 1.5x on this workload.

``--backends`` additionally sweeps the continuous engine across kernel
backends (default: every backend available here) and appends the per-
backend tokens/s to ``BENCH_backend.json`` next to this script — the
record the perf trajectory of the backend work is measured against. The
sweep includes a ``+kv4_paged`` leg: ring vs paged KV layout at q4 on
shared-system-prompt traffic, recording peak-resident vs reserved cache
payload bytes and the prefix-hit rate next to tokens/s (DESIGN.md §13),
and two self-speculative legs (DESIGN.md §14): ``+spec`` — the stock
checkpoint with speculation on, recording tokens/s and the mean
accepted-draft length honestly (a random-init checkpoint's draft slice
rarely agrees with the full mix, so acceptance is near zero and the
rounds are overhead) — and ``+spec_oracle`` — an acceptance-upper-bound
checkpoint (high-bit segment scales zeroed, so the draft IS the full
mix bitwise and every draft survives verification) on a
linear-dominated shape, where the skipped carriers pay for themselves:
the measured tokens/s win of zero-extra-weight-memory speculation.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import soniq
from repro.backend import registry as backend_registry
from repro.configs.base import ArchConfig
from repro.core.qtypes import QuantConfig
from repro.models import lm
from repro.serve import engine as engine_lib
from repro.serve import kv_quant
from repro.serve.scheduler import Request

try:                                   # package run (benchmarks.run)
    from . import _common
except ImportError:                    # direct script run
    import _common

record_backend_bench = _common.record_backend_bench


def make_workload(num_requests: int, rng) -> list:
    """Skewed mixed-length traffic: short chats dominate, a few long
    prompts / long generations drag lockstep batches out."""
    reqs = []
    for i in range(num_requests):
        if i % 4 == 3:                       # 1-in-4 heavy request
            plen = int(rng.integers(24, 48))
            new = int(rng.integers(48, 64))
        else:
            plen = int(rng.integers(4, 12))
            new = int(rng.integers(8, 24))
        reqs.append(Request(prompt=rng.integers(1, 500, (plen,)),
                            max_new_tokens=new, seed=i))
    return reqs


def make_shared_prefix_workload(num_requests: int, rng) -> list:
    """Shared-system-prompt traffic (the paged-KV story, DESIGN.md §13):
    every request opens with the same 64-token system prompt, then a
    short per-user tail — the regime where the prefix map stores the
    system pages once and resident pages stay far below the ring's
    reserved capacity."""
    system = rng.integers(1, 500, (64,)).astype(np.int32)
    reqs = []
    for i in range(num_requests):
        tail = rng.integers(1, 500, (int(rng.integers(2, 9)),))
        reqs.append(Request(
            prompt=np.concatenate([system, tail.astype(np.int32)]),
            max_new_tokens=int(rng.integers(8, 17)), seed=i))
    return reqs


def oracle_low_slice_params(packed_params, draft_bits: int):
    """Acceptance-upper-bound checkpoint for the ``+spec_oracle`` leg:
    zero the per-group scales of every segment ABOVE ``draft_bits``, so
    the low-slice draft forward is bitwise identical to the full mix
    (those segments contribute exactly nothing) while still reading only
    the low-bit carriers. Same packed buffers, zero extra weight bytes —
    this isolates the machinery's ceiling from checkpoint-dependent
    draft/target agreement."""
    def walk(tree):
        if isinstance(tree, dict):
            if "w4" in tree and tree.get("wscale") is not None:
                out = dict(tree)
                n4 = tree["w4"].shape[-2] * 2 // 16
                n2 = tree["w2"].shape[-2] * 4 // 16
                ws = np.array(tree["wscale"])
                ws[..., :(n4 if draft_bits >= 2 else n4 + n2)] = 0.0
                out["wscale"] = jnp.asarray(ws)
                return out
            return {k: walk(v) for k, v in tree.items()}
        if isinstance(tree, list):
            return [walk(v) for v in tree]
        if isinstance(tree, tuple):
            return tuple(walk(v) for v in tree)
        return tree
    return walk(packed_params)


def run_lockstep(eng, reqs, max_batch: int) -> float:
    """Grouped fixed batches, padded to the batch max; returns seconds."""
    t0 = time.time()
    for i in range(0, len(reqs), max_batch):
        group = reqs[i:i + max_batch]
        s0 = max(len(r.prompt) for r in group)
        new = max(r.max_new_tokens for r in group)
        prompts = np.zeros((len(group), s0), np.int32)
        for j, r in enumerate(group):        # right-pad to the batch max
            prompts[j, :len(r.prompt)] = r.prompt
        eng.generate(prompts, new)
    return time.time() - t0


def run_continuous(eng, reqs) -> float:
    eng.reset()
    t0 = time.time()
    for _ in eng.serve(list(reqs)):
        pass
    return time.time() - t0


def _tokens_of_engine(eng, reqs):
    """Serve a fresh copy of ``reqs`` and return {request order: tokens}
    (the spec-leg parity assert; also doubles as the jit warm-up run)."""
    got = {c.request_id: c.tokens for c in eng.serve(
        [dataclasses.replace(r) for r in reqs])}
    return {k - min(got): v for k, v in got.items()}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backends", default=None,
                    help="comma-separated kernel backends to sweep the "
                         "continuous engine over (default: all available; "
                         "'' skips the sweep)")
    args = ap.parse_args(argv)

    cfg = ArchConfig(
        name="bench", family="dense", num_layers=2, d_model=128,
        num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=512, head_dim=32,
        dtype="float32", param_dtype="float32", q_block=64,
        quant=QuantConfig(mode="qat"))
    params = jax.device_get(lm.init_params(jax.random.PRNGKey(0), cfg))
    ecfg = soniq.EngineConfig(max_batch=args.max_batch, cache_len=128,
                              prefill_chunk=args.prefill_chunk)
    lock = engine_lib.LockstepEngine(params, cfg, ecfg)
    cont = engine_lib.DecodeEngine(params, cfg, ecfg,
                                   already_serve=False)

    rng = np.random.default_rng(args.seed)
    reqs = make_workload(args.requests, rng)
    useful = sum(r.max_new_tokens for r in reqs)

    # Warm both jit caches on a toy batch before timing.
    lock.generate(np.ones((args.max_batch, 4), np.int32), 2)
    warm = [Request(prompt=np.ones(5, np.int32), max_new_tokens=2, seed=0)]
    list(cont.serve(warm))

    t_lock = run_lockstep(lock, reqs, args.max_batch)
    t_cont = run_continuous(cont, reqs)
    tps_lock = useful / t_lock
    tps_cont = useful / t_cont
    print(f"workload: {len(reqs)} requests, {useful} useful new tokens, "
          f"max_batch {args.max_batch}, prefill_chunk {args.prefill_chunk}")
    print(f"lockstep   : {t_lock:6.2f}s  {tps_lock:8.1f} tok/s")
    print(f"continuous : {t_cont:6.2f}s  {tps_cont:8.1f} tok/s")
    print(f"speedup    : {tps_cont / tps_lock:.2f}x  (target >= 1.5x)")
    # harness CSV row (us per generated token; derived = speedup)
    print(f"serve_throughput,{1e6 * t_cont / useful:.1f},"
          f"{tps_cont / tps_lock:.2f}x_vs_lockstep", flush=True)

    # ------------------------------------------- kernel-backend sweep ----
    names = (backend_registry.available() if args.backends is None
             else [b for b in args.backends.split(",") if b])
    sweep = {}
    for name in names:
        # Backends carrying the fused activation-quant prologue are timed
        # both ways ("+two_pass" = fused-vs-unfused engine delta); every
        # backend is additionally timed on the quantized KV cache
        # ("+kv4": packed 4-bit ring + qkv_attn_decode — the tokens/s leg
        # of the cache-bytes record below). BENCH_backend.json is the
        # running record.
        variants = [(name, True, None)]
        if backend_registry.resolve(name).supports(
                "fused_act_segment_matmul"):
            variants.append((f"{name}+two_pass", False, None))
        variants.append((f"{name}+kv4", True, 4))
        for label, fuse, kv_bits in variants:
            eng = engine_lib.DecodeEngine(
                params, cfg, soniq.EngineConfig(
                    max_batch=args.max_batch, cache_len=128,
                    prefill_chunk=args.prefill_chunk, backend=name,
                    fuse_act_quant=fuse, kv_bits=kv_bits))
            list(eng.serve([Request(prompt=np.ones(5, np.int32),
                                    max_new_tokens=2, seed=0)]))  # warm jit
            t = run_continuous(eng, reqs)
            sweep[label] = {"tok_s": round(useful / t, 1),
                            "seconds": round(t, 3)}
            print(f"backend {label:>26}: {t:6.2f}s  "
                  f"{useful / t:8.1f} tok/s")
    # --------------------------------------------- paged-KV comparison ----
    # Ring vs paged layout at q4 on shared-system-prompt traffic: same
    # packed weights, same requests — records tokens/s side by side plus
    # the pool's occupancy (peak resident vs reserved payload bytes) and
    # prefix-hit rate. The §13 acceptance bar: resident <= 0.5x the ring's
    # reserved bytes with tokens/s within 10% of the ring engine.
    shared_reqs = make_shared_prefix_workload(args.requests, rng)
    shared_useful = sum(r.max_new_tokens for r in shared_reqs)
    for name in names:
        legs = {}
        for label, layout in (("ring", "ring"), ("paged", "paged")):
            eng = engine_lib.DecodeEngine(
                params, cfg, soniq.EngineConfig(
                    max_batch=args.max_batch, cache_len=128,
                    prefill_chunk=args.prefill_chunk, backend=name,
                    kv_bits=4, kv_layout=layout))
            # Warm the jit caches AND (paged) the prefix map with one
            # system-prompt request: steady-state shared-prefix traffic
            # finds the system pages already registered, the regime the
            # occupancy claim is about. No reset before timing — the
            # warm pages must survive into the measured run.
            list(eng.serve([dataclasses.replace(shared_reqs[0])]))
            t0 = time.time()
            for _ in eng.serve([dataclasses.replace(r)
                                for r in shared_reqs]):
                pass
            legs[label] = (time.time() - t0, eng)
        t_ring, _ = legs["ring"]
        t_paged, paged_eng = legs["paged"]
        stats = paged_eng.kv_stats()
        row = {
            "tok_s": round(shared_useful / t_paged, 1),
            "seconds": round(t_paged, 3),
            "ring_tok_s": round(shared_useful / t_ring, 1),
            "tok_s_vs_ring": round(t_ring / t_paged, 3),
            "page_size": stats["page_size"],
            "peak_resident_payload_bytes":
                stats["peak_resident_payload_bytes"],
            "reserved_payload_bytes": stats["reserved_payload_bytes"],
            "resident_over_reserved": round(
                stats["peak_resident_payload_bytes"]
                / stats["reserved_payload_bytes"], 3),
            "prefix_hit_rate": round(stats["prefix_hit_rate"], 3),
        }
        sweep[f"{name}+kv4_paged"] = row
        print(f"backend {name + '+kv4_paged':>26}: {t_paged:6.2f}s  "
              f"{shared_useful / t_paged:8.1f} tok/s "
              f"({row['tok_s_vs_ring']:.2f}x ring, resident "
              f"{row['resident_over_reserved']:.2f}x reserved, prefix hit "
              f"{row['prefix_hit_rate']:.2f})")

    # -------------------------------------------- speculative decoding ----
    # "+spec": the stock checkpoint/workload with the draft-k/verify-1
    # round on (k=3, draft slice <= 2 bits). Tokens are spec-off
    # identical at temp 0 (asserted); tokens/s and the mean accepted
    # draft length are recorded AS MEASURED — a random-init checkpoint's
    # low slice almost never matches the full-mix argmax, so acceptance
    # ~0 and the extra rounds cost throughput. The row exists so the
    # record separates machinery cost from checkpoint-dependent
    # acceptance (DESIGN.md §14).
    for name in names:
        eng = engine_lib.DecodeEngine(
            params, cfg, soniq.EngineConfig(
                max_batch=args.max_batch, cache_len=128,
                prefill_chunk=args.prefill_chunk, backend=name,
                spec_tokens=3, spec_draft_bits=2))
        list(eng.serve([Request(prompt=np.ones(5, np.int32),
                                max_new_tokens=2, seed=0)]))  # warm jit
        t = run_continuous(eng, reqs)
        st = eng.spec_stats()
        sweep[f"{name}+spec"] = {
            "tok_s": round(useful / t, 1), "seconds": round(t, 3),
            "tok_s_vs_base": round(
                (useful / t) / sweep[name]["tok_s"], 3),
            "spec_tokens": 3, "spec_draft_bits": 2,
            "mean_accepted": round(st["mean_accepted"], 3)}
        print(f"backend {name + '+spec':>26}: {t:6.2f}s  "
              f"{useful / t:8.1f} tok/s (mean accepted "
              f"{st['mean_accepted']:.2f}/3)")

    # "+spec_oracle": the acceptance upper bound, on the backend fast
    # enough to time a linear-dominated shape (the interpreted Pallas
    # backend is orders of magnitude off real kernel timing anyway).
    if "xla_ref" in names:
        big = dataclasses.replace(
            cfg, name="bench-spec", num_layers=4, d_model=256,
            num_heads=4, num_kv_heads=4, d_ff=2048, head_dim=64)
        big_params = jax.device_get(
            lm.init_params(jax.random.PRNGKey(0), big))
        base_kw = dict(max_batch=args.max_batch, cache_len=64,
                       prefill_chunk=args.prefill_chunk, backend="xla_ref")
        probe = engine_lib.DecodeEngine(big_params, big,
                                        soniq.EngineConfig(**base_kw))
        oracle = oracle_low_slice_params(jax.device_get(probe.params),
                                         draft_bits=1)
        spec_reqs = [Request(prompt=rng.integers(1, 500, (int(l),)),
                             max_new_tokens=32, seed=i)
                     for i, l in enumerate((8, 12, 6, 10))]
        spec_useful = sum(r.max_new_tokens for r in spec_reqs)

        def best_of(ecfg, reps=3):
            eng = engine_lib.DecodeEngine(oracle, big, ecfg,
                                          already_serve=True)
            tokens = _tokens_of_engine(eng, spec_reqs)     # warm + tokens
            best = min(run_continuous(eng, [dataclasses.replace(r)
                                            for r in spec_reqs])
                       for _ in range(reps))
            return eng, best, tokens

        _, t_off, tok_off = best_of(soniq.EngineConfig(**base_kw))
        eng_on, t_on, tok_on = best_of(soniq.EngineConfig(
            **base_kw, spec_tokens=5, spec_draft_bits=1))
        # Greedy spec-on vs spec-off agreement is RECORDED, not asserted
        # bitwise at this scale: the spec-off stream comes from the
        # width-1 decode program, the spec-on commits from the
        # width-(k+1) verify program, and at 4 layers x d_ff 2048 the
        # ~1e-6 cross-width lowering noise occasionally lands an
        # activation on a quantization rounding boundary — one
        # knife-edge argmax flips and every autoregressive token after
        # it follows (measured; DESIGN.md §17 "exactness scope").
        # Acceptance-logic bugs are pinned by mean_accepted instead:
        # oracle drafts ARE the full mix bitwise (zeroed high-bit
        # scales add exact +0.0 terms), so anything short of near-total
        # acceptance means the accept/rollback machinery broke. Tier-1
        # pins bitwise spec parity below the knife-edge regime.
        depths, agree = [], 0
        for k in tok_off:
            a, b = np.asarray(tok_off[k]), np.asarray(tok_on[k])
            assert len(a) == len(b), (k, len(a), len(b))
            neq = np.nonzero(a != b)[0]
            depths.append(int(neq[0]) if neq.size else len(a))
            agree += int(not neq.size)
        st = eng_on.spec_stats()
        assert st["mean_accepted"] >= 4.0, st
        row = {
            "tok_s": round(spec_useful / t_on, 1),
            "base_tok_s": round(spec_useful / t_off, 1),
            "tok_s_vs_base": round(t_off / t_on, 3),
            "spec_tokens": 5, "spec_draft_bits": 1,
            "mean_accepted": round(st["mean_accepted"], 3),
            "streams_identical": f"{agree}/{len(depths)}",
            "min_first_divergence": int(min(depths)),
            "packed_model_bytes": engine_lib.packed_model_bytes(oracle),
            "model": {"num_layers": 4, "d_model": 256, "d_ff": 2048},
        }
        sweep["xla_ref+spec_oracle"] = row
        print(f"backend {'xla_ref+spec_oracle':>26}: {t_on:6.2f}s  "
              f"{spec_useful / t_on:8.1f} tok/s "
              f"({row['tok_s_vs_base']:.2f}x no-spec, mean accepted "
              f"{st['mean_accepted']:.2f}/5, streams identical "
              f"{agree}/{len(depths)})")

    # ---------------------------------------------- per-family fast paths ----
    # One row per newly fast-pathed family (DESIGN.md §17), per backend:
    # "+moe_packed"  — MoE arch served from stacked [E, ...] packed expert
    #                  banks through the grouped packed_segment_matmul op
    #                  (the trace-time dispatch counter asserts the
    #                  grouped kernel actually ran on Pallas backends);
    # "+ssm_chunked" — SSM arch with chunked prefill (the capability
    #                  descriptor lifted the old 1-token/step fallback);
    #                  records the chunked-vs-token-prefill ratio;
    # "+crosskv4"    — enc-dec arch decoding against 4-bit cross K/V
    #                  (DecodeEngine.load_cross), recording the
    #                  once-per-request cross payload compression.
    from repro.backend import pallas as pallas_backend
    from repro.configs import registry as arch_registry
    import repro.configs  # noqa: F401  (trigger arch registrations)

    fam_rng = np.random.default_rng(args.seed + 1)
    fam_reqs = [Request(prompt=fam_rng.integers(1, 200, (int(l),)),
                        max_new_tokens=int(n), seed=i)
                for i, (l, n) in enumerate(zip((4, 11, 6, 9),
                                               (10, 8, 12, 8)))]
    fam_useful = sum(r.max_new_tokens for r in fam_reqs)

    def fam_time(eng, enc_out=None):
        for _ in range(2):                     # warm jit, then measure
            eng.reset()
            if enc_out is not None:
                eng.load_cross(enc_out)
            t0 = time.time()
            for _ in eng.serve([dataclasses.replace(r) for r in fam_reqs]):
                pass
        return time.time() - t0

    fam_specs = {
        "moe_packed": ("mixtral-8x22b", {}),
        "ssm_chunked": ("mamba2-2.7b", {}),
        "crosskv4": ("whisper-medium", dict(kv_bits=4)),
    }
    for fam_label, (arch, extra) in fam_specs.items():
        fcfg = arch_registry.get_config(arch).reduced()
        fparams = jax.device_get(lm.init_params(jax.random.PRNGKey(2),
                                                fcfg))
        enc_out = None
        if fcfg.encoder_layers:
            enc_out = fam_rng.normal(
                0, 1, (args.max_batch, 12, fcfg.d_model)).astype(np.float32)
        for name in names:
            kw = dict(max_batch=args.max_batch, cache_len=64,
                      prefill_chunk=args.prefill_chunk, backend=name,
                      **extra)
            eng = engine_lib.DecodeEngine(fparams, fcfg,
                                          soniq.EngineConfig(**kw))
            grouped = "packed_segment_matmul_grouped"
            before = pallas_backend.kernel_call_counts()[grouped]
            t = fam_time(eng, enc_out)
            row = {"arch": fcfg.name, "tok_s": round(fam_useful / t, 1),
                   "seconds": round(t, 3)}
            if fam_label == "moe_packed" and eng.cfg.quant.backend_name \
                    and backend_registry.resolve(name).supports(
                        "packed_segment_matmul_grouped"):
                assert pallas_backend.kernel_call_counts()[grouped] > before, \
                    "MoE serve did not dispatch the grouped kernel"
                row["grouped_dispatch"] = True
            if fam_label == "ssm_chunked":
                tok_eng = engine_lib.DecodeEngine(
                    fparams, fcfg, soniq.EngineConfig(
                        **{**kw, "prefill_chunk": 1}))
                t1 = fam_time(tok_eng)
                row["tok_s_vs_token_prefill"] = round(t1 / t, 3)
            if fam_label == "crosskv4":
                fp_cross = kv_quant.cross_payload_bytes(
                    {"cross": lm.build_cross_cache(eng.params, eng.cfg,
                                                   enc_out)})
                q4_cross = kv_quant.cross_payload_bytes(eng.cache)
                row["cross_payload_bytes"] = q4_cross
                row["fp_cross_payload_bytes"] = fp_cross
                row["cross_payload_ratio"] = round(fp_cross / q4_cross, 2)
            sweep[f"{name}+{fam_label}"] = row
            print(f"backend {name + '+' + fam_label:>26}: {t:6.2f}s  "
                  f"{fam_useful / t:8.1f} tok/s  ({fcfg.name})")

    # Cache-byte accounting for the q4 claim (specs=True: no allocation).
    # Payload = K/V codes + scales (q4) vs fp16 k/v buffers; the ``pos``
    # ring bookkeeping is identical in both families and reported
    # separately so the ratio stays honest (DESIGN.md §12).
    fp16_cache = lm.init_cache(cfg, args.max_batch, 128, jnp.float16,
                               specs=True)
    q4_cache = lm.init_cache(cfg, args.max_batch, 128, jnp.float16,
                             specs=True, kv_bits=4)
    fp_payload = kv_quant.cache_payload_bytes(fp16_cache)
    q4_payload = kv_quant.cache_payload_bytes(q4_cache)
    kv_bytes = {"fp16_payload_bytes": fp_payload,
                "q4_payload_bytes": q4_payload,
                "payload_ratio": round(fp_payload / q4_payload, 2),
                "pos_meta_bytes": kv_quant.cache_meta_bytes(q4_cache)}
    print(f"kv cache payload: fp16 {fp_payload:,} B -> q4 {q4_payload:,} B "
          f"({kv_bytes['payload_ratio']}x, + {kv_bytes['pos_meta_bytes']:,}"
          " B pos metadata either way)")
    if sweep:
        record_backend_bench("serve_throughput", {
            "workload": {"requests": len(reqs), "useful_tokens": useful,
                         "max_batch": args.max_batch,
                         "prefill_chunk": args.prefill_chunk},
            "shared_prefix_workload": {
                "requests": len(shared_reqs), "system_prompt_tokens": 64,
                "useful_tokens": shared_useful},
            "backends": sweep,
            "kv_cache": kv_bytes})
    return tps_cont / tps_lock


if __name__ == "__main__":
    main()
