"""Block-size autotuner for the Pallas backends.

Cache model
-----------
A JSON file mapping a string key

    "<op>|shape=MxKxN|p=<bits>|dtype=<name>|platform=<jax backend>"

to ``{"blocks": {"block_m": ..., ...}, "us": <best measured microseconds>,
"candidates": <n tried>}``. Lookup (:func:`lookup`) is a pure dict read —
safe at jit-trace time, where timing is impossible — and returns ``{}`` on
a miss so callers fall back to the kernels' static defaults.

Measurement (:func:`autotune_op`) is explicit and happens *outside* any
trace: benchmarks (``runtime_proxy.py --autotune``) or an operator's
one-off script time each candidate with ``block_until_ready`` and persist
the winner. The cache location is ``$SONIQ_AUTOTUNE_CACHE`` (a file path)
or ``benchmarks/autotune.json`` inside the checkout, which may be absent
(every kernel then runs its static defaults); nothing is ever written
unless a measurement runs.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence

ENV_CACHE = "SONIQ_AUTOTUNE_CACHE"

# The tunable-op vocabulary: which ops have block knobs, and the
# documented values mirroring each kernel signature's defaults (see
# kernels/*.py headers for the VMEM budget math). The dispatch path does
# NOT read these — a cache miss returns {} and the kernel signature
# defaults apply — they exist for operators/tests enumerating what can be
# tuned (tests assert the keys stay a subset of backend OPS).
DEFAULT_BLOCKS: Dict[str, Dict[str, int]] = {
    "packed_segment_matmul": {"block_m": 256, "block_n": 128,
                              "block_k": 256},
    # Grouped (stacked-expert) segment GEMM: shape key is (E, M, Kp, N);
    # the expert grid dim has no block knob (one expert per grid step).
    "packed_segment_matmul_grouped": {"block_m": 256, "block_n": 128,
                                      "block_k": 256},
    "fused_act_segment_matmul": {"block_m": 256, "block_n": 128,
                                 "block_k": 256},
    "quantize_pack": {"block_k": 256, "block_n": 256},
    "noise_inject": {"block_k": 256, "block_n": 256},
    "fake_quant": {"block_m": 256, "block_k": 256},
    # Quantized-KV flash decode: shape key is (query rows B*Hk*S*G, ring
    # length T, head_dim D); block_t tiles the ring inner loop. (The paged
    # kernel has no knob: its tile is one page.)
    "qkv_attn_decode": {"block_t": 256},
}

_CACHE: Optional[Dict[str, Dict]] = None
_CACHE_FILE: Optional[str] = None


# <checkout>/benchmarks/autotune.json (this file is src/repro/backend/).
DEFAULT_CACHE = Path(__file__).resolve().parents[3] / "benchmarks" \
    / "autotune.json"


def cache_path() -> Path:
    return Path(os.environ.get(ENV_CACHE) or DEFAULT_CACHE)


def cache_key(op: str, shape: Sequence[int], p: int, dtype,
              platform: Optional[str] = None, backend: str = "") -> str:
    """``backend`` is the backend *name* (pallas_interpret vs
    pallas_mosaic time very differently yet share a jax platform — they
    must not share cache entries)."""
    if platform is None:
        import jax
        platform = jax.default_backend()
    dims = "x".join(str(int(d)) for d in shape)
    key = f"{op}|shape={dims}|p={int(p)}|dtype={dtype}|platform={platform}"
    return f"{key}|backend={backend}" if backend else key


def _read_file(path) -> Dict[str, Dict]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _load() -> Dict[str, Dict]:
    global _CACHE, _CACHE_FILE
    path = str(cache_path())
    if _CACHE is None or _CACHE_FILE != path:
        _CACHE_FILE = path
        _CACHE = _read_file(path)
    return _CACHE


def invalidate() -> None:
    """Drop the in-memory cache (next lookup re-reads the file)."""
    global _CACHE
    _CACHE = None


def lookup(op: str, *, shape: Sequence[int], p: int, dtype,
           platform: Optional[str] = None,
           backend: str = "") -> Dict[str, int]:
    """Cached block config for this (op, shape, dtype, platform, backend),
    or ``{}`` (use kernel defaults). Trace-time safe."""
    entry = _load().get(cache_key(op, shape, p, dtype, platform, backend))
    if not entry:
        return {}
    return {k: int(v) for k, v in entry["blocks"].items()}


def save_entry(key: str, blocks: Dict[str, int], us: float,
               candidates: int) -> None:
    """Persist one tuned entry with a read-merge-save cycle.

    Concurrent sweeps (e.g. two ``runtime_proxy.py --autotune`` processes
    covering different ``--backends``) share the cache file: each save
    re-reads the *live* file — never the possibly stale in-memory snapshot
    — merges its one entry in, and publishes atomically via a
    uniquely-named temp file + ``os.replace``. The worst interleaving
    loses one entry to a later merge, never the whole file to a torn or
    shared-temp-file write."""
    path = cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    cache = _read_file(path)
    cache[key] = {"blocks": blocks, "us": round(float(us), 2),
                  "candidates": int(candidates)}
    fd, tmp = tempfile.mkstemp(dir=str(path.parent),
                               prefix=path.name + ".", suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(cache, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    invalidate()


def _divisor_candidates(total: int, multiple: int,
                        wants: Sequence[int]) -> List[int]:
    from repro.kernels.packed_matmul import fit_block
    out: List[int] = []
    for w in wants:
        d = fit_block(total, w, multiple)
        if d not in out:
            out.append(d)
    return out


def candidates_for(op: str, shape: Sequence[int]) -> List[Dict[str, int]]:
    """A small grid of legal block configs for ``op`` at ``shape``: each
    block divides its dim and is tile-aligned (8 sublanes, 128 lanes) or
    the whole dim, the rule Mosaic lowers."""
    from repro.kernels.packed_matmul import LANE, SUBLANE
    if op not in DEFAULT_BLOCKS:
        return [{}]                    # no block knob: the defaults only
    rows = (64, 128, 256, 512)
    if op in ("packed_segment_matmul", "fused_act_segment_matmul",
              "packed_segment_matmul_grouped"):
        m, kp, n = shape[-3:]
        return [{"block_m": bm, "block_n": bn, "block_k": bk}
                for bm in _divisor_candidates(m, SUBLANE, rows)
                for bn in _divisor_candidates(n, LANE, (128, 256))
                for bk in _divisor_candidates(kp, LANE, (128, 256, 512))]
    if op == "fake_quant":
        m, k = shape
        return [{"block_m": bm, "block_k": bk}
                for bm in _divisor_candidates(m, SUBLANE, rows)
                for bk in _divisor_candidates(k, LANE, (128, 256, 512))]
    if op == "qkv_attn_decode":
        _m, t, _d = shape
        return [{"block_t": bt}
                for bt in _divisor_candidates(t, LANE,
                                              (128, 256, 512, 1024))]
    k, n = shape
    return [{"block_k": bk, "block_n": bn}
            for bk in _divisor_candidates(k, LANE, (128, 256, 512))
            for bn in _divisor_candidates(n, LANE, (128, 256, 512))]


def measure(fn, iters: int = 3) -> float:
    """Best-of-``iters`` wall time of ``fn()`` in microseconds (first call
    excluded — it compiles)."""
    import jax
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return best


def autotune_op(call, op: str, *, shape: Sequence[int], p: int, dtype,
                candidates: Optional[List[Dict[str, int]]] = None,
                iters: int = 3, backend: str = "") -> Dict[str, int]:
    """Time ``call(**blocks)`` over the candidate grid, persist the winner
    under this (op, shape, dtype, platform, backend) key, and return its
    blocks.

    ``call`` must run the real op at the real shape (closures over the
    operands); it is invoked outside any trace.
    """
    cands = candidates if candidates is not None \
        else candidates_for(op, shape)
    if not cands:
        return {}
    best_blocks, best_us, last_err = None, float("inf"), None
    for blocks in cands:
        try:
            us = measure(lambda: call(**blocks), iters=iters)
        except Exception as e:         # illegal tiling for this shape
            last_err = e
            continue
        if us < best_us:
            best_blocks, best_us = blocks, us
    if best_blocks is None:
        # Every candidate failing means the kernel itself is broken at
        # this shape, not a tiling quirk — don't pretend tuning succeeded.
        print(f"[autotune] {op} shape={tuple(shape)}: all {len(cands)} "
              f"candidates failed (last: {last_err!r}); using defaults",
              file=sys.stderr)
        return {}
    save_entry(cache_key(op, shape, p, dtype, backend=backend),
               best_blocks, best_us, len(cands))
    return best_blocks
