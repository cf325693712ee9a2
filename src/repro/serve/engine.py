"""Serve engines over packed SONIQ weights (DESIGN.md §10).

Two engines share the packed-weight serve path (``soniq.to_serve`` /
``repro.api.transforms.convert_tree``: per-layer precisions re-budgeted to
the static segment mix, channels reordered (paper Obs. 4), codes
bit-packed into 1/2/4-bit carriers):

* :class:`LockstepEngine` — the original fixed-batch loop: one blocking
  ``generate()`` call, full-batch prefill, every row decodes until the
  longest request finishes. Kept as the parity/throughput baseline.
* :class:`DecodeEngine` — request-level **continuous batching**: an
  admission queue of :class:`repro.serve.scheduler.Request`, slot-based
  batch state, chunked prefill that fills idle slots while other slots
  decode, per-slot sampling params (temperature + seeded rng), and a
  streaming iterator returning :class:`Completion` objects as requests
  finish. Per-slot rows are independent, so its temperature-0 tokens are
  identical to the lockstep engine's (pinned by
  ``tests/test_serve_scheduler.py``).

``rebudget_pbits`` / ``serve_convert`` are deprecation shims kept for
external callers; the implementations moved to ``repro.api.transforms``.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Callable, Dict, Iterable, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import transforms as lifecycle
from repro.core.phases import Phase
from repro.core.qtypes import QuantConfig
from repro.models import lm

from . import kv_pool, kv_quant, spans
from .scheduler import DECODE, Completion, Request, Scheduler


def _paged_geometry(arch_cfg, ecfg: "EngineConfig"):
    """(page_size, pages_per_seq, num_pages) of the paged layout — the
    engine-side mirror of ``blocks.block_cache_init``'s geometry (the
    logical table length is the effective ring length in pages)."""
    clen = min(ecfg.cache_len, arch_cfg.window) if arch_cfg.window \
        else ecfg.cache_len
    ps = ecfg.page_size
    if clen % ps:
        raise ValueError(
            f"page_size {ps} must divide the effective ring length {clen} "
            f"(cache_len clipped to the window) so paged rollover wraps "
            f"where the ring layout does")
    pps = clen // ps
    npages = ecfg.num_pages if ecfg.num_pages is not None \
        else ecfg.max_batch * pps + 1
    return ps, pps, npages


def rebudget_pbits(pbits: np.ndarray, w: np.ndarray,
                   qcfg: QuantConfig) -> np.ndarray:
    """DEPRECATED — moved to ``repro.api.transforms.rebudget_pbits``."""
    warnings.warn(
        "engine.rebudget_pbits is deprecated; use "
        "repro.api.transforms.rebudget_pbits (soniq.rebudget_pbits)",
        DeprecationWarning, stacklevel=2)
    return lifecycle.rebudget_pbits(pbits, w, qcfg)


def serve_convert(params, qcfg: QuantConfig):
    """DEPRECATED — use ``soniq.to_serve`` (or the pytree-level
    ``repro.api.transforms.convert_tree``)."""
    warnings.warn(
        "engine.serve_convert is deprecated; use soniq.to_serve / "
        "repro.api.transforms.convert_tree",
        DeprecationWarning, stacklevel=2)
    return lifecycle.convert_tree(params, qcfg, rebudget=True)


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8
    cache_len: int = 256
    temperature: float = 0.0        # 0 = greedy (default for generate())
    cache_dtype: str = "float32"
    # Prompt tokens fed per slot per prefill step (1 = token-level
    # prefill). Every registered family is chunkable
    # (lm.serve_capabilities — KV caches scatter by position, SSM/hybrid
    # state threads through the chunked SSD scan, enc-dec adds sinusoids
    # in the chunked steps); a family whose descriptor ever turns
    # ``chunkable`` off auto-reduces to 1.
    prefill_chunk: int = 8
    # Kernel backend for the jitted decode/prefill steps — a registry name
    # ("xla_ref", "pallas_interpret", "pallas_mosaic", alias "pallas") or
    # None to keep the model config's choice / SONIQ_BACKEND / negotiation
    # (repro.backend.registry; DESIGN.md §11). Baked into QuantConfig at
    # engine construction, so it is jit-trace-stable.
    backend: Optional[str] = None
    # Allow the backend to fuse the per-decode-step activation quantization
    # into the packed-GEMM prologue (equal to the two-pass form to a few
    # ulp — DESIGN.md §11). False pins the two-pass reference; benchmarks flip
    # this to record the fused-vs-unfused delta.
    fuse_act_quant: bool = True
    # KV-cache precision (DESIGN.md §12). None = fp ring cache in
    # ``cache_dtype`` (status quo); 4 = packed 4-bit ring cache
    # (serve/kv_quant.py): ~4x fewer K/V payload bytes, decode attention
    # runs on the backend's ``qkv_attn_decode`` op (fused flash-decode
    # kernel on Pallas). Greedy tokens stay engine- and backend-parity at
    # q4; they differ from kv_bits=None by the pinned KV round-trip error.
    kv_bits: Optional[int] = None
    # KV-cache layout (DESIGN.md §13). "ring" reserves max_batch x
    # cache_len slots up front; "paged" draws ``page_size``-token pages
    # from a global pool on demand (serve/kv_pool.py: free-list +
    # refcounted copy-on-write prefix sharing), so resident bytes scale
    # with tokens actually cached and shared system prompts are stored
    # once. DecodeEngine only; greedy tokens stay token-identical to the
    # ring layout at equal kv_bits. ``page_size`` must divide the
    # effective ring length (cache_len clipped to the window).
    kv_layout: str = "ring"
    page_size: int = 16
    # Total pool pages incl. the reserved null page 0; None sizes for
    # full per-slot residency (max_batch * pages_per_seq + 1 — paging can
    # then never run out, occupancy is the win). Smaller pools gate
    # admission on page availability (head-of-line, FIFO preserved).
    num_pages: Optional[int] = None
    # Self-speculative decoding (DESIGN.md §14). 0 disables (status quo).
    # k > 0 makes each decode round draft k tokens with the low-slice
    # forward (the [K<=spec_draft_bits] segments of the SAME packed
    # carriers — zero extra weight bytes), then verify them in ONE
    # batched full-mix step; the longest matching prefix plus the verify
    # step's own token commit (1..k+1 tokens per round). Greedy streams
    # are token-identical to spec_tokens=0; temperature > 0 runs standard
    # rejection sampling (distribution-correct, not bitwise-equal).
    # DecodeEngine only; gated on lm.serve_capabilities(cfg).spec_capable.
    # Recurrent-state families (descriptor state != "kv") run the
    # snapshot/recommit speculative flavor: sequential state is
    # snapshotted before the round and the accepted prefix recommitted
    # with one masked re-feed of the same compiled verify step.
    spec_tokens: int = 0
    # Precision bound of the draft slice: segments above this skip.
    spec_draft_bits: int = 2


@dataclasses.dataclass
class JitEntry:
    """One jitted engine step function plus its audit metadata.

    Engines create every hot-path jit through :meth:`_PackedEngine._jit`,
    which records — *at trace time*, so steady-state steps pay nothing —
    how many times the function compiled (``trace_count``; the
    ``repro.analysis`` recompile guard pins this to 1 per shape family
    over a mixed traffic trace) and the abstract shapes it was traced at
    (``abstract_args``; the jaxpr/donation audits re-lower from these).
    ``donate_argnums`` is the engine's declaration of which cache-sized
    operands are donated (SQ004): the audit cross-checks it against the
    ``tf.aliasing_output`` markers in the lowered module.
    """
    name: str
    fn: Callable                       # the pre-jit python callable
    jitted: Callable = None
    donate_argnums: Tuple[int, ...] = ()
    trace_count: int = 0
    abstract_args: Optional[tuple] = None


class _PackedEngine:
    """Shared packed-params + jitted-step plumbing of both engines."""

    def _jit(self, name: str, fn: Callable, *,
             donate_argnums: Tuple[int, ...] = ()) -> Callable:
        """``jax.jit`` with the engine's audit bookkeeping (JitEntry) and
        buffer donation. Every cache-threading step function donates its
        cache operand: the old ring/pool buffers alias the new ones
        in-place instead of double-buffering cache-sized arrays each step
        (SQ004 — at production cache sizes the copy halves the batch that
        fits)."""
        entry = JitEntry(name, fn, donate_argnums=tuple(donate_argnums))

        @functools.wraps(fn)
        def traced(*args):
            entry.trace_count += 1
            entry.abstract_args = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                               jnp.result_type(x)), args)
            return fn(*args)

        entry.jitted = jax.jit(traced, donate_argnums=donate_argnums)
        self.jit_table[name] = entry
        return entry.jitted

    def __init__(self, params, arch_cfg, ecfg: EngineConfig,
                 *, already_serve: bool = False):
        self.jit_table: Dict[str, JitEntry] = {}
        self.cfg = arch_cfg.with_quant_mode(Phase.SERVE)
        if ecfg.backend is not None:
            self.cfg = dataclasses.replace(
                self.cfg, quant=dataclasses.replace(
                    self.cfg.quant, backend=ecfg.backend))
        if not ecfg.fuse_act_quant:
            self.cfg = dataclasses.replace(
                self.cfg, quant=dataclasses.replace(
                    self.cfg.quant, fuse_act_quant=False))
        if self.cfg.quant.act_scale_mode == "per_tensor":
            # Per-tensor dynamic act scales couple batch rows; serving needs
            # every request's tokens independent of batch composition
            # (continuous batching + lockstep parity), so the engines run
            # the row-independent per-token scale (DESIGN.md §10).
            self.cfg = dataclasses.replace(
                self.cfg, quant=dataclasses.replace(
                    self.cfg.quant, act_scale_mode="per_token"))
        if ecfg.kv_layout not in ("ring", "paged"):
            raise ValueError(f"unknown kv_layout {ecfg.kv_layout!r} "
                             f"(expected 'ring' or 'paged')")
        self.ecfg = ecfg
        self.params = params if already_serve else lifecycle.convert_tree(
            params, self.cfg.quant, rebudget=True)
        self._step = self._jit(
            "step", lambda p, c, t, pos: lm.decode_step(p, self.cfg, c, t,
                                                        pos),
            donate_argnums=(1,))

    def init_cache(self, batch: int):
        ecfg = self.ecfg
        if ecfg.kv_layout not in ("ring", "paged"):
            raise ValueError(f"unknown kv_layout {ecfg.kv_layout!r} "
                             f"(expected 'ring' or 'paged')")
        kwargs = {}
        if ecfg.kv_layout == "paged":
            ps, _pps, npages = _paged_geometry(self.cfg, ecfg)
            kwargs = dict(kv_layout="paged", page_size=ps,
                          num_pages=npages)
        return lm.init_cache(self.cfg, batch, ecfg.cache_len,
                             jnp.dtype(ecfg.cache_dtype),
                             kv_bits=ecfg.kv_bits, **kwargs)


class LockstepEngine(_PackedEngine):
    """Fixed-batch generation loop (greedy / shared-rng temperature
    sampling): the pre-continuous-batching baseline. Every row prefills and
    decodes in lockstep, so mixed-length batches burn full decode steps on
    rows that are already finished — `benchmarks/serve_throughput.py`
    quantifies the gap."""

    def generate(self, prompts: np.ndarray, max_new_tokens: int,
                 rng: Optional[jax.Array] = None) -> np.ndarray:
        """prompts [B, S0] int32 -> [B, S0 + max_new] (greedy unless
        temperature > 0)."""
        if self.ecfg.kv_layout != "ring":
            raise ValueError(
                "LockstepEngine only supports kv_layout='ring': the paged "
                "layout needs the DecodeEngine's host-side PagePool to "
                "drive page allocation (DESIGN.md §13)")
        b, s0 = prompts.shape
        cache = self.init_cache(b)
        toks = jnp.asarray(prompts, jnp.int32)
        out = [toks]
        logits = None
        for t in range(s0):
            pos = jnp.full((b,), t, jnp.int32)
            logits, cache = self._step(self.params, cache, toks[:, t], pos)
        cur = self._sample(logits, rng, 0)
        for t in range(max_new_tokens):
            out.append(cur[:, None])
            if t == max_new_tokens - 1:
                break
            pos = jnp.full((b,), s0 + t, jnp.int32)
            logits, cache = self._step(self.params, cache, cur, pos)
            cur = self._sample(logits, rng, t + 1)
        return np.asarray(jnp.concatenate(out, axis=1))

    def _sample(self, logits, rng, t):
        if self.ecfg.temperature <= 0 or rng is None:
            return jnp.argmax(logits, -1).astype(jnp.int32)
        k = jax.random.fold_in(rng, t)
        return jax.random.categorical(
            k, logits / self.ecfg.temperature).astype(jnp.int32)


def _key_bits(key) -> np.ndarray:
    """Raw uint32 bits of a PRNG key (accepts legacy raw or typed keys)."""
    if jnp.issubdtype(jnp.asarray(key).dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return np.asarray(key, np.uint32)


def _softmax(x: np.ndarray) -> np.ndarray:
    """Numerically stable host-side softmax over the last axis (the
    speculative acceptance rule runs on host — DESIGN.md §14)."""
    x = np.asarray(x, np.float64)
    x = x - x.max(axis=-1, keepdims=True)
    e = np.exp(x)
    return e / e.sum(axis=-1, keepdims=True)


def _sample_tokens(logits, keys, temps, counts):
    """Per-slot sampling: greedy where temp <= 0, else categorical with the
    slot's request key folded by its generated-token index (scheduling-
    invariant: request i's t-th token always uses fold_in(key_i, t))."""
    def one(lg, key, temp, n):
        greedy = jnp.argmax(lg, -1).astype(jnp.int32)
        k = jax.random.fold_in(key, n)
        samp = jax.random.categorical(
            k, lg / jnp.maximum(temp, 1e-6)).astype(jnp.int32)
        return jnp.where(temp > 0, samp, greedy)
    return jax.vmap(one)(logits, keys, temps, counts)


class DecodeEngine(_PackedEngine):
    """Request-level continuous-batching engine (DESIGN.md §10).

    Usage — streaming::

        eng = DecodeEngine(params, cfg, EngineConfig(max_batch=8))
        for completion in eng.serve(requests):   # yields as they finish
            ...

    or incremental (``submit`` / ``step``) for request loops that interleave
    admission with other work. ``generate()`` is a lockstep-compatible
    wrapper (same-shape prompts in, stacked tokens out) used by the legacy
    callers; at temperature 0 it returns exactly the lockstep tokens.
    """

    def __init__(self, params, arch_cfg, ecfg: EngineConfig,
                 *, already_serve: bool = False):
        super().__init__(params, arch_cfg, ecfg,
                         already_serve=already_serve)
        # Per-family serving capability descriptor (DESIGN.md §17): drives
        # the prefill chunk width, the spec_tokens gate, and which
        # speculative rollback flavor the engine runs.
        self.caps = lm.serve_capabilities(self.cfg)
        self.chunk = ecfg.prefill_chunk if self.caps.chunkable else 1
        # Recurrent (SSM/hybrid) sequential state cannot be un-written in
        # place the way ring/paged K/V can — speculative rounds snapshot
        # it and recommit the accepted prefix (see _spec_step).
        self._seq_state = self.caps.state != "kv"
        b = ecfg.max_batch
        # Ring length per slot: cache_len clipped to the window.
        self._clen = min(ecfg.cache_len, self.cfg.window) \
            if self.cfg.window else ecfg.cache_len

        # Self-speculative decoding (DESIGN.md §14): a draft step running
        # the low-slice forward (same packed weights, high-bit carriers
        # skipped) and a verify step returning per-lane full-mix logits.
        self.spec_width = ecfg.spec_tokens + 1
        if ecfg.spec_tokens > 0:
            if not self.caps.spec_capable:
                raise ValueError(
                    f"spec_tokens > 0 is not supported for arch family "
                    f"{self.caps.family!r}"
                    + (f": {self.caps.note}" if self.caps.note else "")
                    + " (lm.serve_capabilities — DESIGN.md §14/§17)")
            if self.spec_width > ecfg.cache_len:
                raise ValueError(
                    f"spec_tokens={ecfg.spec_tokens} cannot exceed "
                    f"cache_len-1={ecfg.cache_len - 1}")
            self._draft_cfg = dataclasses.replace(
                self.cfg, quant=dataclasses.replace(
                    self.cfg.quant,
                    draft_slice_bits=ecfg.spec_draft_bits))

            # Both return (argmax tokens, logits, cache): at temp 0 only
            # the tiny int argmaxes cross to host; the logits stay on
            # device unless a slot actually samples (rejection sampling).
            def draft_step(p, c, t, pos, act):
                lg, c2 = lm.decode_step(p, self._draft_cfg, c, t, pos,
                                        active=act)
                return jnp.argmax(lg, -1).astype(jnp.int32), lg, c2

            def verify_step(p, c, t, pos):
                lg, c2 = lm.verify_step(p, self.cfg, c, t, pos)
                return jnp.argmax(lg, -1).astype(jnp.int32), lg, c2

            self._draft = self._jit("draft", draft_step,
                                    donate_argnums=(1,))
            self._verify = self._jit("verify", verify_step,
                                     donate_argnums=(1,))
            if self._seq_state:
                # Snapshot/restore of the strictly-sequential state leaves
                # (SSM h/conv) around speculative rounds. The snapshot is
                # NOT donated: one snap is restored twice per round (before
                # verify and again for the accepted-prefix recommit).
                self._snap = self._jit("snap_state", lm.extract_seq_state)
                self._restore = self._jit("restore_state",
                                          lm.restore_seq_state,
                                          donate_argnums=(0,))

        # Sampling is fused into the jitted step: one dispatch and one
        # [B]-int transfer per engine step (the decode loop is host-latency
        # bound at small batch).
        def decode_sample(p, c, t, pos, act, keys, temps, counts):
            logits, c2 = lm.decode_step(p, self.cfg, c, t, pos, active=act)
            return _sample_tokens(logits, keys, temps, counts), c2

        def prefill_sample(p, c, t, pos, last, keys, temps, counts):
            logits, c2 = lm.prefill_step(p, self.cfg, c, t, pos, last)
            return _sample_tokens(logits, keys, temps, counts), c2

        self._decode = self._jit("decode", decode_sample,
                                 donate_argnums=(1,))
        self._prefill = self._jit("prefill", prefill_sample,
                                  donate_argnums=(1,))
        # One compiled reset for any admission set: idx is padded to
        # max_batch by repeating the first slot (re-wiping a row is
        # idempotent), so eager per-admission scatters never compile.
        self._reset = self._jit("reset", lm.reset_cache_slots,
                                donate_argnums=(0,))
        if ecfg.kv_layout == "paged":
            self._apply_ops = self._jit("apply_ops", kv_pool.apply_step_ops,
                                        donate_argnums=(0,))
            self._apply_poison = self._jit("apply_poison",
                                           kv_pool.apply_poison,
                                           donate_argnums=(0,))
        self._init_host_state()
        self.cache = None
        self._keys = np.zeros((b, 2), np.uint32)
        self._temps = np.zeros((b,), np.float32)

    def _init_host_state(self):
        """(Re)build the host-side scheduler — and, in the paged layout,
        the page-pool allocator that gates its admission."""
        b = self.ecfg.max_batch
        if self.ecfg.kv_layout == "paged":
            ps, pps, npages = _paged_geometry(self.cfg, self.ecfg)
            self.pool = kv_pool.PagePool(npages, ps, pps, b)
            self.sched = Scheduler(b, can_admit=self.pool.admissible)
            # Per-step device-op capacities (fixed jit shapes): each
            # planned slot touches at most ceil(width/page) + 1 pages,
            # where the step width is the prefill chunk — or the
            # speculative round width k+1 when that is larger.
            w = max(self.chunk, getattr(self, "spec_width", 1), 1)
            self._op_cap = b * (-(-w // ps) + 1)
            self._table_dirty = True       # first flush uploads the table
        else:
            self.pool = None
            self.sched = Scheduler(b)
        # Per-step records and speculation totals (serve/spans.py).
        self.stats = spans.EngineStats()

    def init_cache(self, batch: int):
        """The device cache, and from its shapes the KV room in positions
        and the payload bytes of one position (``kv_stats``)."""
        cache = super().init_cache(batch)
        if self.pool is not None:
            room = self.pool.capacity * self.pool.page_size
            per_pos = kv_pool.paged_payload_bytes_per_page(cache) \
                // self.pool.page_size
        else:
            room = batch * self._clen
            per_pos = kv_quant.cache_payload_bytes(cache) // room
        self._kv_room = room if per_pos else 0     # 0: no K/V to hold
        self._kv_position_bytes = per_pos
        return cache

    def _live_kv(self, widths: Optional[Dict[int, int]] = None) -> int:
        """KV positions the active slots hold once ``widths`` more tokens
        are fed: ``min(n_fed, ring length)`` per slot, from host state."""
        if not self._kv_room:
            return 0
        w = widths or {}
        return sum(min(st.n_fed + w.get(s, 0), self._clen)
                   for s, st in self.sched.slots.items())

    # --------------------------------------------------------- requests ----
    def submit(self, request: Request) -> int:
        """Queue a request; returns its request_id. In the paged layout a
        prompt whose page demand can never fit the pool is rejected here
        (ValueError) rather than deadlocking the admission queue, and the
        prompt's page digests are memoized for the prefix-map lookup at
        admission."""
        if self.pool is not None:
            plen = int(np.asarray(request.prompt).reshape(-1).shape[0])
            if plen and self.pool.target_pages(plen) > self.pool.capacity:
                raise ValueError(
                    f"prompt needs {self.pool.target_pages(plen)} KV pages "
                    f"but the pool only has {self.pool.capacity} "
                    f"allocatable pages — it could never be admitted. "
                    f"Raise EngineConfig.num_pages or shorten the prompt.")
        rid = self.sched.submit(request)
        if self.pool is not None and request.max_new_tokens > 0:
            self.pool.note_submit(rid, request.prompt)
        return rid

    def reset(self):
        """Drop all queued/active requests and cache state."""
        self._init_host_state()
        self.cache = None

    def load_cross(self, enc_out) -> None:
        """Install enc-dec cross-attention K/V for the current batch:
        project ``enc_out`` [B, T, D] through every decoder layer's cross
        K/V heads (``lm.build_cross_cache``) and store the result in the
        cache. At ``kv_bits=4`` the projections ride the serve 4-bit KV
        family (codes + fp16 scales — the same carriers as the
        self-attention ring), quantized ONCE here per encoder pass rather
        than per decode step; fp otherwise. Slot admission never wipes
        the cross subtree (``lm.reset_cache_slots``), so one load serves
        all requests decoding against this encoder batch; call again when
        the encoder output changes."""
        if self.cache is None:
            self.cache = self.init_cache(self.ecfg.max_batch)
            if self.pool is not None:
                self._table_dirty = True
        self.cache["cross"] = lm.build_cross_cache(
            self.params, self.cfg, enc_out, kv_bits=self.ecfg.kv_bits)

    # ---------------------------------------------------------- paging ----
    def _flush_pool_ops(self, ops: "kv_pool.StepOps"):
        """Apply one batch of allocator decisions to the device cache:
        COW copies + fresh-page wipes + the full host page table (one
        fixed-shape jitted call — ids are padded with null-page no-ops),
        then any debug poisons. No-op when nothing changed."""
        if ops.any() or self._table_dirty:
            cap = self._op_cap
            assert len(ops.wipes) <= cap and len(ops.copies) <= cap, \
                (len(ops.wipes), len(ops.copies), cap)
            wipes = np.zeros((cap,), np.int32)     # pad: re-wipe null page
            wipes[:len(ops.wipes)] = ops.wipes
            src = np.zeros((cap,), np.int32)       # pad: null self-copy
            dst = np.zeros((cap,), np.int32)
            for i, (s, d) in enumerate(ops.copies):
                src[i], dst[i] = s, d
            self.cache = self._apply_ops(self.cache, self.pool.table,
                                         wipes, src, dst)
            self._table_dirty = False
        if ops.poisons:
            # Pad by repeating a real pid (the null page is never
            # poisoned); fixed capacity = the whole pool.
            pids = np.full((self.pool.capacity,), ops.poisons[0], np.int32)
            pids[:len(ops.poisons)] = ops.poisons
            self.cache = self._apply_poison(self.cache, pids)

    # ------------------------------------------------------------- step ----
    def step(self) -> List[Completion]:
        """One engine step: admit arrived requests into free slots (wiping
        their cache rows), feed every active slot (chunked prefill for
        prompt-phase slots, one token for decode-phase slots), sample, and
        return any completions (their slots free up for the next step).

        Paged layout (DESIGN.md §13): admission maps prefix-map hits into
        the slot's page table (those prompt tokens skip prefill — the
        final prompt token is always re-fed, its logits seed sampling);
        before the device step the allocator makes every page the step
        writes privately mapped (fresh allocations wiped, shared/
        registered pages copy-on-write); after it, freshly completed
        prompt pages register in the prefix map and finished slots release
        their pages (back to the free list, or parked in the cached LRU
        when registered — poisoned in ``SONIQ_KV_POISON=1`` debug mode).

        ``spec_tokens > 0`` routes the step through the speculative
        draft-k/verify-1 round instead (DESIGN.md §14) — same admission,
        same completions contract, 1..k+1 tokens committed per decoding
        slot per step.

        Every phase runs under an ``engine.*`` span and the step leaves a
        record in ``self.stats`` (serve/spans.py).
        """
        with self.stats.step() as rec:
            with self.stats.phase("admit"):
                admitted = self._admit()
            rec.admitted = tuple(req.request_id for _, req in admitted)
            rec.reserved_kv = self._kv_room
            done = self._spec_step(rec) if self.ecfg.spec_tokens > 0 \
                else self._plain_step(rec)
            rec.finished = tuple(c.request_id for c in done)
        return done

    def _admit(self) -> List[Tuple[int, Request]]:
        """Admit arrived requests: one slot-reset program for the whole
        set, the per-slot sampling keys and, paged, the pool admission."""
        b = self.ecfg.max_batch
        if self.cache is None:
            self.cache = self.init_cache(b)
            if self.pool is not None:
                self._table_dirty = True
        admitted = self.sched.admit()
        if admitted:
            idx = np.full((b,), admitted[0][0], np.int32)
            idx[:len(admitted)] = [s for s, _ in admitted]
            self.cache = self._reset(self.cache, idx)
            for slot, req in admitted:
                self._keys[slot] = _key_bits(jax.random.PRNGKey(req.seed))
                self._temps[slot] = req.temperature
                if self.pool is not None:
                    shared = self.pool.admit(slot, req)
                    if shared:
                        # Prefix hit: those tokens are already in mapped
                        # pages — prefill starts after them.
                        self.sched.slots[slot].n_fed = shared
                        self._table_dirty = True
        return admitted

    def _plain_step(self, rec: spans.StepRecord) -> List[Completion]:
        """The prefill-width or decode program over every active slot."""
        b = self.ecfg.max_batch
        phase = self.stats.phase
        with phase("plan"):
            plan = self.sched.plan(self.chunk)
            widths = {s: len(t) for s, t in plan.items()}
            if plan and self.pool is not None:
                ops = kv_pool.StepOps()
                for slot, n in widths.items():
                    self.pool.prepare(slot, self.sched.slots[slot].n_fed, n,
                                      ops)
                if ops.any():
                    self._table_dirty = True
                self._flush_pool_ops(ops)
        if not plan:                       # idle: let queued arrivals age in
            with phase("advance"):
                return self.sched.advance({}, {})
        prefill = max(widths.values()) > 1
        with phase("inputs"):
            rec.kind = "prefill" if prefill else "decode"
            rec.fed = sum(widths.values())
            rec.rows = b * (self.chunk if prefill else 1)
            rec.live_kv = self._live_kv(widths)
            counts = np.zeros((b,), np.int32)
            for slot in plan:
                counts[slot] = len(self.sched.slots[slot].generated)
            if prefill:
                c = self.chunk             # fixed width: one compiled shape
                tokens = np.zeros((b, c), np.int32)
                pos = np.full((b, c), -1, np.int32)
                last = np.zeros((b,), np.int32)
                for slot, toks in plan.items():
                    n = widths[slot]
                    st = self.sched.slots[slot]
                    tokens[slot, :n] = toks
                    pos[slot, :n] = st.n_fed + np.arange(n)
                    last[slot] = n - 1
            else:
                tokens = np.zeros((b,), np.int32)
                pos = np.zeros((b,), np.int32)
                active = np.zeros((b,), bool)
                for slot, toks in plan.items():
                    tokens[slot] = toks[0]
                    pos[slot] = self.sched.slots[slot].n_fed
                    active[slot] = True
        with phase("dispatch"):
            if prefill:
                out, self.cache = self._prefill(self.params, self.cache,
                                                tokens, pos, last,
                                                self._keys, self._temps,
                                                counts)
            else:
                out, self.cache = self._decode(self.params, self.cache,
                                               tokens, pos, active,
                                               self._keys, self._temps,
                                               counts)
        with phase("sync"):
            # soniq-lint: disable=SQ005(the one budgeted [B]-int sync per step)
            sampled = np.asarray(out)
        with phase("advance"):
            slot_of = {st.request.request_id: s
                       for s, st in self.sched.slots.items()}
            # Post-step fed counts, captured before advance() pops finished
            # slots: note_filled's wrapped-through guard needs the TRUE fed
            # count (prompt + generated - 1), not the prompt length — a
            # wrap-overwritten page must never register as prompt content.
            fed_of = {st.request.request_id: st.n_fed + widths.get(s, 0)
                      for s, st in self.sched.slots.items()}
            done = self.sched.advance(
                widths, {s: int(sampled[s]) for s in plan})
            if self.pool is not None:
                self._paged_after_advance(done, slot_of, fed_of, plan,
                                          kv_pool.StepOps())
        return done

    def _paged_after_advance(self, done, slot_of, fed_of, plan, ops):
        """Post-advance pool bookkeeping shared by both step flavors:
        register finished prompts' full pages (before release parks them
        in the cached LRU for future hits) and release their pages,
        register freshly completed prompt pages of still-active slots,
        then flush the accumulated device ops."""
        for c in done:
            slot = slot_of.get(c.request_id)
            if slot is None:               # zero-generation immediate
                continue
            self.pool.note_filled(slot, c.request.prompt,
                                  fed_of[c.request_id])
            self.pool.release(slot, ops)
            self._table_dirty = True
        for slot in plan:
            st = self.sched.slots.get(slot)
            if st is not None:
                self.pool.note_filled(slot, st.request.prompt, st.n_fed)
        self._flush_pool_ops(ops)

    # ------------------------------------------------------ speculative ----
    def _spec_rng(self, st, tag: int) -> np.random.Generator:
        """Deterministic host rng for temperature > 0 speculative
        sampling, keyed by (request seed, purpose tag, generated count):
        a request's stream depends only on its own state — never on batch
        composition (the scheduling-invariance contract of DESIGN.md
        §10). Spec-mode temp > 0 streams are distribution-correct but
        NOT bitwise-equal to the spec-off device sampler (§14)."""
        return np.random.default_rng(
            (int(st.request.seed) & 0x7FFFFFFF, tag, len(st.generated)))

    def _accept(self, st, drafts, dprobs, targets, lg_rows, rng):
        """Acceptance rule for one slot's k drafts given the verify
        argmaxes ``targets`` [k+1] and (temp > 0 only) the verify logits
        ``lg_rows`` [k+1, V] — lane j is the full-mix distribution of
        the token FOLLOWING draft j. Returns the committed token list
        (accepted prefix + one bonus/correction token — 1..k+1 tokens).

        temp 0: longest prefix of drafts matching the verify argmaxes,
        then the argmax at the first mismatch (correction) or after the
        last draft (bonus) — exactly the token-by-token greedy stream.
        temp > 0: standard speculative rejection sampling — accept draft
        d with prob min(1, q(d)/p(d)); on reject, sample the residual
        max(q - p, 0); if all accepted, sample the bonus from q."""
        t = st.request.temperature
        committed = []
        if t <= 0:
            a = 0
            while a < len(drafts) and drafts[a] == int(targets[a]):
                committed.append(drafts[a])
                a += 1
            committed.append(int(targets[a]))
            return committed
        for j, d in enumerate(drafts):
            q = _softmax(lg_rows[j] / t)
            p = dprobs[j]
            if rng.random() < q[d] / max(p[d], 1e-30):
                committed.append(d)
                continue
            resid = np.maximum(q - p, 0.0)
            tot = resid.sum()
            probs = resid / tot if tot > 0 else q
            committed.append(int(rng.choice(len(q), p=probs)))
            return committed
        q = _softmax(lg_rows[len(drafts)] / t)
        committed.append(int(rng.choice(len(q), p=q)))
        return committed

    def _spec_step(self, rec: spans.StepRecord) -> List[Completion]:
        """One speculative engine round (DESIGN.md §14): draft k tokens
        per decoding slot with the low-slice forward, verify them in ONE
        batched full-mix ``lm.verify_step`` of fixed width k+1 (which
        doubles as the chunked-prefill feed for prompt-phase slots riding
        the same call), commit the accepted prefix + the verify step's
        own token, and roll the rejected suffix back (ring: pure
        accounting — rejected entries carry future position stamps the
        causal mask excludes until legitimately overwritten; paged:
        wholly-stale freshly-allocated pages release).

        A slot whose round would wrap the KV ring cannot draft (the
        wrap-clobbered history could not be restored on rejection); it
        rides the verify step with just its own token — a plain full-mix
        decode step, so the guard never costs correctness."""
        b = self.ecfg.max_batch
        k = self.ecfg.spec_tokens
        c = self.spec_width                             # k + 1
        phase = self.stats.phase
        with phase("plan"):
            plan = self.sched.plan(c)
            base_fed = {s: self.sched.slots[s].n_fed for s in plan}
            decode_slots = [s for s in plan
                            if self.sched.slots[s].phase == DECODE]
            draft_slots = [s for s in decode_slots
                           if base_fed[s] + c <= self._clen]
            if plan and self.pool is not None:
                ops = kv_pool.StepOps()
                for s in plan:
                    w = c if s in draft_slots else \
                        (1 if s in decode_slots else len(plan[s]))
                    self.pool.prepare(s, base_fed[s], w, ops)
                if ops.any():
                    self._table_dirty = True
                self._flush_pool_ops(ops)
        if not plan:                       # idle: let queued arrivals age in
            with phase("advance"):
                return self.sched.advance({}, {})
        rec.kind = "spec"

        # Recurrent-state families: snapshot the sequential (h/conv)
        # leaves before any draft forward touches them — the SSM decode
        # rule advances every row's state (no position masking), so both
        # the draft slots' own speculation AND bystander slots are
        # restored from this snapshot before the verify step re-derives
        # the exact state via the chunked SSD scan (DESIGN.md §17).
        snap = None
        if self._seq_state and draft_slots:
            with phase("dispatch"):
                snap = self._snap(self.cache)

        # --- draft sub-steps: low-slice forward, decode-phase slots only
        # (a draft write to a PROMPT position would never be rewritten by
        # verify, so prefill-phase slots sit out with pos = -1).
        with phase("inputs"):
            cur = np.zeros((b,), np.int32)
            for s in decode_slots:
                cur[s] = int(plan[s][0])
            hot = [s for s in decode_slots
                   if self.sched.slots[s].request.temperature > 0]
            round_rng = {s: self._spec_rng(self.sched.slots[s], 0x5EC)
                         for s in hot}
            drafts = {s: [] for s in draft_slots}
            dprobs = {s: [] for s in draft_slots}
            active = np.zeros((b,), bool)
            for s in draft_slots:
                active[s] = True
        for j in range(k if draft_slots else 0):
            with phase("inputs"):
                pos = np.zeros((b,), np.int32)
                for s in draft_slots:
                    pos[s] = base_fed[s] + j
                rec.fed += len(draft_slots)
                rec.rows += b
            with phase("dispatch"):
                gr, lg, self.cache = self._draft(self.params, self.cache,
                                                 cur, pos, active)
            with phase("sync"):
                # soniq-lint: disable=SQ005(host acceptance needs the draft)
                gr = np.asarray(gr)
                # soniq-lint: disable=SQ005(logits only cross when sampling)
                lgh = np.asarray(lg, np.float32) if hot else None
            with phase("inputs"):
                for s in draft_slots:
                    if self.sched.slots[s].request.temperature > 0:
                        p = _softmax(
                            lgh[s] / self.sched.slots[s].request.temperature)
                        tok = int(round_rng[s].choice(len(p), p=p))
                        dprobs[s].append(p)
                    else:
                        tok = int(gr[s])
                    drafts[s].append(tok)
                    cur[s] = tok

        # --- one batched full-mix verify (+ prefill feed) step
        if snap is not None:
            # Rewind the drafting corruption: verify re-derives every
            # slot's sequential state from the pre-round snapshot (its
            # chunked SSD scan threads h0 through the fed lanes), so its
            # per-lane logits are exactly the token-by-token full-mix
            # stream.
            with phase("dispatch"):
                self.cache = self._restore(self.cache, snap)
        with phase("inputs"):
            tokens = np.zeros((b, c), np.int32)
            pos = np.full((b, c), -1, np.int32)
            lanes = {}
            for s, toks in plan.items():
                feed = [int(plan[s][0])] + drafts[s] if s in draft_slots \
                    else [int(x) for x in toks[:c]]
                tokens[s, :len(feed)] = feed
                pos[s, :len(feed)] = base_fed[s] + np.arange(len(feed))
                lanes[s] = len(feed)
            rec.fed += sum(lanes.values())
            rec.rows += b * c
            rec.live_kv = self._live_kv(lanes)
        with phase("dispatch"):
            gr, lg, self.cache = self._verify(self.params, self.cache,
                                              tokens, pos)
        with phase("sync"):
            # soniq-lint: disable=SQ005(per-round acceptance sync, DESIGN §14)
            gr = np.asarray(gr)                         # [B, C] argmaxes
            need_lg = bool(hot) or any(
                self.sched.slots[s].request.temperature > 0 for s in plan
                if s not in decode_slots)
            # soniq-lint: disable=SQ005(logits only cross when a slot samples)
            lgh = np.asarray(lg, np.float32) if need_lg else None  # [B,C,V]

        # --- host-side acceptance + commit
        with phase("advance"):
            fed = {}
            sampled = {}
            for s, toks in plan.items():
                st = self.sched.slots[s]
                if s not in decode_slots:
                    n = len(toks)
                    fed[s] = n
                    if st.n_fed + n >= len(st.request.prompt):
                        # Prompt completes this step: its last lane's
                        # logits seed sampling (argmax at temp 0 —
                        # identical to the device sampler's greedy branch).
                        sampled[s] = int(gr[s, n - 1]) \
                            if st.request.temperature <= 0 \
                            else self._pick(st, lgh[s, n - 1])
                    continue
                committed = self._accept(st, drafts.get(s, []),
                                         dprobs.get(s, []), gr[s],
                                         None if lgh is None else lgh[s],
                                         round_rng.get(s))
                a = len(committed) - 1          # accepted drafts
                fed[s] = 1 + a
                sampled[s] = committed
                if s in draft_slots:
                    self.stats.spec_rounds += 1
                    self.stats.spec_drafted += k
                    self.stats.spec_accepted += a
                    if self.pool is not None and a < k:
                        self._table_dirty = True

        # --- recurrent-state recommit: verify advanced the sequential
        # state through ALL c fed lanes, accepted or not, and rejected
        # SSM state cannot be rolled back by position stamps the way
        # ring/paged K/V is. Restore the pre-round snapshot once more and
        # re-feed through the SAME compiled verify step with the rejected
        # draft lanes masked to pos -1 (their dt contribution is
        # neutralized — ssm.mamba2_prefill), committing state for exactly
        # the accepted tokens. Prefill-phase slots re-feed identical
        # lanes from identical state (deterministic — their post-recommit
        # state is bitwise the verify result), and ring K/V rewrites of
        # the same (position, token) are idempotent.
        if snap is not None:
            with phase("inputs"):
                pos2 = pos.copy()
                for s in draft_slots:
                    pos2[s, fed[s]:] = -1
                rec.fed += int((pos2 >= 0).sum())
                rec.rows += b * c
            with phase("dispatch"):
                self.cache = self._restore(self.cache, snap)
                _, _, self.cache = self._verify(self.params, self.cache,
                                                tokens, pos2)

        # --- paged rollback of wholly-rejected pages, then advance
        with phase("advance"):
            slot_of = {st.request.request_id: s
                       for s, st in self.sched.slots.items()}
            fed_of = {st.request.request_id: st.n_fed + fed.get(s, 0)
                      for s, st in self.sched.slots.items()}
            ops = kv_pool.StepOps()
            if self.pool is not None:
                for s in draft_slots:
                    self.pool.rollback(s, base_fed[s] + fed[s],
                                       base_fed[s] + c, ops)
            done = self.sched.advance(fed, sampled)
            if self.pool is not None:
                self._paged_after_advance(done, slot_of, fed_of, plan, ops)
        return done

    def _pick(self, st, lg_row) -> int:
        """Sample one token from a host-side fp32 logits row with the
        slot's params (greedy argmax at temp 0; host rng otherwise)."""
        t = st.request.temperature
        if t <= 0:
            return int(np.argmax(lg_row))
        p = _softmax(lg_row / t)
        return int(self._spec_rng(st, 0x9EF).choice(len(p), p=p))

    def spec_stats(self) -> dict:
        """Speculation telemetry: rounds drafted, draft tokens proposed /
        accepted, and the mean accepted draft length per round (the
        benchmark's acceptance figure — k accepted means every draft
        survived verification)."""
        st = self.stats
        return {
            "spec_tokens": self.ecfg.spec_tokens,
            "rounds": st.spec_rounds,
            "drafted": st.spec_drafted,
            "accepted": st.spec_accepted,
            "mean_accepted": (st.spec_accepted / st.spec_rounds
                              if st.spec_rounds else 0.0),
        }

    # ----------------------------------------------------- cancellation ----
    def cancel(self, request_id: int) -> Optional[Completion]:
        """Cancel a request by id — queued or active — releasing every
        resource it holds. A queued request leaves the admission queue
        (and, paged, drops its memoized digests + any admissible()
        reservation); an active one frees its batch slot AND routes its
        pool pages through ``PagePool.release`` with the device table
        re-uploaded before the next step — ``Scheduler.evict`` alone
        would leak them (refcount drift, ``PagePool.check()`` asserts).
        Returns the "evicted" Completion, or None when the id is unknown
        or already finished. Call between engine steps."""
        comp = self.sched.cancel(request_id)
        if comp is not None:
            if self.pool is not None:
                self.pool.forget_submit(request_id)
            return comp
        slot = next((s for s, st in self.sched.slots.items()
                     if st.request.request_id == request_id), None)
        if slot is None:
            return None
        if self.pool is not None:
            ops = kv_pool.StepOps()
            # A cancelled slot's finished prompt pages still register
            # (they are valid shared-prefix content for future requests);
            # mid-prefill slots simply have no full pages to offer.
            st = self.sched.slots[slot]
            self.pool.note_filled(slot, st.request.prompt, st.n_fed)
            self.pool.release(slot, ops)
            self._table_dirty = True
            self._flush_pool_ops(ops)
        return self.sched.evict(slot)

    # ---------------------------------------------------------- metrics ----
    def kv_stats(self) -> dict:
        """KV payload bytes reserved against bytes in use, for either
        layout (benchmarks record these next to tokens/s — DESIGN.md §13).
        Byte figures count K/V *payload* only (codes + scales / fp k and
        v), as ``kv_quant.cache_payload_bytes`` does; the bytes of one
        position come from the cache's shapes, taken once when it was
        made. In use: ``min(n_fed, ring length)`` positions per active
        slot. Reserved: every slot's ring, or, paged, every allocatable
        page. The paged layout adds its occupancy and sharing figures; a
        "page" spans every layer (the allocator maps one physical id in
        all layers)."""
        assert self.cache is not None, "run at least one step first"
        per_pos = self._kv_position_bytes
        live = self._live_kv()
        stats = {
            "kv_layout": self.ecfg.kv_layout,
            "position_payload_bytes": per_pos,
            "reserved_positions": self._kv_room,
            "live_positions": live,
            "reserved_payload_bytes": self._kv_room * per_pos,
            "live_payload_bytes": live * per_pos,
        }
        pool = self.pool
        if pool is not None:
            per_page = per_pos * pool.page_size
            stats.update({
                "page_size": pool.page_size,
                "num_pages": pool.num_pages,
                "page_payload_bytes": per_page,
                "resident_pages": pool.resident_pages,
                "peak_resident_pages": pool.peak_resident,
                "resident_payload_bytes": pool.resident_pages * per_page,
                "peak_resident_payload_bytes": pool.peak_resident * per_page,
                "prefix_hits": pool.hits,
                "prefix_lookups": pool.lookups,
                "prefix_hit_rate": pool.prefix_hit_rate,
            })
        return stats

    # -------------------------------------------------------- streaming ----
    def run(self) -> Iterator[Completion]:
        """Drive steps until queue and slots drain, yielding completions in
        finish order."""
        while self.sched.has_work():
            yield from self.step()

    def serve(self, requests: Iterable[Request]) -> Iterator[Completion]:
        """Submit all requests, then stream completions."""
        for r in requests:
            self.submit(r)
        return self.run()

    # ------------------------------------------------------------ compat ----
    def generate(self, prompts: np.ndarray, max_new_tokens: int,
                 rng: Optional[jax.Array] = None) -> np.ndarray:
        """Lockstep-compatible batch call: same-length prompts [B, S0] ->
        stacked [B, S0 + max_new]. Resets any in-flight engine state.
        Greedy unless the engine temperature > 0 AND ``rng`` is given (the
        per-request seeds are then derived from ``rng``; the stream is
        reproducible but not bitwise-identical to lockstep sampling, which
        shares one rng across the batch)."""
        self.reset()
        prompts = np.asarray(prompts, np.int32)
        temp = self.ecfg.temperature if rng is not None else 0.0
        base = int(_key_bits(rng).ravel()[-1]) if rng is not None else 0
        reqs = [Request(prompt=p, max_new_tokens=max_new_tokens,
                        temperature=temp, seed=base + i)
                for i, p in enumerate(prompts)]
        out = {c.request_id - reqs[0].request_id: c.tokens
               for c in self.serve(reqs)}
        return np.stack([out[i] for i in range(len(reqs))])


# Leaf-name vocabulary for packed_model_bytes. Packed carriers count one
# byte per element; fp leaves count their dtype itemsize; metadata leaves
# (permutations / precision maps — the paper's "3 ints per layer" lives in
# buffer shapes, not here) are excluded from the network-size metric.
_PACKED_LEAVES = frozenset({"w4", "w2", "w1"})
_FP_LEAVES = frozenset({"w", "table", "wscale", "b", "g", "conv_w",
                        "conv_b", "A_log", "D", "dt_bias", "norm_g"})
_META_LEAVES = frozenset({"perm", "pbits_sorted", "pbits", "s"})


def packed_model_bytes(serve_params) -> int:
    """Total packed weight bytes (the paper's network-size metric).

    Every leaf name must be classified (packed carrier / fp weight /
    metadata); an unknown name raises ``ValueError`` instead of being
    silently skipped — a renamed carrier leaf must not make the metric
    under-report."""
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(serve_params)[0]:
        if leaf is None:
            continue
        name = str(getattr(path[-1], "key", ""))
        if name in _PACKED_LEAVES:
            total += leaf.size
        elif name in _FP_LEAVES:
            total += leaf.size * np.dtype(leaf.dtype).itemsize
        elif name not in _META_LEAVES:
            raise ValueError(
                f"packed_model_bytes: unknown leaf {jax.tree_util.keystr(path)!r}"
                f" (name {name!r}) — classify it in engine._PACKED_LEAVES/"
                "_FP_LEAVES/_META_LEAVES so the size metric stays honest")
    return int(total)
