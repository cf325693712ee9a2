"""Paged KV-cache subsystem (DESIGN.md §13): the block-pool allocator's
invariants, the paged device cache + ``qkv_attn_decode_paged`` backend op,
engine token parity against the ring layout, admission behaviour under
page pressure, and the ``SONIQ_KV_POISON`` use-after-free trip wire."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backend import pallas as pallas_backend
from repro.backend import registry
from repro.configs.base import ArchConfig
from repro.core.qtypes import QuantConfig
from repro.models import lm
from repro.serve import engine, kv_pool, kv_quant
from repro.serve.scheduler import Request


# ============================================== host allocator (jax-free) =
def test_pool_alloc_wipe_release_roundtrip():
    pool = kv_pool.PagePool(5, 4, 4, 2, poison=False)
    ops = kv_pool.StepOps()
    pool.prepare(0, 0, 8, ops)               # two fresh pages
    assert sorted(ops.wipes) == [1, 2] and not ops.copies
    assert pool.table[0, :2].tolist() == [1, 2]
    assert pool.resident_pages == 2
    pool.check()
    pool.release(0, ops)
    assert (pool.table[0] == -1).all()
    assert pool.resident_pages == 0 and sorted(pool.free) == [1, 2, 3, 4]
    pool.check()


def test_pool_cow_on_shared_and_registered_pages():
    """Writing into a page another slot maps (or a registered prefix page)
    must allocate a private copy, never mutate in place."""
    pool = kv_pool.PagePool(6, 4, 4, 2, poison=False)
    ops = kv_pool.StepOps()
    prompt = np.arange(8, dtype=np.int32)
    pool.admit(0, Request(prompt=prompt, max_new_tokens=4, request_id=0))
    pool.prepare(0, 0, 8, ops)
    pool.note_filled(0, prompt, 8)           # pages 1, 2 now registered
    first = int(pool.table[0, 0])
    assert first in pool.page_hash
    ops = kv_pool.StepOps()
    pool.prepare(0, 8, 1, ops)               # decode rolls into page 3
    assert int(pool.table[0, 2]) not in (first, -1)
    # Rolling over INTO a registered page copies it out of the map's reach.
    ops = kv_pool.StepOps()
    pool.prepare(0, 16, 1, ops)              # wraps to logical page 0
    new = int(pool.table[0, 0])
    assert new != first and (first, new) in ops.copies
    assert new not in pool.page_hash         # the copy is private
    assert first in pool.cached              # canonical page parked in LRU
    pool.check()


def test_pool_prefix_sharing_and_lru_revival():
    pool = kv_pool.PagePool(8, 4, 4, 4, poison=False)
    prompt = np.arange(9, dtype=np.int32)    # 2 full pages + 1 token
    ops = kv_pool.StepOps()
    pool.note_submit(0, prompt)
    pool.admit(0, Request(prompt=prompt, max_new_tokens=2, request_id=0))
    pool.prepare(0, 0, 9, ops)
    pool.note_filled(0, prompt, 9)
    # Second request with the same prompt: both full pages hit.
    pool.note_submit(1, prompt)
    shared = pool.admit(1, Request(prompt=prompt, max_new_tokens=2,
                                   request_id=1))
    assert shared == 8 and pool.hits == 2
    p0 = int(pool.table[0, 0])
    assert int(pool.table[1, 0]) == p0 and pool.refcount[p0] == 2
    pool.check()
    # Both slots release: registered pages park in the LRU, not the free
    # list, and a third admission revives them.
    ops = kv_pool.StepOps()
    pool.release(0, ops)
    pool.release(1, ops)
    assert p0 in pool.cached and p0 not in pool.free
    shared = pool.admit(2, Request(prompt=prompt, max_new_tokens=2,
                                   request_id=2))
    assert shared == 8 and p0 not in pool.cached
    pool.check()


def test_pool_exhaustion_raises_not_corrupts():
    pool = kv_pool.PagePool(3, 4, 4, 2, poison=False)
    ops = kv_pool.StepOps()
    pool.prepare(0, 0, 8, ops)               # takes both usable pages
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.prepare(1, 0, 4, ops)
    pool.check()


def test_pool_poison_ops_and_realloc_cancellation():
    """Freed pages are queued for poisoning; a page freed and reallocated
    within the same StepOps batch must NOT stay queued (the engine applies
    poisons after wipes — a stale poison would corrupt the new page)."""
    pool = kv_pool.PagePool(4, 4, 4, 2, poison=True)
    ops = kv_pool.StepOps()
    pool.prepare(0, 0, 4, ops)
    pool.release(0, ops)
    assert ops.poisons == [int(pool.free[-1])]
    pid = ops.poisons[0]
    pool.prepare(1, 0, 4, ops)               # reallocates the same page
    assert int(pool.table[1, 0]) == pid
    assert pid not in ops.poisons and pid in ops.wipes
    pool.check()


# A deterministic allocator fuzz driver shared by the always-on seeded
# test and the hypothesis property test: random interleavings of
# admission (some with shared prompts), prefill/decode prepares,
# speculative rollbacks (a prepared suffix un-commits, DESIGN.md §14),
# engine-style cancels (note_filled + release mid-flight) and releases,
# with pool.check() asserting the partition/refcount invariants after
# every operation.
def _run_pool_program(seed, num_pages, page_size, pages_per_seq,
                      max_batch, n_ops):
    rng = np.random.default_rng(seed)
    pool = kv_pool.PagePool(num_pages, page_size, pages_per_seq,
                            max_batch, poison=bool(seed % 2))
    prompts = [rng.integers(0, 50, (int(l),)).astype(np.int32)
               for l in rng.integers(1, pages_per_seq * page_size + 1,
                                     (4,))]
    active = {}                              # slot -> (prompt, n_fed)
    rid = 0
    for _ in range(n_ops):
        ops = kv_pool.StepOps()
        kind = rng.choice(["admit", "feed", "release", "cancel"])
        if kind == "admit" and len(active) < max_batch:
            slot = next(s for s in range(max_batch) if s not in active)
            prompt = prompts[int(rng.integers(0, len(prompts)))]
            req = Request(prompt=prompt, max_new_tokens=4, request_id=rid)
            if not pool.admissible(req):
                continue
            pool.note_submit(rid, prompt)
            shared = pool.admit(slot, req)
            active[slot] = [prompt, shared]
            rid += 1
        elif kind == "feed" and active:
            slot = int(rng.choice(sorted(active)))
            prompt, n_fed = active[slot]
            width = int(rng.integers(1, page_size + 2))
            try:
                pool.prepare(slot, n_fed, width, ops)
            except RuntimeError:
                pool.check()                 # exhaustion must not corrupt
                continue
            # At allocation time (before the step registers anything),
            # shared (refcount > 1) and registered pages must never be
            # handed out as in-place write targets (shared definition
            # with PagePool.check() and the model checker).
            assert kv_pool.step_ops_violations(pool, ops) == []
            fed = n_fed + width
            if fed <= pages_per_seq * page_size and rng.random() < 0.4:
                # Speculative rollback: the verify pass rejected a random
                # suffix of this round's writes (no-wrap rounds only —
                # the engine's spec guard, DESIGN.md §14).
                committed = int(rng.integers(n_fed, fed + 1))
                rops = kv_pool.StepOps()
                pool.rollback(slot, committed, fed, rops)
                pool.check()
                fed = committed
            active[slot][1] = fed
            pool.note_filled(slot, prompt, fed)
        elif kind == "cancel" and active:
            # The engine's cancel path: finished prompt pages register,
            # then every page reference drops (DecodeEngine.cancel).
            slot = int(rng.choice(sorted(active)))
            prompt, n_fed = active[slot]
            pool.note_filled(slot, prompt, n_fed)
            pool.release(slot, ops)
            del active[slot]
        elif kind == "release" and active:
            slot = int(rng.choice(sorted(active)))
            pool.release(slot, ops)
            del active[slot]
        pool.check()


def test_pool_wrap_never_registers_overwritten_pages():
    """Once decode growth wraps the logical ring, the early pages hold
    wrap content, not prompt content — ``note_filled`` must not register
    them under the prompt's page hashes (a poisoned prefix map would feed
    later requests garbage)."""
    pool = kv_pool.PagePool(8, 4, 2, 2, poison=False)  # 2 logical pages
    prompt = np.arange(8, dtype=np.int32)    # exactly fills the ring
    pool.admit(0, Request(prompt=prompt, max_new_tokens=8, request_id=0))
    ops = kv_pool.StepOps()
    pool.prepare(0, 0, 8, ops)
    # Decode token 8 wraps into logical page 0 BEFORE any registration:
    # the private page is legally rewritten in place.
    pool.prepare(0, 8, 1, ops)
    pool.note_filled(0, prompt, 9)
    h = pool.page_hashes(prompt)
    assert h[0] not in pool.prefix_map       # overwritten: must not enter
    assert h[1] in pool.prefix_map           # untouched: registers fine
    pool.check()


def test_pool_wrap_into_registered_page_at_full_residency():
    """Regression: COW into a registered page that is ours alone
    (refcount 1), with no free or cached page anywhere — the state a
    full-residency slot's decode wrap reaches under the default pool
    sizing — must unregister the canonical and write in place (the ring
    layout wraps the same page), not raise pool exhaustion."""
    pool = kv_pool.PagePool(3, 4, 2, 1, poison=False)   # capacity 2
    prompt = np.arange(8, dtype=np.int32)
    pool.admit(0, Request(prompt=prompt, max_new_tokens=4, request_id=0))
    ops = kv_pool.StepOps()
    pool.prepare(0, 0, 8, ops)               # both pages mapped
    pool.note_filled(0, prompt, 8)           # both registered
    first = int(pool.table[0, 0])
    ops = kv_pool.StepOps()
    pool.prepare(0, 8, 1, ops)               # decode wraps into page 0
    assert int(pool.table[0, 0]) == first    # wrote in place
    assert not ops.copies and not ops.wipes
    assert first not in pool.page_hash       # canonical unregistered
    assert pool.page_hashes(prompt)[0] not in pool.prefix_map
    assert pool.page_hashes(prompt)[1] in pool.prefix_map
    pool.check()


def test_pool_same_step_admission_reserves_capacity():
    """Regression: an ``admissible()`` pass that returns True must
    reserve the request's page demand — Scheduler.admit() checks every
    head-of-queue request before any pool.admit() runs, so a second
    same-step check that cannot see the first's demand overcommits a
    tight pool (prefill then dies with pool exhaustion)."""
    pool = kv_pool.PagePool(5, 4, 4, 2, poison=False)   # capacity 4
    r0 = Request(prompt=np.arange(12, dtype=np.int32), max_new_tokens=2,
                 request_id=0)                           # 3 pages
    r1 = Request(prompt=np.arange(50, 58, dtype=np.int32),
                 max_new_tokens=2, request_id=1)         # 2 pages
    assert pool.admissible(r0)
    assert not pool.admissible(r1)           # 3 + 2 > 4: must wait
    pool.admit(0, r0)
    assert not pool.admissible(r1)           # demand now tracked via slot
    ops = kv_pool.StepOps()
    pool.prepare(0, 0, 12, ops)
    assert not pool.admissible(r1)           # 3 mapped + 2 > 4
    pool.release(0, ops)
    assert pool.admissible(r1)               # capacity freed up
    pool.check()


def test_admissible_own_prefix_pages_not_double_counted():
    """Regression (the spec-PR lifecycle bug): ``admissible()`` counted a
    request's own revivable cached-LRU prefix pages twice — once as
    shareable (subtracted from the demand) and once as evictable (added
    to the supply). On the repro state — free list empty, cached LRU
    holding exactly the request's prefix pages — the double count admits
    the request and its first fresh allocation dies with the mid-step
    pool-exhausted RuntimeError."""
    def fill_and_park(pool):
        p1 = np.arange(8, dtype=np.int32)            # 2 full pages
        r1 = Request(prompt=p1, max_new_tokens=2, request_id=0)
        pool.note_submit(0, p1)
        assert pool.admissible(r1)
        pool.admit(0, r1)
        ops = kv_pool.StepOps()
        pool.prepare(0, 0, 8, ops)
        pool.note_filled(0, p1, 8)
        pool.release(0, ops)                          # both pages park
        return Request(prompt=np.arange(12, dtype=np.int32),
                       max_new_tokens=2, request_id=1)

    # Repro sizing: capacity 2, so after the fill free=[] and cached =
    # exactly the 12-token extension's 2 prefix-hit pages. It still
    # needs 1 fresh page -> must NOT be admissible (revived pages are
    # not evictable), where the double count said 0 + 2 >= 1.
    pool = kv_pool.PagePool(3, 4, 3, 1, poison=False)
    r2 = fill_and_park(pool)
    assert not pool.free and len(pool.cached) == 2
    pool.note_submit(1, r2.prompt)
    assert not pool.admissible(r2)
    pool.forget_submit(1)
    pool.check()

    # Control: one genuinely free page makes the same request admissible
    # and the fresh allocation succeeds.
    pool = kv_pool.PagePool(4, 4, 3, 1, poison=False)
    r2 = fill_and_park(pool)
    assert len(pool.free) == 1
    pool.note_submit(1, r2.prompt)
    assert pool.admissible(r2)
    assert pool.admit(1 - 1, r2) == 8                 # both prefix pages hit
    ops = kv_pool.StepOps()
    pool.prepare(0, 8, 4, ops)                        # the fresh page
    pool.check()


def test_pool_rollback_frees_wholly_stale_pages_keeps_boundary():
    """Speculative rollback (DESIGN.md §14): after a draft round writes
    positions [committed, touched), every logical page WHOLLY beyond the
    committed content unmaps and frees; the partially-committed boundary
    page stays (its stale tail carries future pos stamps the causal mask
    excludes)."""
    pool = kv_pool.PagePool(6, 4, 4, 1, poison=False)
    prompt = np.arange(6, dtype=np.int32)
    pool.admit(0, Request(prompt=prompt, max_new_tokens=8, request_id=0))
    ops = kv_pool.StepOps()
    pool.prepare(0, 0, 6, ops)                        # pages 0, 1 mapped
    pool.note_filled(0, prompt, 6)
    ops = kv_pool.StepOps()
    pool.prepare(0, 6, 4, ops)                        # round: pos 6..9
    boundary = int(pool.table[0, 1])
    fresh = int(pool.table[0, 2])
    assert fresh >= 0
    free_before = len(pool.free)
    ops = kv_pool.StepOps()
    pool.rollback(0, 7, 10, ops)   # verify committed only pos 6 (+bonus)
    assert int(pool.table[0, 2]) == -1                # wholly stale: freed
    assert int(pool.table[0, 1]) == boundary          # boundary stays
    assert len(pool.free) == free_before + 1 and fresh in pool.free
    pool.check()
    # committed == touched is a no-op; a wrapped round is rejected (the
    # engine's draft guard makes it unreachable).
    table_before = pool.table.copy()
    pool.rollback(0, 7, 7, ops)
    np.testing.assert_array_equal(pool.table, table_before)
    with pytest.raises(AssertionError):
        pool.rollback(0, 4, 17, ops)                  # 17 > 4 * 4: wrap


@pytest.mark.parametrize("seed", range(8))
def test_pool_random_program_invariants(seed):
    _run_pool_program(seed, num_pages=int(5 + seed), page_size=4,
                      pages_per_seq=4, max_batch=3, n_ops=40)


# ===================================================== paged device cache =
def _toy_paged(kv_bits, num_pages=6, ps=4, npg=4, b=2, hk=2, d=8):
    cache = kv_pool.init_paged_cache(num_pages, ps, npg, b, hk, d,
                                     kv_bits=kv_bits, dtype=jnp.float32)
    # slot 0 -> pages 1, 2; slot 1 -> page 3 (allocator-style mapping)
    table = np.full((b, npg), -1, np.int32)
    table[0, :2] = [1, 2]
    table[1, 0] = 3
    cache["page_table"] = jnp.asarray(table)
    return cache


@pytest.mark.parametrize("kv_bits", [None, 4])
def test_paged_write_gather_roundtrip(kv_bits):
    """Tokens written through the page table must come back from
    ``gather_paged`` at ring position pos with everything else empty;
    masked lanes (pos < 0) and unmapped logical pages must drop."""
    cache = _toy_paged(kv_bits)
    kv = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 2, 8))
    pos = jnp.asarray([[2, 3, 4], [0, -1, 1]], jnp.int32)
    cache = kv_pool.update_paged_cache(cache, kv, -kv, pos)
    k, v, kpos = kv_pool.gather_paged(cache)
    want0 = np.full((16,), -1); want0[[2, 3, 4]] = [2, 3, 4]
    want1 = np.full((16,), -1); want1[[0, 1]] = [0, 1]
    np.testing.assert_array_equal(np.asarray(kpos),
                                  np.stack([want0, want1]))
    tol = dict(rtol=0, atol=0) if kv_bits is None else \
        dict(rtol=0.2, atol=0.1)
    np.testing.assert_allclose(np.asarray(k[0, 2]), np.asarray(kv[0, 0]),
                               **tol)
    np.testing.assert_allclose(np.asarray(v[1, 1]), np.asarray(-kv[1, 2]),
                               **tol)
    assert np.asarray(k[0, 5:]).sum() == 0   # beyond writes: empty
    # The masked lane of row 1 never landed anywhere.
    assert np.asarray(kpos[1]).tolist().count(1) == 1


def test_paged_write_never_touches_unmapped_pool_pages():
    """A write to a position whose logical page is unmapped (table -1)
    must drop — not land on the null page or any pool page."""
    cache = _toy_paged(4)
    before = {n: np.asarray(v) for n, v in cache.items()}
    kv = jax.random.normal(jax.random.PRNGKey(1), (2, 1, 2, 8))
    pos = jnp.asarray([[9], [5]], jnp.int32)  # logical pages 2, 1: unmapped
    cache = kv_pool.update_paged_cache(cache, kv, -kv, pos)
    for name in cache:
        np.testing.assert_array_equal(np.asarray(cache[name]),
                                      before[name], err_msg=name)


def test_apply_step_ops_copy_then_wipe_and_stacked_table():
    """COW copies carry payload + pos; wipes clear payload and stamp pos
    -1; stacked [L, ...] caches broadcast one table across layers."""
    cache = _toy_paged(4)
    kv = jax.random.normal(jax.random.PRNGKey(2), (2, 4, 2, 8))
    cache = kv_pool.update_paged_cache(
        cache, kv, -kv, jnp.asarray([[0, 1, 2, 3], [0, 1, 2, 3]],
                                    jnp.int32))
    stacked = {n: (v if n == "page_table"
                   else jnp.stack([v, v])) for n, v in cache.items()}
    stacked["page_table"] = jnp.stack([cache["page_table"]] * 2)
    table = np.array(cache["page_table"])
    table[0, 0] = 4                          # remap after COW 1 -> 4
    out = kv_pool.apply_step_ops(stacked, table, np.asarray([2], np.int32),
                                 np.asarray([1], np.int32),
                                 np.asarray([4], np.int32))
    for l in range(2):
        np.testing.assert_array_equal(np.asarray(out["page_table"][l]),
                                      table)
        np.testing.assert_array_equal(np.asarray(out["k_codes"][l, 4]),
                                      np.asarray(cache["k_codes"][1]))
        np.testing.assert_array_equal(np.asarray(out["pos"][l, 4]),
                                      np.asarray(cache["pos"][1]))
        assert (np.asarray(out["pos"][l, 2]) == -1).all()
        assert (np.asarray(out["k_codes"][l, 2]) == 0).all()


def test_poisoned_page_keeps_pos_and_nans_payload():
    """``apply_poison`` must keep the pos stamps (so a stale table
    reference passes the mask) while NaN-ing scales / 0xFF-ing codes —
    and attention through the stale table must go NaN, which is the
    whole point of SONIQ_KV_POISON=1."""
    cache = _toy_paged(4)
    kv = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 2, 8))
    cache = kv_pool.update_paged_cache(
        cache, kv, -kv, jnp.asarray([[0, 1, 2, 3], [0, 1, 2, 3]],
                                    jnp.int32))
    poisoned = kv_pool.apply_poison(cache, np.asarray([1], np.int32))
    np.testing.assert_array_equal(np.asarray(poisoned["pos"][1]),
                                  np.asarray(cache["pos"][1]))
    assert (np.asarray(poisoned["k_codes"][1]) == 0xFF).all()
    assert np.isnan(np.asarray(poisoned["k_scale"][1],
                               np.float32)).all()
    q = jax.random.normal(jax.random.PRNGKey(4), (2, 1, 2, 2, 8))
    q_pos = jnp.asarray([[3], [3]], jnp.int32)
    out = registry.get("xla_ref").qkv_attn_decode_paged(q, poisoned, q_pos)
    assert np.isnan(np.asarray(out[0])).any()   # slot 0 read page 1: trip
    assert np.isfinite(np.asarray(out[1])).all()  # slot 1 untouched


# ==================================================== paged backend op ====
def _filled_paged(kv_bits, seed=0):
    cache = _toy_paged(kv_bits, num_pages=7, ps=4, npg=4)
    table = np.full((2, 4), -1, np.int32)
    table[0] = [1, 2, 3, 4]                  # full logical ring
    table[1, :2] = [5, 6]
    cache["page_table"] = jnp.asarray(table)
    key = jax.random.PRNGKey(seed)
    for t in range(14):
        kv = jax.random.normal(jax.random.fold_in(key, t), (2, 1, 2, 8))
        pos = jnp.asarray([t, t if t < 7 else -1], jnp.int32)
        cache = kv_pool.update_paged_cache(cache, kv, -kv, pos)
    q = jax.random.normal(jax.random.fold_in(key, 99), (2, 3, 2, 2, 8))
    q_pos = jnp.asarray([[12, -1, 13], [5, 6, -1]], jnp.int32)
    return cache, q, q_pos


@pytest.mark.parametrize("kv_bits", [None, 4])
@pytest.mark.parametrize("window", [None, 6])
def test_paged_op_backend_parity(kv_bits, window):
    """xla_ref (gather_paged + dense oracle) and pallas_interpret (the
    online-softmax paged kernel) must agree to fp32 tolerance, wrapped
    rings / masked lanes / windows included — and the q4 leg must
    actually dispatch the kernel (trace-time counter)."""
    cache, q, q_pos = _filled_paged(kv_bits)
    ref = registry.get("xla_ref").qkv_attn_decode_paged(q, cache, q_pos,
                                                        window=window)
    before = pallas_backend.kernel_call_counts()["qkv_attn_decode_paged"]
    got = registry.get("pallas_interpret").qkv_attn_decode_paged(
        q, cache, q_pos, window=window)
    dispatched = pallas_backend.kernel_call_counts()[
        "qkv_attn_decode_paged"] - before
    assert dispatched == (1 if kv_bits == 4 else 0)  # fp falls back
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert np.isfinite(np.asarray(got)).all()


def test_paged_oracle_matches_ring_oracle_on_same_content():
    """Acceptance cross-check: identical K/V content read through the
    paged table and through the ring cache must attend identically (the
    layouts are bit-compatible per token)."""
    hk, d, t = 2, 8, 8
    key = jax.random.PRNGKey(7)
    kv = jax.random.normal(key, (1, t, hk, d))
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    ring = kv_quant.update_qkv_cache(
        kv_quant.init_qkv_cache(1, t, hk, d), kv, -kv, pos)
    paged = kv_pool.init_paged_cache(3, 4, 2, 1, hk, d, kv_bits=4)
    paged["page_table"] = jnp.asarray([[1, 2]], jnp.int32)
    paged = kv_pool.update_paged_cache(paged, kv, -kv, pos)
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, hk, 2, d))
    q_pos = jnp.asarray([[t - 1]], jnp.int32)
    ref = registry.get("xla_ref")
    np.testing.assert_array_equal(
        np.asarray(ref.qkv_attn_decode(q, ring, q_pos)),
        np.asarray(ref.qkv_attn_decode_paged(q, paged, q_pos)))


def test_paged_op_supports_probe():
    assert registry.get("pallas_interpret").supports(
        "qkv_attn_decode_paged")
    assert not registry.get("xla_ref").supports("qkv_attn_decode_paged")


# ======================================================= engine parity ====
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


def _ecfg(**kw):
    base = dict(max_batch=2, cache_len=32, prefill_chunk=4)
    base.update(kw)
    return engine.EngineConfig(**base)


def _mixed_requests(rng, lens=(3, 9, 5, 2), news=(4, 7, 3, 6)):
    return [Request(prompt=rng.integers(1, 100, (l,)), max_new_tokens=n,
                    seed=i) for i, (l, n) in enumerate(zip(lens, news))]


@pytest.mark.parametrize("kv_bits", [None, 4])
def test_paged_engine_token_parity(served, kv_bits):
    """THE acceptance pin: the paged DecodeEngine's greedy tokens are
    identical to the ring DecodeEngine's AND the LockstepEngine's on the
    same packed checkpoint, at q4 and fp alike."""
    cfg, params = served
    prompts = np.random.default_rng(3).integers(
        1, 100, (3, 7)).astype(np.int32)
    ring = engine.DecodeEngine(params, cfg, _ecfg(kv_bits=kv_bits))
    paged = engine.DecodeEngine(params, cfg, _ecfg(
        kv_bits=kv_bits, kv_layout="paged", page_size=4))
    lock = engine.LockstepEngine(params, cfg, _ecfg(kv_bits=kv_bits))
    out_p = paged.generate(prompts, 6)
    np.testing.assert_array_equal(out_p, ring.generate(prompts, 6))
    np.testing.assert_array_equal(out_p, lock.generate(prompts, 6))
    paged.pool.check()


def test_paged_cross_backend_token_identity_and_dispatch(served):
    """xla_ref and pallas_interpret agree token-for-token through the
    paged engine at q4, and the Pallas leg served every layer through the
    paged kernel — not the fallback (trace-time counter: one dispatch per
    stacked scan body per compiled step shape)."""
    cfg, params = served
    outs = {}
    for name in ("xla_ref", "pallas_interpret"):
        eng = engine.DecodeEngine(params, cfg, _ecfg(
            backend=name, kv_bits=4, kv_layout="paged", page_size=4))
        before = pallas_backend.kernel_call_counts()["qkv_attn_decode_paged"]
        got = {c.request_id: c.tokens
               for c in eng.serve(_mixed_requests(np.random.default_rng(1)))}
        outs[name] = {k - min(got): v for k, v in got.items()}
        dispatched = pallas_backend.kernel_call_counts()[
            "qkv_attn_decode_paged"] - before
        assert dispatched == (0 if name == "xla_ref" else 2), dispatched
        eng.pool.check()
    assert set(outs["xla_ref"]) == set(outs["pallas_interpret"])
    for k in outs["xla_ref"]:
        np.testing.assert_array_equal(outs["xla_ref"][k],
                                      outs["pallas_interpret"][k])


def test_paged_engine_prefix_sharing_and_occupancy(served):
    """Shared-system-prompt traffic: the prefix map must actually hit, the
    tokens must stay parity with the ring engine, and peak resident
    payload bytes must stay <= 0.5x the ring layout's reserved bytes (the
    occupancy win: the ring pays for configured capacity up front, the
    pool pays per token actually cached)."""
    cfg, params = served
    rng = np.random.default_rng(5)
    system = rng.integers(1, 100, (9,)).astype(np.int32)
    reqs = [Request(prompt=np.concatenate(
                [system, rng.integers(1, 100, (2 + i,)).astype(np.int32)]),
            max_new_tokens=4 + i, seed=i) for i in range(4)]

    def run(ecfg):
        eng = engine.DecodeEngine(params, cfg, ecfg)
        outs = {c.request_id: c.tokens for c in eng.serve(
            [dataclasses.replace(r) for r in reqs])}
        return eng, {k - min(outs): v for k, v in outs.items()}

    ring_eng, ring_out = run(_ecfg(kv_bits=4, cache_len=64))
    paged_eng, paged_out = run(_ecfg(kv_bits=4, cache_len=64,
                                     kv_layout="paged", page_size=4))
    for k in ring_out:
        np.testing.assert_array_equal(ring_out[k], paged_out[k])
    paged_eng.pool.check()
    stats = paged_eng.kv_stats()
    assert stats["prefix_hits"] > 0
    ring_reserved = kv_quant.cache_payload_bytes(ring_eng.cache)
    assert stats["reserved_payload_bytes"] == ring_reserved
    assert stats["peak_resident_payload_bytes"] <= 0.5 * ring_reserved, \
        stats


def test_paged_submit_rejects_impossible_prompt(served):
    """Satellite regression: a prompt whose page demand can never fit the
    pool raises at submit() — it must not sit in the queue deadlocking
    admission forever."""
    cfg, params = served
    eng = engine.DecodeEngine(params, cfg, _ecfg(
        kv_bits=4, kv_layout="paged", page_size=4, num_pages=5))
    with pytest.raises(ValueError, match="KV pages"):
        eng.submit(Request(prompt=np.arange(1, 40, dtype=np.int32),
                           max_new_tokens=2))
    # An admissible request still flows end to end afterwards.
    outs = list(eng.serve([Request(prompt=np.asarray([1, 2, 3], np.int32),
                                   max_new_tokens=3)]))
    assert len(outs) == 1 and outs[0].new_tokens.size == 3


def test_paged_page_pressure_queues_without_deadlock(served):
    """A pool too small for full concurrency must gate admission (requests
    wait for pages) and still drain with ring-identical tokens."""
    cfg, params = served
    reqs = _mixed_requests(np.random.default_rng(2))
    ring = engine.DecodeEngine(params, cfg, _ecfg(kv_bits=4))
    want = {c.request_id: c.tokens for c in ring.serve(
        [dataclasses.replace(r) for r in reqs])}
    tight = engine.DecodeEngine(params, cfg, _ecfg(
        kv_bits=4, kv_layout="paged", page_size=4, num_pages=11))
    got = {c.request_id: c.tokens for c in tight.serve(
        [dataclasses.replace(r) for r in reqs])}
    assert len(got) == len(want)
    for k in want:
        np.testing.assert_array_equal(want[k], got[k])
    tight.pool.check()


def test_paged_decode_wrap_at_full_residency(served):
    """Regression: a single long-running request on a max_batch=1 engine
    with the default pool sizing must wrap its logical ring in place
    (unregistering the canonical prompt page) with ring-identical
    tokens — not crash prefill/decode with pool exhaustion — and the
    completion-path note_filled must see the TRUE fed count, so the
    wrap-overwritten page never re-registers as prompt content."""
    cfg, params = served
    prompt = np.arange(1, 7, dtype=np.int32)
    ring = engine.DecodeEngine(params, cfg, _ecfg(
        max_batch=1, cache_len=8, kv_bits=4))
    want = ring.generate(prompt[None], 8)[0]
    paged = engine.DecodeEngine(params, cfg, _ecfg(
        max_batch=1, cache_len=8, kv_bits=4, kv_layout="paged",
        page_size=4))
    first = list(paged.serve([Request(prompt=prompt, max_new_tokens=8)]))
    np.testing.assert_array_equal(first[0].tokens, want)
    paged.pool.check()
    # Page 0 was wrapped through by decode growth: it must have left the
    # prefix map (in-place fallback) and must NOT have been re-registered
    # at completion (the n_fed=len(prompt) bug registered decode garbage
    # under the prompt's hash there).
    assert paged.pool.page_hashes(prompt)[0] not in paged.pool.prefix_map
    # A repeat of the same prompt re-prefills instead of mapping a stale
    # page, so its tokens stay ring-identical too.
    second = list(paged.serve([Request(prompt=prompt, max_new_tokens=8)]))
    np.testing.assert_array_equal(second[0].tokens, want)
    paged.pool.check()


def test_paged_same_step_admission_does_not_overcommit(served):
    """Regression: two prompts whose joint page demand exceeds a tight
    pool must not be co-admitted in one step — the second waits for
    pages (head-of-line) and both finish with ring-identical tokens."""
    cfg, params = served
    rng = np.random.default_rng(6)
    reqs = [Request(prompt=rng.integers(1, 100, (12,)).astype(np.int32),
                    max_new_tokens=4, seed=i) for i in range(2)]
    ring = engine.DecodeEngine(params, cfg, _ecfg(kv_bits=4))
    want = {c.request_id: c.tokens for c in ring.serve(
        [dataclasses.replace(r) for r in reqs])}
    tight = engine.DecodeEngine(params, cfg, _ecfg(
        kv_bits=4, kv_layout="paged", page_size=4, num_pages=5))
    got = {c.request_id: c.tokens for c in tight.serve(
        [dataclasses.replace(r) for r in reqs])}
    assert len(got) == len(want)
    for k in want:
        np.testing.assert_array_equal(want[k], got[k])
    tight.pool.check()


def test_paged_poison_mode_is_parity_preserving(served):
    """SONIQ_KV_POISON=1 must not change tokens for correct code — freed
    pages are poisoned but allocation wipes before reuse."""
    cfg, params = served
    prompts = np.random.default_rng(4).integers(
        1, 100, (3, 5)).astype(np.int32)
    plain = engine.DecodeEngine(params, cfg, _ecfg(
        kv_bits=4, kv_layout="paged", page_size=4))
    out = plain.generate(prompts, 5)
    poisoned = engine.DecodeEngine(params, cfg, _ecfg(
        kv_bits=4, kv_layout="paged", page_size=4))
    poisoned.pool.poison = True
    np.testing.assert_array_equal(out, poisoned.generate(prompts, 5))
    poisoned.pool.check()


def test_pool_poison_env_knob(monkeypatch):
    monkeypatch.setenv(kv_pool.POISON_ENV, "1")
    assert kv_pool.PagePool(4, 4, 4, 1).poison
    monkeypatch.setenv(kv_pool.POISON_ENV, "0")
    assert not kv_pool.PagePool(4, 4, 4, 1).poison


def test_paged_geometry_validation(served):
    cfg, params = served
    with pytest.raises(ValueError, match="page_size"):
        engine.DecodeEngine(params, cfg, _ecfg(
            kv_layout="paged", page_size=5))
    with pytest.raises(ValueError, match="kv_layout"):
        engine.DecodeEngine(params, cfg, _ecfg(kv_layout="blocked"))
    with pytest.raises(ValueError, match="ring"):
        engine.LockstepEngine(params, cfg, _ecfg(
            kv_layout="paged", page_size=4)).generate(
                np.ones((1, 3), np.int32), 2)


# --------------------------------------------- hypothesis properties ----
# Guarded import (not a module-level importorskip, which would skip every
# test above too): CI installs hypothesis and fails fast if the property
# tests would silently vanish from the run.
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                          # pragma: no cover
    HAVE_HYPOTHESIS = False


if not HAVE_HYPOTHESIS:
    def test_property_tests_require_hypothesis():
        pytest.skip("hypothesis not installed — property tests skipped")
else:
    @settings(max_examples=60, deadline=None)
    @given(st.integers(0, 2 ** 16), st.integers(3, 12),
           st.sampled_from([2, 4]), st.integers(2, 5), st.integers(1, 3),
           st.integers(5, 50))
    def test_pool_program_property(seed, num_pages, page_size,
                                   pages_per_seq, max_batch, n_ops):
        """Allocator invariants under arbitrary admit/feed/release
        interleavings: the free list / cached LRU / mapped set partition
        the pool (no double-free, no lost pages), refcounts equal table
        references, and shared-prefix pages are never in-place write
        targets — checked after every single operation."""
        _run_pool_program(seed, num_pages, page_size, pages_per_seq,
                          max_batch, n_ops)
