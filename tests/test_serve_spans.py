"""Engine spans and step records (serve/spans.py): each step's record names
the program it dispatched, counts the tokens it fed against the rows the
program computed and the KV positions in use against those reserved, and
its phases' seconds fit inside the step's; ``kv_stats()`` matches a hand
count on the ring and the paged layout; ``spec_stats()`` reads the same
totals as before; and under a running profiler every ``engine.*`` span
lands on the host plane."""
import dataclasses
import glob

import jax
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core.qtypes import QuantConfig
from repro.models import lm
from repro.serve import engine, kv_pool, kv_quant, spans
from repro.serve.scheduler import Request

B, CLEN, CHUNK = 2, 32, 4
HK, HD, LAYERS = 2, 32, 2


def _tiny():
    return ArchConfig(
        name="t", family="dense", num_layers=LAYERS, d_model=64,
        num_heads=2, num_kv_heads=HK, d_ff=128, vocab_size=128,
        head_dim=HD, dtype="float32", param_dtype="float32", q_block=32,
        quant=QuantConfig(mode="qat"))


@pytest.fixture(scope="module")
def served():
    cfg = _tiny()
    params = jax.device_get(lm.init_params(jax.random.PRNGKey(0), cfg))
    return cfg, params


def _engine(served, **kw):
    cfg, params = served
    base = dict(max_batch=B, cache_len=CLEN, prefill_chunk=CHUNK)
    base.update(kw)
    return engine.DecodeEngine(params, cfg, engine.EngineConfig(**base))


def _watch(eng):
    """Per step: which program the engine dispatched and the widths it
    planned, seen from outside the engine."""
    seen = {"programs": [], "widths": []}
    for name in ("_prefill", "_decode"):
        fn = getattr(eng, name)

        def wrapped(*a, fn=fn, name=name):
            seen["programs"].append(name[1:])
            return fn(*a)
        setattr(eng, name, wrapped)
    plan = eng.sched.plan

    def planned(chunk):
        p = plan(chunk)
        seen["widths"].append(sum(len(t) for t in p.values()))
        return p
    eng.sched.plan = planned
    return seen


def _requests():
    # Two arrive at once (prefill, then decode steps); the third arrives
    # at step 12, after both have finished, so idle steps come between.
    rng = np.random.default_rng(0)
    return [Request(prompt=rng.integers(1, 100, (3,)), max_new_tokens=3),
            Request(prompt=rng.integers(1, 100, (9,)), max_new_tokens=3),
            Request(prompt=rng.integers(1, 100, (5,)), max_new_tokens=2,
                    arrival_step=12)]


@pytest.mark.parametrize("layout", [
    dict(), dict(kv_bits=4), dict(kv_bits=4, kv_layout="paged",
                                  page_size=4)])
def test_records_name_the_program_and_count_its_work(served, layout):
    eng = _engine(served, **layout)
    seen = _watch(eng)
    reqs = _requests()
    for r in reqs:
        eng.submit(r)
    n = 0
    while eng.sched.has_work():
        before = len(seen["programs"])
        eng.step()
        rec = eng.stats.steps[-1]
        ran = seen["programs"][before:]
        assert rec.kind == (ran[0] if ran else "idle"), (n, ran)
        assert len(ran) <= 1
        assert rec.fed == (seen["widths"][n] if ran else 0)
        assert rec.rows == {"prefill": B * CHUNK, "decode": B,
                            "idle": 0}[rec.kind]
        assert set(rec.phase_s) <= set(spans.PHASES)
        assert all(v >= 0 for v in rec.phase_s.values())
        assert sum(rec.phase_s.values()) <= rec.seconds
        assert rec.reserved_kv == B * CLEN
        assert 0 <= rec.live_kv <= rec.reserved_kv
        n += 1
    recs = list(eng.stats.steps)
    assert len(recs) == n
    kinds = [r.kind for r in recs]
    assert {"prefill", "decode", "idle"} <= set(kinds)
    assert kinds[0] == "prefill" and recs[0].admitted == (
        reqs[0].request_id, reqs[1].request_id)
    assert [i for r in recs for i in r.admitted] == [r.request_id
                                                     for r in reqs]
    assert sorted(i for r in recs for i in r.finished) == sorted(
        r.request_id for r in reqs)
    # First step: prompts of 3 and 9 tokens, chunk 4 -> 3 + 4 fed.
    assert recs[0].fed == 7 and recs[0].live_kv == 7
    for a, b in zip(recs, recs[1:]):
        assert a.t1 <= b.t0


def _per_position_bytes(kv_bits):
    if kv_bits == 4:        # 4-bit codes two to a byte + f16 scale, K and V
        return LAYERS * 2 * HK * (HD // 2 + 2)
    return LAYERS * 2 * HK * HD * 4                     # float32 k and v


@pytest.mark.parametrize("kv_bits", [None, 4])
@pytest.mark.parametrize("kv_layout", ["ring", "paged"])
def test_kv_stats_match_a_hand_count(served, kv_bits, kv_layout):
    layout = dict(kv_layout="paged", page_size=4) \
        if kv_layout == "paged" else {}
    eng = _engine(served, kv_bits=kv_bits, **layout)
    rng = np.random.default_rng(1)
    eng.submit(Request(prompt=rng.integers(1, 100, (3,)), max_new_tokens=6))
    eng.submit(Request(prompt=rng.integers(1, 100, (9,)), max_new_tokens=6))
    per_pos = _per_position_bytes(kv_bits)
    # Prompts of 3 and 9 at chunk 4: step 1 feeds 3 and 4, step 2 one
    # decode token and 4 more, step 3 one decode token and the last 1.
    for step, want in ((1, 3 + 4), (2, 4 + 8), (3, 5 + 9)):
        eng.step()
        st = eng.kv_stats()
        assert st["kv_layout"] == kv_layout
        assert st["position_payload_bytes"] == per_pos
        assert st["reserved_positions"] == B * CLEN
        assert st["reserved_payload_bytes"] == B * CLEN * per_pos
        assert st["live_positions"] == want, step
        assert st["live_payload_bytes"] == want * per_pos
    assert st["reserved_payload_bytes"] == \
        kv_quant.cache_payload_bytes(_engine(served, kv_bits=kv_bits)
                                     .init_cache(B))
    if kv_layout == "paged":
        per_page = kv_pool.paged_payload_bytes_per_page(eng.cache)
        assert st["page_payload_bytes"] == per_page == 4 * per_pos
        assert st["resident_payload_bytes"] == \
            st["resident_pages"] * per_page
        assert st["resident_pages"] == -(-5 // 4) + -(-9 // 4)


def test_live_kv_stops_at_the_ring_length(served):
    eng = _engine(served, max_batch=1, cache_len=16)
    eng.submit(Request(prompt=np.arange(1, 6, dtype=np.int32),
                       max_new_tokens=20))
    while eng.sched.has_work():
        eng.step()
        rec = eng.stats.steps[-1]
        if eng.sched.slots:
            fed = eng.sched.slots[0].n_fed
            assert eng.kv_stats()["live_positions"] == min(fed, 16)
        assert rec.live_kv <= rec.reserved_kv == 16
    assert max(r.live_kv for r in eng.stats.steps) == 16


def test_spec_stats_keep_their_counts(served):
    """``spec_stats()`` reads the engine's stats object and reports what
    it reported before: rounds are draft-slot rounds, drafted is k per
    round, accepted is the accepted drafts counted from outside."""
    k = 2
    eng = _engine(served, spec_tokens=k)
    counted = {"lanes": 0, "accepted": 0}
    draft, accept = eng._draft, eng._accept

    def drafting(p, c, t, pos, act):
        counted["lanes"] += int(np.sum(act))
        return draft(p, c, t, pos, act)

    def accepting(st, drafts, *a):
        out = accept(st, drafts, *a)
        if drafts:
            counted["accepted"] += len(out) - 1
        return out
    eng._draft, eng._accept = drafting, accepting
    rng = np.random.default_rng(2)
    for n in (3, 9, 5):
        eng.submit(Request(prompt=rng.integers(1, 100, (n,)),
                           max_new_tokens=7))
    list(eng.run())
    st = eng.spec_stats()
    assert set(st) == {"spec_tokens", "rounds", "drafted", "accepted",
                       "mean_accepted"}
    assert st["spec_tokens"] == k
    assert st["drafted"] == counted["lanes"] == k * st["rounds"] > 0
    assert st["accepted"] == counted["accepted"]
    assert st["mean_accepted"] == st["accepted"] / st["rounds"]
    kinds = {r.kind for r in eng.stats.steps}
    assert kinds <= {"spec", "idle"} and "spec" in kinds
    # A reset starts the totals and the records again.
    eng.reset()
    assert eng.spec_stats()["rounds"] == 0 and not eng.stats.steps


def test_records_are_bounded(monkeypatch):
    monkeypatch.setattr(spans, "MAX_RECORDS", 3)
    stats = spans.EngineStats()
    for _ in range(5):
        with stats.step() as rec:
            with stats.phase("plan"):
                pass
            with stats.phase("plan"):
                pass
        assert rec.t1 >= rec.t0 and rec.phase_s["plan"] >= 0
    assert len(stats.steps) == 3
    with pytest.raises(ValueError):
        with stats.step():
            raise ValueError("a failed step leaves no record")
    assert len(stats.steps) == 3 and stats.current is None


def test_spans_land_on_the_profilers_host_plane(served, tmp_path):
    from jax.profiler import ProfileData
    eng = _engine(served)
    reqs = _requests()
    reqs[2] = dataclasses.replace(reqs[2], arrival_step=4)
    for r in reqs:
        eng.submit(r)
    eng.step()                             # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        while eng.sched.has_work():
            eng.step()
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(sorted(glob.glob(
        str(tmp_path / "**" / "*.xplane.pb"), recursive=True))[-1])
    names, steps = set(), []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(spans.PREFIX):
                    names.add(e.name)
                if e.name == "engine.step":
                    steps.append((e.start_ns, dict(e.stats)["kind"]))
    assert names == {spans.PREFIX + p for p in ("step",) + spans.PHASES}
    kinds = [k for _, k in sorted(steps)]
    assert kinds == [r.kind for r in list(eng.stats.steps)[1:]]
    assert {"prefill", "decode"} <= set(kinds)
