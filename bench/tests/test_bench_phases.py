"""The engine's spans and step records as the benchmark reads them
(``benchlib/phases.py``): idle time put down to the innermost ``engine.*``
span on hand-built events, adding up to ``trace.py``'s idle time; the five
metric files that read the spans and records, on a hand-built ``Run``,
and on one that lacks them (a program without the spans); and a traced
run end to end on the CPU, in which the engine's records agree with the
session's view of every step."""
import json
import types
from pathlib import Path

import pytest

from benchlib import cell as cell_lib
from benchlib import phases, trace
from benchlib.report import Run
from test_bench_run import _own_compile_cache, tiny_cell  # noqa: F401

MS = 1e6                                # ns per ms
NEW = ("step_host_ms", "device_idle_host_ms", "device_idle_sync_ms",
       "prefill_row_use", "kv_live_share")


def _planes(engine_spans=True):
    """Two steps on one device, in ms. Device ops: [0, 15), [30, 50),
    [60, 70), [90, 105), [115, 130), [140, 160)."""
    ops = [(a * MS, (b - a) * MS) for a, b in
           ((0, 15), (30, 50), (60, 70), (90, 105), (115, 130), (140, 160))]
    host = [("prefill", 10, 90), ("decode", 120, 30)]
    if engine_spans:
        host += [("engine.step", 10, 90), ("engine.admit", 12, 8),
                 ("engine.dispatch", 25, 15), ("engine.sync", 40, 40),
                 ("engine.advance", 85, 10),
                 ("engine.step", 120, 30), ("engine.sync", 125, 20)]
    return [("/device:TPU:0", [("XLA Ops", [("fusion.1", s, d)
                                            for s, d in ops])]),
            ("/host:CPU", [("python", [(n, s * MS, d * MS)
                                       for n, s, d in host])])]


def test_idle_goes_to_the_innermost_engine_span(monkeypatch):
    monkeypatch.setattr(trace, "SLACK_NS", 0.0)
    planes = _planes()
    s = trace.reduce_events(planes)
    assert (s.lo, s.hi) == (10 * MS, 150 * MS)
    p = phases.reduce_events(planes, s.lo, s.hi)
    # Gaps: [15, 30) over admit, the step's own time and dispatch; [50,
    # 60) in sync; [70, 90) over sync, the step's own time and advance;
    # [105, 115) between steps; [130, 140) in the second step's sync.
    want = {"engine.admit": 5, "engine.step": 10, "engine.dispatch": 5,
            "engine.sync": 30, "engine.advance": 5, phases.OUTSIDE: 10}
    assert p.idle == pytest.approx({k: v * MS for k, v in want.items()})
    assert p.steps == 2 and p.devices == 1
    assert sum(p.idle.values()) == pytest.approx(
        sum(ns for _, ns in s.idle))
    assert p.per_step_ms(phases.HOST_PHASES) == pytest.approx(7.5)
    assert p.per_step_ms((phases.SYNC,)) == pytest.approx(15.0)


def test_pieces_label_nested_and_overlapping_spans():
    spans = [("engine.step", 0, 10), ("engine.plan", 2, 4),
             ("engine.sync", 6, 10)]
    assert phases.pieces(spans) == [
        ("engine.step", 0, 2), ("engine.plan", 2, 4), ("engine.step", 4, 6),
        ("engine.sync", 6, 10)]
    assert phases.pieces([]) == []


def test_recorded_trace_without_engine_spans_is_all_outside():
    planes = json.loads((Path(__file__).parent / "data" /
                         "v5e_trace.json").read_text())
    s = trace.reduce_events(planes)
    p = phases.reduce_events(planes, s.lo, s.hi)
    assert set(p.idle) == {phases.OUTSIDE} and p.steps == 0
    assert p.idle[phases.OUTSIDE] == pytest.approx(
        sum(ns for _, ns in s.idle))
    assert p.per_step_ms(phases.HOST_PHASES) is None


def _record(t0, t1, kind, sync=0.0, fed=0, rows=0, live=0, room=1000):
    from repro.serve.spans import StepRecord
    return StepRecord(t0, t1, kind, {"sync": sync, "dispatch": 0.5},
                      fed=fed, rows=rows, live_kv=live, reserved_kv=room)


def _run(tmp_path, monkeypatch, *, program=True):
    """A window of [0, 100) s with four engine steps in it, the third
    idle and the fourth under the profiler, and one after it."""
    monkeypatch.setattr(trace, "SLACK_NS", 0.0)
    planes = _planes(engine_spans=program)
    monkeypatch.setattr(phases, "load_planes", lambda path: planes)
    records = [_record(10, 20, "prefill", 4, fed=40, rows=512, live=100),
               _record(30, 36, "decode", 1, fed=16, rows=16, live=200),
               _record(40, 41, "idle", live=0),
               _record(50, 60, "prefill", 2, fed=24, rows=512, live=300),
               _record(200, 210, "decode", 1, fed=16, rows=16, live=900)]
    steps = [types.SimpleNamespace(t0=r.t0 - 0.1, t1=r.t1 + 0.1,
                                   traced=r.t0 == 50) for r in records]
    eng = types.SimpleNamespace(stats=types.SimpleNamespace(steps=records)) \
        if program else types.SimpleNamespace()
    session = types.SimpleNamespace(
        eng=eng, steps=steps,
        tracer=types.SimpleNamespace(dir=tmp_path / str(program)))
    return Run(cell=None, session=session, window=[], t_start=0.0,
               seconds=100.0, setup_s=0.0, late=[],
               trace=trace.reduce_events(planes))


def _read(name, run):
    return cell_lib.load_module("metrics", name).read(run)


def test_metric_files_read_spans_and_records(tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch)
    # Untraced steps that ran a program: (10 - 4) and (6 - 1) s.
    assert _read("step_host_ms", run) == pytest.approx(5500.0)
    assert _read("device_idle_host_ms", run) == pytest.approx(7.5)
    assert _read("device_idle_sync_ms", run) == pytest.approx(15.0)
    assert _read("prefill_row_use", run) == pytest.approx(
        100.0 * (40 + 24) / (2 * 512))
    assert _read("kv_live_share", run) == pytest.approx(
        100.0 * (100 + 200 + 0 + 300) / 4000)


def test_metric_files_read_nothing_without_the_program_side(
        tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch, program=False)
    assert [_read(n, run) for n in NEW] == [None] * len(NEW)
    run.trace = None
    assert [_read(n, run) for n in NEW] == [None] * len(NEW)


def test_traced_run_reads_the_engine_records(tmp_path, monkeypatch):
    """A traced run of the cell at the test size on the CPU: the record
    the engine kept of each session step names the program the session
    saw it feed, and the readers of the records give numbers (the device
    idle metrics need a TPU plane, so they read nothing here)."""
    import time

    import run as bench_run
    from benchlib import session as session_lib
    sessions = []

    class Kept(session_lib.Session):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            sessions.append(self)
    monkeypatch.setattr(session_lib, "Session", Kept)
    res = bench_run.run_cell(tiny_cell("h2o-danube-1.8b.chat"),
                             2 ** 31 + 91, 2.0, True, backend="xla_ref",
                             t_process=time.perf_counter(), out_dir=tmp_path,
                             engine_hook=lambda eng: sessions.append(eng))
    assert res["correct"], res["checked"]
    assert res["_notes"]["kind_mismatch"] == 0
    got = res["metrics"]
    assert got["step_host_ms"]["value"] > 0
    assert 0 < got["prefill_row_use"]["value"] <= 100
    assert 0 < got["kv_live_share"]["value"] <= 100
    assert "device_idle_host_ms" not in got
    eng, sess = sessions
    records = list(eng.stats.steps)
    for st in sess.steps:
        inside = [r for r in records if st.t0 <= r.t0 and r.t1 <= st.t1]
        assert len(inside) == 1
        kind = inside[0].kind
        assert kind == st.kind or (kind == "idle" and not st.fed)
