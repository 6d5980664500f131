"""The readers of the program's own spans, on hand-made span records,
mirrored span events and device intervals.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import program_spans as ps  # noqa: E402
from repro.obs import SpanRecord  # noqa: E402

trace = ps.trace


def _metric(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py")


def _rec(span_id, name, start, dur, parent=None, **attrs):
    return SpanRecord(span_id=span_id, name=name, cat="t", round=None,
                      node=None, parent=parent, depth=0, wall_start=start,
                      wall_dur=dur, attrs=attrs)


def _ctx(spans=(), rounds=2, ops=(), cell="cell-under-test"):
    red = trace.Reduced(devices=["/device:TPU:0"] if ops else [])
    red.ops = [trace.Op(s, e, "op", "m", "/device:TPU:0") for s, e in ops]
    return SimpleNamespace(spans=list(spans), rounds=rounds, trace=red,
                           cell=SimpleNamespace(name=cell))


# -- wall readers over span records ------------------------------------------

def test_per_round_wall_readers():
    spans = [_rec(0, "fel.prep", 0.0, 0.004),
             _rec(1, "fel.prep", 1.0, 0.002),
             _rec(2, "device.wait", 0.1, 0.010, on="W"),
             _rec(3, "device.wait", 0.3, 0.006, on="eval"),
             _rec(4, "serialize", 0.2, 0.001, d2h_bytes=400),
             _rec(5, "crypto.sign", 0.5, 0.003),
             _rec(6, "crypto.sign", 0.6, 0.001)]
    ctx = _ctx(spans)
    assert _metric("fel_host_ms").read(ctx) == pytest.approx(3.0)
    assert _metric("device_wait_ms").read(ctx) == pytest.approx(8.0)
    assert _metric("serialize_ms").read(ctx) == pytest.approx(0.5)
    assert _metric("crypto_sign_ms").read(ctx) == pytest.approx(2.0)


def test_hash_ms_counts_nested_hashes_once():
    # [0, 4) ms holds a nested [1, 2) ms; [10, 11) ms stands alone
    spans = [_rec(0, "crypto.sha256", 0.000, 0.004, bytes=10),
             _rec(1, "crypto.sha256", 0.001, 0.001, parent=0, bytes=3),
             _rec(2, "crypto.sha256", 0.010, 0.001, bytes=5)]
    assert _metric("hash_ms").read(_ctx(spans)) == pytest.approx(2.5)


def test_host_transfer_mb_sums_both_directions():
    spans = [_rec(0, "serialize", 0, 1, d2h_bytes=3_000_000),
             _rec(1, "device.put", 0, 1, h2d_bytes=31_000_000, on="test"),
             _rec(2, "fel.prep", 0, 1, h2d_bytes=1_000_000),
             _rec(3, "crypto.sha256", 0, 1, bytes=99_000_000)]
    assert _metric("host_transfer_mb").read(_ctx(spans)) == pytest.approx(
        17.5)


def test_wall_readers_are_silent_without_their_spans():
    ctx = _ctx([_rec(0, "crypto.verify_batch", 0, 1)])
    for name in ("fel_host_ms", "device_wait_ms", "serialize_ms", "hash_ms",
                 "crypto_sign_ms", "host_transfer_mb"):
        assert _metric(name).read(ctx) is None, name
    assert _metric("fel_host_ms").read(
        _ctx([_rec(0, "fel.prep", 0, 1)], rounds=0)) is None


# -- the mirrored events against device-busy time ------------------------------

def _round_events():
    E = ps.Event
    # one round [0, 100): a phase [10, 60) holding a sign [12, 20) and a
    # wait [30, 50); a sha256 [40, 55) that overlaps the wait's end; the
    # evaluate [70, 90) holding a put [72, 74)
    return [E(0, 100, "round", 0), E(10, 60, "phase:commit_reveal", 1),
            E(12, 20, "crypto.sign", 2), E(30, 50, "device.wait", 3),
            E(40, 55, "crypto.sha256", 4), E(70, 90, "evaluate", 5),
            E(72, 74, "device.put", 6), E(200, 210, "crypto.sign", 7)]


def test_work_spans_exclude_only_the_groups():
    assert not ps.is_work("round") and not ps.is_work("consensus")
    assert not ps.is_work("phase:tally")
    assert not ps.is_work("hcds:commit_stage")
    for name in ("hcds.receive", "device.wait", "evaluate", "fel",
                 "crypto.sha256"):
        assert ps.is_work(name), name


def test_untraced_ns_with_nested_and_overlapping_spans():
    evs = _round_events()
    # work covers [12, 20) ∪ [30, 55) ∪ [70, 90); the rest of [0, 100) is
    # 12 + 10 + 15 + 10 = 47 ns, of which the device ran [5, 15) ∩ gaps
    # = [5, 12) → 7 ns busy; the event outside every round is ignored
    assert ps.untraced_ns(evs, []) == 47
    assert ps.untraced_ns(evs, trace.merge([(5, 15), (32, 48)])) == 40


def test_idle_by_span_takes_the_innermost_span():
    evs = _round_events()
    got = ps.idle_by_span(evs, trace.merge([(30, 45)]))
    # the sha256 opens inside the wait: [30, 40) is the wait's (all busy),
    # [40, 55) the sha256's ([40, 45) busy), [55, 60) the phase's again
    assert got == {"round": 10 + 10 + 10,
                   "phase:commit_reveal": 2 + 10 + 5,
                   "crypto.sign": 8, "device.wait": 0,
                   "crypto.sha256": 10, "evaluate": 18, "device.put": 2}
    assert sum(got.values()) == 100 - 15


def test_host_untraced_ms_reads_the_mirrored_events(monkeypatch):
    ctx = _ctx(ops=[(5, 15)], rounds=1)
    monkeypatch.setattr(ps, "events", lambda c: _round_events())
    assert _metric("host_untraced_ms").read(ctx) == pytest.approx(40e-6)
    monkeypatch.setattr(ps, "events", lambda c: [])
    assert _metric("host_untraced_ms").read(ctx) is None


def test_events_without_a_trace_folder_are_empty():
    assert ps.events(_ctx(cell="no-such-cell-anywhere")) == []


def test_events_read_from_a_recorded_xplane(tmp_path):
    """Spans a TraceRecorder opens under a profiler trace come back from
    the xplane with their names and ids, in the order they opened."""
    import jax

    from repro.obs import TraceRecorder
    rec = TraceRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        rec.open_span("round", round=0)
        with rec.span("fel.prep"):
            pass
        rec.close_span()
    finally:
        jax.profiler.stop_trace()
    evs = ps.load(trace.find_xplane(tmp_path))
    assert [(e.name, e.span_id) for e in evs] == [("round", 0),
                                                   ("fel.prep", 1)]
    assert evs[0].start <= evs[1].start <= evs[1].end <= evs[0].end
