"""`repro.obs` — unit tests for the tracer itself: dual-clock span
nesting, the metrics registry, security-event attribution, exporter
schemas, the summarize/convert CLI, and the equivalence pin showing the
default NullRecorder changes no round outputs (tracing observes the
protocol, it never perturbs it).
"""

from __future__ import annotations

import json

import pytest

from repro import api, obs
from repro.obs.metrics import summarize_values
from repro.obs.profile import (critical_paths, events_to_trace,
                               format_summary, phase_percentiles)


def _span(rec, name):
    return next(s for s in rec.spans if s.name == name)


# ---------------------------------------------------------------------------
# spans: nesting, dual clocks, unwind
# ---------------------------------------------------------------------------

def test_span_nesting_and_dual_clocks():
    rec = obs.TraceRecorder("t")
    rec.open_span("outer", cat="x", round=3, sim_now=100.0)
    rec.open_span("inner", sim_now=110.0, detail="yes")
    assert rec.depth() == 2
    rec.close_span(sim_now=140.0)
    rec.close_span(sim_now=200.0, extra=1)
    assert rec.depth() == 0

    outer, inner = _span(rec, "outer"), _span(rec, "inner")
    # parentage and depth reflect the open/close stack
    assert inner.parent == outer.span_id and outer.parent is None
    assert (outer.depth, inner.depth) == (0, 1)
    # sim clock: explicit start/end, exact durations
    assert (inner.sim_start, inner.sim_end, inner.sim_dur) == (110.0, 140.0,
                                                               30.0)
    assert outer.sim_dur == 100.0
    # wall clock: monotonic and nested
    assert inner.wall_start >= outer.wall_start
    assert inner.wall_dur <= outer.wall_dur
    # attrs merge open-time and close-time keys
    assert inner.attrs == {"detail": "yes"}
    assert outer.attrs == {"extra": 1} and outer.round == 3


def test_span_sim_clock_from_env_object():
    class _Net:
        now = 42.0

    class _Env:
        network = _Net()

    env = _Env()
    rec = obs.TraceRecorder()
    rec.open_span("s", sim_env=env)
    env.network.now = 55.0
    rec.close_span()                 # end read deferred to close time
    s = _span(rec, "s")
    assert (s.sim_start, s.sim_end, s.sim_dur) == (42.0, 55.0, 13.0)


def test_span_context_manager_records_errors():
    rec = obs.TraceRecorder()
    with pytest.raises(ValueError):
        with rec.span("boom", sim_now=1.0):
            raise ValueError("x")
    assert _span(rec, "boom").error == "ValueError"
    with rec.span("fine"):
        pass
    assert _span(rec, "fine").error is None


def test_unwind_closes_orphans_and_tolerates_unmatched_close():
    rec = obs.TraceRecorder()
    rec.open_span("round")
    rec.open_span("phase:a")
    rec.open_span("net:x")
    rec.unwind(1, error="QuorumNotReached")   # a phase raised mid-flight
    assert rec.depth() == 1
    assert {s.name: s.error for s in rec.spans} == {
        "net:x": "QuorumNotReached", "phase:a": "QuorumNotReached"}
    rec.close_span()
    rec.close_span()                 # unmatched: swallowed, not raised
    assert rec.depth() == 0 and len(rec.spans) == 3


# ---------------------------------------------------------------------------
# events: ordering and security attribution
# ---------------------------------------------------------------------------

def test_events_get_dense_sequence_numbers():
    rec = obs.TraceRecorder()
    rec.event("net_delivery", round=0, node=2, sim_ms=10.0)
    rec.event("wal_append", node=1)
    assert [e.seq for e in rec.events] == [0, 1]
    assert rec.events[0].name == "net_delivery"
    assert rec.events[0].attrs == {}


def test_security_events_require_node_attribution():
    rec = obs.TraceRecorder()
    for name in sorted(obs.SECURITY_EVENTS):
        with pytest.raises(ValueError, match="attributed"):
            rec.event(name, round=0)
        rec.event(name, round=0, node=4)     # attributed: fine
    assert all(e.is_security for e in rec.events)
    # non-security events never need a node
    rec.event("net_exchange", round=0)
    assert not rec.events[-1].is_security


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_metrics_registry_roundtrip():
    rec = obs.TraceRecorder()
    rec.counter("c.calls")
    rec.counter("c.calls", 2)
    rec.gauge("g.depth", 7.0)
    for v in [1.0, 2.0, 3.0, 4.0]:
        rec.observe("h.ms", v)
    snap = rec.metrics_snapshot()
    assert snap["counters"] == {"c.calls": 3}
    assert snap["gauges"] == {"g.depth": 7.0}
    h = snap["histograms"]["h.ms"]
    assert (h["count"], h["sum"], h["max"]) == (4, 10.0, 4.0)
    assert h["p50"] in (2.0, 3.0) and h["p99"] == 4.0


def test_summarize_values_nearest_rank():
    s = summarize_values([5.0, 1.0, 3.0])
    assert (s["count"], s["p50"], s["max"]) == (3, 3.0, 5.0)
    empty = summarize_values([])
    assert empty["count"] == 0 and empty["max"] == 0.0


# ---------------------------------------------------------------------------
# the NullRecorder default: zero-cost, zero state
# ---------------------------------------------------------------------------

def test_null_recorder_is_inert():
    rec = obs.NullRecorder()
    assert not rec.enabled
    cm = rec.span("anything", round=1)
    assert cm is rec.span("else")        # one shared no-op CM
    with cm:
        pass
    rec.open_span("x")
    rec.event("envelope_rejected")       # not even validation runs
    rec.counter("c")
    rec.unwind(0)
    rec.close_span()
    assert rec.depth() == 0 and rec.metrics_snapshot() == {}


def test_recorder_scoping():
    assert isinstance(obs.get_recorder(), obs.NullRecorder)
    rec = obs.TraceRecorder()
    with obs.use_recorder(rec):
        assert obs.get_recorder() is rec
        inner = obs.TraceRecorder()
        with obs.use_recorder(inner):
            assert obs.get_recorder() is inner
        assert obs.get_recorder() is rec
    assert isinstance(obs.get_recorder(), obs.NullRecorder)


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def _tiny_recorder():
    rec = obs.TraceRecorder("tiny")
    rec.open_span("round", cat="runtime", round=0, sim_now=0.0)
    rec.open_span("consensus", cat="consensus", round=0, sim_now=0.0)
    rec.open_span("phase:commit_reveal", cat="consensus", round=0,
                  sim_now=0.0)
    rec.close_span(sim_now=20.0)
    rec.open_span("phase:block_mint", cat="consensus", round=0, sim_now=20.0)
    rec.close_span(sim_now=30.0)
    rec.close_span(sim_now=30.0)
    rec.close_span(sim_now=30.0)
    rec.event("net_delivery", round=0, node=1, sim_ms=5.0, attempt=0)
    return rec


def test_chrome_trace_schema():
    trace = obs.chrome_trace([("tiny", _tiny_recorder())])
    events = trace["traceEvents"]
    assert trace["displayTimeUnit"] == "ms"
    phs = [e["ph"] for e in events]
    assert phs.count("X") == 4 and phs.count("i") == 1 and "M" in phs
    xs = {e["name"]: e for e in events if e["ph"] == "X"}
    rnd = xs["round"]
    assert rnd["ts"] == 0 and rnd["dur"] >= 0
    assert rnd["args"]["sim_dur_ms"] == 30.0
    # parent links survive the export, so profilers can rebuild the tree
    cons = xs["consensus"]
    assert cons["args"]["parent"] == rnd["args"]["span_id"]
    inst = next(e for e in events if e["ph"] == "i")
    assert inst["s"] == "t" and inst["args"]["node"] == 1
    json.dumps(trace)                    # JSON-clean without default=


def test_events_jsonl_is_deterministic_and_wall_free():
    lines = obs.events_jsonl([("tiny", _tiny_recorder())])
    assert len(lines) == 1
    row = json.loads(lines[0])
    assert row == {"scenario": "tiny", "seq": 0, "event": "net_delivery",
                   "round": 0, "node": 1, "sim_ms": 5.0,
                   "attrs": {"attempt": 0}}
    # no wall-clock field can leak into the replay-pinned log
    assert "wall" not in lines[0]


def test_profile_summary_and_critical_paths():
    trace = obs.chrome_trace([("tiny", _tiny_recorder())])
    pct = phase_percentiles(trace, clock="sim")
    assert pct["commit_reveal"]["p50"] == 20.0
    paths = critical_paths(trace, clock="sim")
    assert len(paths) == 1 and paths[0]["total_ms"] == 30.0
    parts = {p["name"]: p["ms"] for p in paths[0]["breakdown"]}
    # the consensus span is drilled through to its phase children
    assert parts == {"phase:commit_reveal": 20.0, "phase:block_mint": 10.0}
    text = format_summary(trace, clock="sim")
    assert "phase:commit_reveal" in text and "round 0" in text


def test_cli_summarize_and_convert(tmp_path, capsys):
    from repro.obs.__main__ import main
    rec = _tiny_recorder()
    trace_path = tmp_path / "trace.json"
    events_path = tmp_path / "events.jsonl"
    obs.write_chrome_trace(str(trace_path), [("tiny", rec)])
    obs.write_events_jsonl(str(events_path), [("tiny", rec)])

    assert main(["summarize", str(trace_path), "--clock", "sim"]) == 0
    out = capsys.readouterr().out
    assert "sim clock" in out and "phase:commit_reveal" in out

    out_path = tmp_path / "converted.json"
    assert main(["convert", str(events_path), "-o", str(out_path)]) == 0
    converted = json.loads(out_path.read_text())
    inst = [e for e in converted["traceEvents"] if e["ph"] == "i"]
    assert len(inst) == 1 and inst[0]["ts"] == 5000   # sim_ms -> µs


def test_events_to_trace_matches_chrome_trace_instants(tmp_path):
    p = tmp_path / "e.jsonl"
    obs.write_events_jsonl(str(p), [("tiny", _tiny_recorder())])
    trace = events_to_trace(str(p))
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "i"}
    assert names == {"net_delivery"}


# ---------------------------------------------------------------------------
# the pin: tracing observes the protocol, it never changes it
# ---------------------------------------------------------------------------

def _small_run():
    return api.run_bhfl(model="mlp", n_nodes=3, clients_per_node=2,
                        fel_iterations=1, rounds=2,
                        data=api.make_mnist_like(n_train=300, n_test=60))


def test_noop_recorder_changes_no_round_outputs():
    """Identical protocol outputs with tracing off (NullRecorder default)
    and on (TraceRecorder) — the recorder holds zero protocol state."""
    with obs.use_recorder(obs.NullRecorder()):
        off = _small_run()
    with obs.use_recorder(obs.TraceRecorder("pin")) as rec:
        on = _small_run()

    def fingerprint(run):
        return ([(m.round, m.leader_id, float(m.test_accuracy),
                  float(m.test_loss)) for m in run.history],
                [b.global_model_digest
                 for b in run.runtime.consensus.ledgers[0].blocks])

    assert fingerprint(off) == fingerprint(on)
    # and the traced run really did record the work it watched
    assert off.obs is None and on.obs is not None
    assert len([s for s in rec.spans if s.name == "round"]) == 2
    assert on.obs["counters"].get("recovery.wal_appends", 0) > 0


# ---------------------------------------------------------------------------
# spans inside the round: attrs at close, transfers, the device's edges
# ---------------------------------------------------------------------------

def test_span_cm_set_adds_attrs_and_transfers_are_counted():
    rec = obs.TraceRecorder()
    with rec.span("device.put", on="test_set") as put:
        put.set(h2d_bytes=100)
    with rec.span("serialize", d2h_bytes=7):
        pass
    rec.open_span("device.get", on="gw")
    rec.close_span(d2h_bytes=5)
    assert _span(rec, "device.put").attrs == {"on": "test_set",
                                              "h2d_bytes": 100}
    counters = rec.metrics_snapshot()["counters"]
    assert counters["transfer.h2d_bytes"] == 100
    assert counters["transfer.d2h_bytes"] == 12
    with obs.NullRecorder().span("device.put") as put:
        put.set(h2d_bytes=1)         # the disabled path takes it too


def test_spanned_decorator_records_only_while_enabled():
    @obs.spanned("work.item", cat="t", kind="k")
    def add_one(x):
        return x + 1

    assert add_one(1) == 2
    rec = obs.TraceRecorder()
    with obs.use_recorder(rec):
        assert add_one(x=2) == 3
    assert [(s.name, s.cat, s.attrs) for s in rec.spans] == [
        ("work.item", "t", {"kind": "k"})]
    assert add_one.__name__ == "add_one"


def test_device_wait_is_a_span_only_while_tracing():
    import jax.numpy as jnp
    import numpy as np
    x = jnp.ones(4) * 2
    tree = {"a": x, "b": np.ones(2)}
    obs.device_wait("x", tree)        # disabled: nothing to record into
    rec = obs.TraceRecorder()
    with obs.use_recorder(rec):
        obs.device_wait("x", tree)
    assert [(s.name, s.attrs) for s in rec.spans] == [("device.wait",
                                                       {"on": "x"})]
    # only device arrays count as bytes a pull would move
    assert obs.device_nbytes(tree) == 16


def test_crypto_spans_carry_sizes_and_count_hashed_bytes():
    from repro.core import crypto
    kp = crypto.ECDSAKeyPair.generate(seed=b"k")
    rec = obs.TraceRecorder()
    with obs.use_recorder(rec):
        d = crypto.sha256_digest(b"ab", b"cde")
        crypto.dsign(d, kp.private_key)
    assert _span(rec, "crypto.sha256").attrs == {"bytes": 5}
    assert _span(rec, "crypto.sign").cat == "crypto"
    assert rec.metrics_snapshot()["counters"]["crypto.sha256_bytes"] == 5
    # the traced and the untraced path give the same digest and tag
    assert d == crypto.sha256_digest(b"abcde")


def _xplane_span_events(folder):
    """(start, end, name, stats) of the host events that carry a
    ``span_id``, in CLOCK_REALTIME ns, from the one xplane in ``folder``."""
    from pathlib import Path
    from jax.profiler import ProfileData
    path = sorted(Path(folder).rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    start = next(dict(p.stats)["profile_start_time"] for p in pd.planes
                 if p.name == "Task Environment")
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "span_id" in stats:
                    s = start + int(e.start_ns)
                    out.append((s, s + int(e.duration_ns), e.name, stats))
    return out


def test_mirrored_annotations_lie_inside_their_spans(tmp_path):
    """A TraceRecorder mirrors each span as a profiler annotation of the
    same name that carries its span_id (and round): on the profiler's
    clock (CLOCK_REALTIME), each lies inside its span's wall window."""
    import time

    import jax

    from repro.core import crypto
    anchor_ns, anchor_s = time.time_ns(), time.perf_counter()
    rec = obs.TraceRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        crypto.sha256_digest(b"untraced")   # NullRecorder: mirrors nothing
        with obs.use_recorder(rec):
            rec.open_span("round", cat="runtime", round=4)
            with rec.span("fel.prep", cat="fel"):
                time.sleep(0.002)
            for _ in range(2):
                crypto.sha256_digest(bytes(1 << 20))
            time.sleep(0.002)
            rec.close_span()
    finally:
        jax.profiler.stop_trace()
    events = _xplane_span_events(tmp_path)
    by_id = {s.span_id: s for s in rec.spans}
    assert sorted(st["span_id"] for *_, st in events) == sorted(by_id)
    tol = 50_000                          # ns: the two clocks' read skew
    for start, end, name, stats in events:
        span = by_id[stats["span_id"]]
        assert name == span.name
        assert stats.get("round") == span.round
        lo = anchor_ns + (span.wall_start - anchor_s) * 1e9
        hi = lo + span.wall_dur * 1e9
        assert lo - tol <= start <= end <= hi + tol, (name, start - lo,
                                                      hi - end)
    assert _span(rec, "round").round == 4


def _ancestors(rec, span):
    by_id = {s.span_id: s for s in rec.spans}
    out = []
    while span.parent is not None:
        span = by_id[span.parent]
        out.append(span.name)
    return out


@pytest.fixture(scope="module")
def traced_round():
    """One batched BHFL round (3 servers) under a TraceRecorder."""
    data = api.make_mnist_like(n_train=300, n_test=60)
    rec = obs.TraceRecorder("round")
    with obs.use_recorder(rec):
        run = api.run_bhfl(model="mlp", n_nodes=3, clients_per_node=2,
                           fel_iterations=1, rounds=1, engine="batched",
                           data=data)
    return rec, run, data


def _named(rec, name, **attrs):
    return [s for s in rec.spans if s.name == name
            and all(s.attrs.get(k) == v for k, v in attrs.items())]


def test_round_work_spans_nest_under_their_phases_and_count_bytes(
        traced_round):
    """One batched BHFL round under a TraceRecorder: each new span sits
    under the phase or runtime span that contains the work, and the bytes
    each copy moves are on its span and in the run's counters."""
    import numpy as np
    rec, run, data = traced_round
    assert run.runtime.engine == "batched"

    def named(name, **attrs):
        return _named(rec, name, **attrs)

    parent_of = {s.span_id: s.name for s in rec.spans}
    (wait_w,) = named("device.wait", on="W")
    assert parent_of[wait_w.parent] == "phase:commit_reveal"
    (wait_me,) = named("device.wait", on="me")
    assert parent_of[wait_me.parent] == "phase:vote_collection"
    (wait_eval,) = named("device.wait", on="eval")
    assert parent_of[wait_eval.parent] == "evaluate"
    (prep,) = named("fel.prep")
    assert parent_of[prep.parent] == "fel"
    signs = {p for s in named("crypto.sign") for p in _ancestors(rec, s)}
    assert {"phase:commit_reveal", "phase:vote_collection",
            "phase:block_mint"} <= signs
    for name, phase in (("btsv.tally", "phase:tally"),
                        ("ledger.append", "phase:block_mint"),
                        ("block.build", "phase:block_mint"),
                        ("me.dispatch", "phase:model_evaluation"),
                        ("me.predictions", "phase:vote_collection"),
                        ("serialize", "phase:commit_reveal")):
        assert named(name) and all(phase in _ancestors(rec, s)
                                   for s in named(name)), name
    (gw,) = named("device.get", on="gw")
    assert parent_of[gw.parent] == "block.build"

    n, d = 3, run.runtime._global_flat.size
    rows = named("serialize")
    assert len(rows) == n
    assert sum(s.attrs["d2h_bytes"] for s in rows) == n * d * 4
    assert gw.attrs["d2h_bytes"] == d * 4
    test_bytes = np.asarray(data[1].x).nbytes + np.asarray(data[1].y).nbytes
    (put,) = named("device.put", on="test_set")
    assert put.attrs["h2d_bytes"] == test_bytes
    eng = run.runtime._engine
    plan = 4 * eng.fel_iterations * eng.n_clusters * eng.n_clients_padded
    assert prep.attrs["h2d_bytes"] == plan * (
        eng.steps_per_iteration * eng.batch_pad + 1)
    (btsv_put,) = named("device.put", on="btsv")
    (btsv_get,) = named("device.get", on="btsv")
    counters = run.obs["counters"]
    assert counters["transfer.d2h_bytes"] == ((n + 1) * d * 4
                                              + btsv_get.attrs["d2h_bytes"])
    assert counters["transfer.h2d_bytes"] == (test_bytes
                                              + prep.attrs["h2d_bytes"]
                                              + btsv_put.attrs["h2d_bytes"])
    # SHA-256 reads each model three times (commitment, the sha256(w) the
    # WAL and the block share, the receivers' verification) and gw once;
    # the rest is nonces and envelope, vote and block digests
    from repro.core.serialization import serialize_pytree
    model_len = len(serialize_pytree(run.runtime.global_params))
    small = counters["crypto.sha256_bytes"] - (3 * n * model_len + d * 4)
    assert 0 <= small < 64 * 1024, small


def test_serialize_starts_every_row_pull_before_packing(traced_round):
    """CommitReveal serialises the N rows in one batch: every row's host
    copy is started first (``serialize.prefetch_bytes``), and each row
    keeps its own ``serialize`` span with its bytes, split by one
    ``serialize.pull`` child into the wait for the row and the packing."""
    import numpy as np

    from repro.core.serialization import serialize_pytrees
    rec, run, _ = traced_round
    n, d = 3, run.runtime._global_flat.size
    rows = _named(rec, "serialize")
    assert len(rows) == n
    pulls = _named(rec, "serialize.pull")
    assert sorted(p.parent for p in pulls) == sorted(s.span_id for s in rows)
    assert all("d2h_bytes" not in p.attrs for p in pulls)
    assert run.obs["counters"]["serialize.prefetch_bytes"] == n * d * 4

    host = obs.TraceRecorder("host")
    with obs.use_recorder(host):
        serialize_pytrees([{"w": np.ones(4, np.float32)}] * 2)
    counters = host.metrics_snapshot()["counters"]
    assert counters.get("serialize.prefetch_bytes", 0) == 0
    assert counters.get("transfer.d2h_bytes", 0) == 0


def test_btsv_tally_crosses_to_the_device_once_each_way(traced_round):
    """The tally uploads its votes and predictions in one transfer and
    pulls its whole result in one, both inside ``btsv.tally``; the block
    carries the pulled float32 weights and advotes exactly."""
    import numpy as np
    rec, run, _ = traced_round
    n = 3
    parent_of = {s.span_id: s.name for s in rec.spans}
    (put,) = _named(rec, "device.put", on="btsv")
    (get,) = _named(rec, "device.get", on="btsv")
    assert parent_of[put.parent] == "btsv.tally"
    assert parent_of[get.parent] == "btsv.tally"
    assert put.attrs["h2d_bytes"] == n * 4 + n * n * 4   # votes, P
    assert get.attrs["d2h_bytes"] == 4 + 4 * n * 4       # leader, 4 x (N,)
    consensus = run.runtime.consensus
    block = consensus.ledgers[0].blocks[-1]
    res = consensus.contract.result(block.round)
    assert block.vote_weights == {i: float(np.float32(w))
                                  for i, w in enumerate(res.weights)}
    assert block.advotes == {j: float(np.float32(a))
                             for j, a in enumerate(res.advotes)}
    assert block.leader_id == int(res.leader)


# ---------------------------------------------------------------------------
# the device programs' module names, which the benchmark reads by prefix
# ---------------------------------------------------------------------------

def test_device_module_names_are_stable():
    """``fel_device_ms``, ``fel_mfu`` and ``me_roofline`` find the round
    program and ME in the device trace by these names; a rename would turn
    them to null without a word."""
    import re

    import jax.numpy as jnp

    from repro.core.model_eval import model_evaluation
    from repro.fl.hfl_runtime import BHFLConfig, BHFLRuntime
    from repro.fl.hierarchy import build_hierarchy
    from repro.models.mlp import MLPConfig

    def module(lowered):
        return re.search(r"module @(\w+)", lowered.as_text()).group(1)

    train, _ = api.make_mnist_like(n_train=64, n_test=8)
    cfg = BHFLConfig(n_nodes=2, clients_per_node=2, fel_iterations=1,
                     mlp=MLPConfig(hidden=8), engine="batched")
    rt = BHFLRuntime(build_hierarchy(train, 2, 2, "iid"), cfg, None)
    eng = rt._engine
    idx, seeds = eng._prep(1)
    lowered = eng._round_fn.lower(rt._global_flat, idx, seeds, eng._data,
                                  eng._sizes_f, eng._bs_dev, eng._stepmask)
    assert module(lowered) == "jit_round_fn"
    W = jnp.ones((3, 16), jnp.float32)
    assert module(model_evaluation.lower(W, jnp.ones(3))) == \
        "jit_model_evaluation"
