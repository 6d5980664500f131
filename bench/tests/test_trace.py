"""The trace reduction, on hand-made intervals and on a trace recorded on
a TPU v5e (``data/mlp_tiny.xplane.pb``, made by ``make_fixture.py``).

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import importlib.util
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
FIXTURE = BENCH / "tests" / "data" / "mlp_tiny.xplane.pb"


def _load(name):
    spec = importlib.util.spec_from_file_location(f"bench_{name}_under_test",
                                                  BENCH / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


trace = _load("trace")


def test_merge_and_overlap():
    merged = trace.merge([(5, 9), (0, 3), (2, 4), (9, 10), (20, 25)])
    assert merged == [(0, 4), (5, 10), (20, 25)]
    assert trace.overlap(merged, 3, 22) == 1 + 5 + 2
    assert trace.overlap(merged, 10, 20) == 0
    assert trace.overlap(merged, -5, 100) == 4 + 5 + 5


def _reduced():
    r = trace.Reduced(devices=["/device:TPU:0"])
    r.ops = [trace.Op(10, 20, "fusion.1", "jit_round_fn(1)", "/device:TPU:0"),
             trace.Op(15, 30, "fusion.2", "jit_round_fn(1)", "/device:TPU:0"),
             trace.Op(60, 70, "custom-call", "jit_model_evaluation(2)",
                      "/device:TPU:0")]
    r.annotations = [(0, 100, "bench.round"),
                     (35, 55, "bench.phase.commit_reveal"),
                     (58, 80, "bench.phase.model_evaluation")]
    return r


def test_busy_idle_modules_and_host_only_time():
    r = _reduced()
    assert r.window_seconds() == pytest.approx(100e-9)
    assert r.busy_seconds() == pytest.approx(30e-9)
    assert r.module_ns("jit_round_fn") == 20
    assert r.module_ns("jit_model_evaluation") == 10
    # commit_reveal: 20 ns, all idle; model_evaluation: 22 ns, 10 busy
    assert r.host_only_ns("bench.phase.") == 20 + 12
    gaps = r.idle_gaps()
    assert gaps == [(0, 10), (30, 60), (70, 100)]
    b = r.breakdown()
    name, seconds = b["device_ops"][0]
    assert name == "jit_round_fn(1)/fusion.2"
    assert seconds == pytest.approx(15e-9)
    labels = dict(b["idle_gaps"])
    assert labels["bench.phase.commit_reveal"] == pytest.approx(30e-9)


@pytest.fixture(scope="module")
def recorded():
    if not FIXTURE.exists():
        pytest.skip("no recorded trace; run make_fixture.py on the chip")
    return trace.load(FIXTURE)


def test_recorded_trace_has_device_ops_inside_rounds(recorded):
    assert recorded.devices and recorded.devices[0].startswith("/device:TPU")
    rounds = [a for a in recorded.annotations if a[2] == "bench.round"]
    assert len(rounds) == 3
    s, e = recorded.window()
    inside = [o for o in recorded.ops if s <= o.start and o.end <= e]
    assert inside, "device ops and host annotations share no clock"
    assert 0 < recorded.busy_seconds() < recorded.window_seconds()


def test_recorded_trace_names_the_round_program_and_me(recorded):
    assert recorded.module_ns("jit_round_fn") > 0
    assert recorded.module_ns("jit_model_evaluation") > 0
    phases = {n for _, _, n in recorded.annotations
              if n.startswith("bench.phase.")}
    assert {"bench.phase.commit_reveal", "bench.phase.model_evaluation",
            "bench.phase.vote_collection", "bench.phase.tally",
            "bench.phase.block_mint"} <= phases
    busy = recorded.busy_seconds()
    assert sum(v for _, v in recorded.breakdown(top=10**6)["device_ops"]) \
        >= busy * (1 - 1e-9)
