"""The check that decides ``correct``, at a size a CPU test run can hold.

For every cell of ``BENCHMARK.json``, at the cell's ``test_size``, a run
is driven end to end (set-up, a short window, the chain checks and the
comparison with the reference) without the harness's look for a TPU:
once sound, and once with the timed path broken underneath for each
fault of ``bench/faults.py``. The cell's own limits must pass the sound run and
fail every broken one; the control (the reference one precision step
below the configuration, in the program's place) must fail them too.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import compare  # noqa: E402
import harness  # noqa: E402
from faults import FAULTS  # noqa: E402

CONFIGS = {w["name"]: w["config"] for w in
           harness.load_json(BENCH.parent / "BENCHMARK.json")["workloads"]}
CELLS = sorted(CONFIGS)


def at_test_size(name: str) -> "harness.Cell":
    w = harness.load_json(BENCH / "workloads" / f"{name}.json")
    cfg = harness.load_json(BENCH / "configs" / f"{CONFIGS[name]}.json")
    small = w["test_size"]
    cfg.update(small.get("config", {}))
    for key in ("traffic", "deployment"):
        w[key].update(small.get(key, {}))
    w["check_rounds"] = small["check_rounds"]
    return harness.Cell(name, workload=w, config=cfg)


@pytest.fixture(scope="module", params=CELLS)
def cell(request):
    return at_test_size(request.param)


def run(cell) -> dict:
    return harness.run(cell, 20261016, 0.3, False, time.perf_counter(),
                       require_tpu=False)


def test_sound_run_is_correct(cell):
    result = run(cell)
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_caught(cell, fault, monkeypatch):
    FAULTS[fault](cell, monkeypatch)
    assert not run(cell)["correct"]


def test_control_one_precision_below_is_caught(cell):
    seeds = harness.Seeds.of(7)
    servers, test = cell.data(seeds)
    params = cell.weights(seeds)
    rounds = cell.w["check_rounds"]
    want = harness.make_reference(cell, seeds, servers, test).run(
        params, rounds, cell.layout)
    got = harness.make_reference(
        cell, seeds, servers, test,
        prec=cell.cfg["precision"]["control"]).run(params, rounds,
                                                   cell.layout)
    values = compare.numbers(got, want)
    judged = compare.judge(values, {k: v for k, v in cell.w["limits"].items()
                                    if k in values})
    assert not all(c["ok"] for c in judged.values()), values
