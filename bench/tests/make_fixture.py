"""Records the small trace that ``test_trace.py`` reads.

    python3 bench/tests/make_fixture.py

Runs the mlp-paper-n8 cell at a tiny size (3 servers x 2 clients, 400
training rows) with ``--trace 1`` semantics on the chip this process
finds, copies the ``.xplane.pb`` to ``bench/tests/data/``, and prints the
planes, lines and op stats of the trace, for a reader who adapts the
reduction to another profiler version.
"""

import json
import shutil
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

DATA = BENCH / "tests" / "data"


def tiny_cell() -> "harness.Cell":
    w = harness.load_json(BENCH / "workloads" / "mlp-paper-n8.json")
    w["traffic"].update(n_train=400, n_test=100)
    w["deployment"].update(n_nodes=3, clients_per_node=2)
    w["check_rounds"], w["trace_rounds"] = 2, 3
    return harness.Cell("mlp-paper-n8", workload=w)


def main() -> int:
    result = harness.run(tiny_cell(), 7, 5.0, True, T0,
                         require_tpu="--allow-cpu" not in sys.argv)
    print(json.dumps(result))
    src = harness.load_module(BENCH / "trace.py").find_xplane(
        harness.OUT / "trace" / "mlp-paper-n8")
    DATA.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, DATA / "mlp_tiny.xplane.pb")
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(src))
    for plane in pd.planes:
        print("plane", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  line", line.name, len(events))
            for e in events[:2]:
                try:
                    stats = dict(e.stats)
                except Exception as exc:   # report, keep listing
                    stats = {"unreadable": repr(exc)}
                print("    event", e.name, e.start_ns, e.duration_ns,
                      sorted(stats)[:8])
    return 0


if __name__ == "__main__":
    sys.exit(main())
