"""Benchmark entry point.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the TPU this process finds (it
exits nonzero, printing no result, anywhere else), and prints the result
as one JSON object on the last line of standard output; the numbers that
decided ``correct`` are the last lines of standard error.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
# libtpu otherwise writes its logs under /tmp, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    import harness
    cell = harness.Cell(args.workload)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START)
    gc.collect()
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
