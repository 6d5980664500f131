"""The readings that the limits of ``correct`` are set from.

    python3 bench/control.py --workload <cell> --program 12 --control 3 \
        --faults 3 --seed 1000 [--against reference,default] [--out f.json]

In one process, for consecutive seeds from ``--seed``:

* ``program``: the cell's checked rounds through the timed path (set-up
  only, no window) against the reference: the lower readings;
* ``control``: the reference computed one precision step below what the
  configuration states, put in the program's place: the upper readings;
* each fault of ``bench/faults.py`` (``state_unchanged``, ``half_batch``,
  ``similarity_altered``, ``vote_altered``): the program again with that
  fault planted underneath, on ``--faults`` seeds each.

Every reading is taken against each reference precision of ``--against``
(``bench/reference.py``; the configuration's own by default). Prints one
line per run and, per precision, the largest program reading and the
smallest control and fault readings of each number; ``--out`` writes them
all.
"""

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1000)
    p.add_argument("--program", type=int, default=12)
    p.add_argument("--control", type=int, default=3)
    p.add_argument("--faults", type=int, default=3)
    p.add_argument("--against", default="",
                   help="reference precisions, comma-separated")
    p.add_argument("--out")
    p.add_argument("--allow-cpu", action="store_true",
                   help="run on the CPU (a rehearsal: no reading counts)")
    args = p.parse_args(argv)

    import pytest

    import compare
    import faults
    import harness
    from repro import compile_cache

    cell = harness.Cell(args.workload)
    device = harness.device_info(cell.chips, not args.allow_cpu)
    compile_cache.enable()
    against = ([a for a in args.against.split(",") if a]
               or [cell.cfg["precision"]["reference"]])
    rounds = cell.w["check_rounds"]
    readings = {"device": device, "against": against, "runs": []}

    def program_run(seeds):
        rt, probe, servers, test = harness.setup(cell, seeds, False)
        del rt
        gc.collect()
        return probe.traj, servers, test

    def record(kind, seed, got, wants, t0):
        for prec, want in wants.items():
            row = {"kind": kind, "against": prec, "seed": seed,
                   **compare.numbers(got, want)}
            readings["runs"].append(row)
            print(json.dumps({**row, "seconds": round(time.perf_counter()
                                                      - t0, 3)}), flush=True)

    plan = (["program"] * args.program + ["control"] * args.control
            + [f for f in sorted(faults.FAULTS) for _ in range(args.faults)])
    for i, kind in enumerate(plan):
        seed = args.seed + i
        seeds = harness.Seeds.of(seed)
        t0 = time.perf_counter()
        if kind == "program":
            got, servers, test = program_run(seeds)
        elif kind in faults.FAULTS:
            with pytest.MonkeyPatch.context() as mp:
                faults.FAULTS[kind](cell, mp)
                got, servers, test = program_run(seeds)
        else:
            servers, test = cell.data(seeds)
            got = harness.make_reference(
                cell, seeds, servers, test,
                prec=cell.cfg["precision"]["control"]).run(
                    cell.weights(seeds), rounds, cell.layout)
        wants = {prec: harness.make_reference(cell, seeds, servers, test,
                                              prec=prec).run(
            cell.weights(seeds), rounds, cell.layout) for prec in against}
        record(kind, seed, got, wants, t0)
    summary = {}
    for row in readings["runs"]:
        pick = max if row["kind"] == "program" else min
        key = f"{row['kind']}@{row['against']}"
        have = summary.setdefault(key, {})
        for k, v in row.items():
            if k not in ("kind", "against", "seed"):
                have[k] = pick(have[k], v) if k in have else v
    readings["summary"] = summary
    for key, values in summary.items():
        print(json.dumps({"summary": key, **values}), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(readings, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
