"""Adversary simulation (paper §3.2 + §7.4) — a thin wrapper over the
``repro.sim`` scenario registry:

1. Model plagiarism — a BCFL node copies a peer's FEL model; HCDS
   rejects the duplicate reveal every round.
2. Bribery voting — colluding nodes vote a fixed target (TA) or randomly
   (RA); BTSV down-weights them and the honest argmax keeps winning.

Run:  PYTHONPATH=src python examples/attack_simulation.py

The CI-enforced versions of these assertions live in
``tests/test_attacks.py``; the scenario registry and the report schema
are documented in ``README.md`` ("The ``repro.sim`` scenario
registry"). Add your own attacks by registering a ``sim.Scenario`` with
adversaries from ``repro.sim.adversary``.
"""

from repro import compile_cache, sim

compile_cache.enable()

for name in ("plagiarist", "bribery_targeted", "bribery_random"):
    sc = sim.get_scenario(name)
    print(f"=== {name} ===\n    {sc.description}")
    report = sim.run_scenario(name, seed=0)
    print(f"    {report.summary()}")
    assert report.liveness and report.safety_violations == 0

    if name == "plagiarist":
        plag = sc.adversaries[0].node_id
        reasons = {r.round: r.rejected.get(plag) for r in report.rounds}
        print(f"    plagiarist node {plag} rejected: {reasons}")
        assert all(v == "plagiarized-model" for v in reasons.values())
        assert report.honest_leader_rate == 1.0
    else:
        # the bribed votes never displaced the honest similarity argmax
        assert report.argmax_leader_rate == 1.0

print("\nHCDS + BTSV suppressed all three attacks ✓")
