"""`repro.analysis` — fixture tests: every rule fires on its known-bad
snippet, stays silent on the known-good twin, and `# noqa: RA###`
suppresses it; plus registry/baseline mechanics, the PR-5 arrival-order
regression fixture, a self-run asserting the analyzer's own code is
clean, and the repo-wide gate (`src tests` + baseline → zero findings).
"""

from __future__ import annotations

import json
import os
import textwrap

import pytest

from repro.analysis import (analyze_contexts, analyze_paths, analyze_source,
                            load_baseline, save_baseline)
from repro.analysis.baseline import BaselineError, apply_baseline
from repro.analysis.core import FileContext, file_scopes

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONSENSUS_PATH = "src/repro/core/tally_helper.py"
CRYPTO_PATH = "src/repro/core/crypto/helper.py"
NEUTRAL_PATH = "src/repro/launch/helper.py"


def codes(report):
    return [f.rule for f in report.findings]


def run(src, path=CONSENSUS_PATH):
    return analyze_source(textwrap.dedent(src), path=path)


# ---------------------------------------------------------------------------
# scoping
# ---------------------------------------------------------------------------

def test_file_scopes():
    assert "consensus" in file_scopes("src/repro/core/hcds.py")
    assert "consensus" in file_scopes("src/repro/blockchain/ledger.py")
    assert "consensus" in file_scopes("src/repro/sim/network.py")
    assert "consensus" not in file_scopes("src/repro/fl/client.py")
    assert "rng" in file_scopes("benchmarks/bench_hcds.py")
    assert "crypto" in file_scopes("src/repro/core/crypto/field.py")
    assert "crypto" in file_scopes("src/repro/core/envelope.py")
    assert "crypto" in file_scopes("src/repro/core/phases.py")
    assert "crypto" not in file_scopes("src/repro/core/btsv.py")
    assert "tests" in file_scopes("tests/test_hcds.py")
    assert "repro" in file_scopes("src/repro/core/hcds.py")
    assert "repro" not in file_scopes("benchmarks/bench_hcds.py")


# ---------------------------------------------------------------------------
# RA1xx — determinism
# ---------------------------------------------------------------------------

def test_ra101_fires_on_global_numpy_rng():
    bad = """
        import numpy as np
        def pick_round():
            return np.random.randint(1 << 30)
    """
    assert codes(run(bad)) == ["RA101"]


def test_ra101_fires_on_stdlib_random_module():
    bad = """
        import random
        def jitter():
            return random.random()
    """
    assert codes(run(bad)) == ["RA101"]


def test_ra101_good_seeded_generator_silent():
    good = """
        import numpy as np
        def pick_round(seed):
            rng = np.random.default_rng(seed)
            return int(rng.integers(1 << 30))
    """
    assert codes(run(good)) == []


def test_ra101_out_of_scope_module_silent():
    bad = """
        import numpy as np
        def pick():
            return np.random.randint(10)
    """
    assert codes(run(bad, path=NEUTRAL_PATH)) == []


def test_ra101_noqa_suppresses():
    bad = """
        import numpy as np
        def pick():
            return np.random.randint(10)  # noqa: RA101
    """
    report = run(bad)
    assert codes(report) == []
    assert [f.rule for f in report.suppressed] == ["RA101"]


def test_ra102_fires_on_wall_clock():
    bad = """
        import time
        def deadline():
            return time.time() + 60.0
    """
    assert codes(run(bad)) == ["RA102"]


def test_ra102_perf_counter_silent():
    good = """
        import time
        def stopwatch():
            return time.perf_counter()
    """
    assert codes(run(good)) == []


def test_ra103_fires_on_pr5_arrival_order_pattern():
    # the PR-5 bug class: precedence assigned by iterating an unordered
    # collection of committers — two nodes disagree on who owns a model
    bad = """
        def finalize_commit_order(commits):
            order = {}
            for nid in {c.node_id for c in commits}:
                order[nid] = len(order)
            return order
    """
    assert codes(run(bad)) == ["RA103"]


def test_ra103_sorted_iteration_silent():
    good = """
        def finalize_commit_order(commits):
            order = {}
            for nid in sorted({c.node_id for c in commits}):
                order[nid] = len(order)
            return order
    """
    assert codes(run(good)) == []


def test_ra103_tracks_local_set_variables():
    bad = """
        def tally_order(votes):
            voters = set(votes)
            return [v for v in voters]
    """
    assert codes(run(bad)) == ["RA103"]


def test_ra103_membership_tests_silent():
    good = """
        def tally(votes, quorum_ids):
            members = set(quorum_ids)
            return [v for v in votes if v in members]
    """
    assert codes(run(good)) == []


# ---------------------------------------------------------------------------
# RA15x — observability hooks must be read-only
# ---------------------------------------------------------------------------

OBS_PATH = "src/repro/obs/helper.py"


def test_ra151_fires_on_mutating_registered_hook():
    bad = """
        def evict_on_phase(phase, ctx):
            ctx.rejected[0] = "nope"

        def install(consensus):
            consensus.add_phase_hook("*", evict_on_phase, when="after")
    """
    assert codes(run(bad, path=NEUTRAL_PATH)) == ["RA151"]


def test_ra151_fires_on_mutating_lambda_hook():
    bad = """
        def install(consensus):
            consensus.add_phase_hook(
                "Tally", lambda phase, ctx: ctx.votes.clear())
    """
    assert codes(run(bad, path=NEUTRAL_PATH)) == ["RA151"]


def test_ra151_fires_on_mutator_call_through_env():
    bad = """
        def watch(phase, ctx):
            ctx.env.note("peek", round=ctx.round)

        def install(consensus):
            consensus.add_phase_hook("*", watch)
    """
    assert codes(run(bad, path=NEUTRAL_PATH)) == ["RA151"]


def test_ra151_read_only_hook_silent():
    good = """
        def watch(phase, ctx):
            print(phase, ctx.round, len(ctx.commitments),
                  ctx.env.network.now if ctx.env else None)

        def install(consensus):
            consensus.add_phase_hook("*", watch, when="after")
    """
    assert codes(run(good, path=NEUTRAL_PATH)) == []


def test_ra151_unregistered_function_silent_outside_obs():
    # same mutation, but the function is never registered as a hook and
    # the file is not in the obs package — protocol code may mutate ctx
    good = """
        def evict(phase, ctx):
            ctx.rejected[0] = "nope"
    """
    assert codes(run(good, path=NEUTRAL_PATH)) == []


def test_ra151_obs_package_ctx_mutation_fires():
    bad = """
        def snapshot(rec, ctx):
            ctx.votes.clear()
            return dict(ctx.commitments)
    """
    assert codes(run(bad, path=OBS_PATH)) == ["RA151"]


def test_ra151_obs_package_read_only_silent():
    good = """
        def snapshot(rec, ctx, env):
            rec.observe("votes", len(ctx.votes))
            return env.network.now
    """
    assert codes(run(good, path=OBS_PATH)) == []


def test_ra151_obs_package_own_state_mutation_silent():
    good = """
        def record(rec, value):
            rec.spans.append(value)
            rec.metrics.update({"x": 1})
    """
    assert codes(run(good, path=OBS_PATH)) == []


def test_ra151_noqa_suppresses():
    bad = """
        def install(consensus):
            consensus.add_phase_hook(
                "*", lambda phase, ctx: ctx.votes.clear())  # noqa: RA151
    """
    report = run(bad, path=NEUTRAL_PATH)
    assert codes(report) == []
    assert [f.rule for f in report.suppressed] == ["RA151"]


def test_ra151_tests_scope_silent():
    bad = """
        def fake_hook(phase, ctx):
            ctx.votes.clear()

        def install(consensus):
            consensus.add_phase_hook("*", fake_hook)
    """
    assert codes(run(bad, path="tests/test_hooks.py")) == []


# ---------------------------------------------------------------------------
# RA2xx — constant-time crypto
# ---------------------------------------------------------------------------

def test_ra201_fires_on_digest_equality():
    bad = """
        def check(reveal_digest, commitment):
            if reveal_digest != commitment.digest:
                return False
            return True
    """
    assert codes(run(bad, path=CRYPTO_PATH)) == ["RA201"]


def test_ra201_fires_on_tuple_wrapped_tag_compare():
    bad = """
        def same_tag(r, c):
            return tuple(r.tag) == tuple(c.tag)
    """
    assert codes(run(bad, path=CRYPTO_PATH)) == ["RA201"]


def test_ra201_compare_digest_silent():
    good = """
        import hmac
        def check(reveal_digest, commitment):
            return hmac.compare_digest(reveal_digest, commitment.digest)
    """
    assert codes(run(good, path=CRYPTO_PATH)) == []


def test_ra201_out_of_crypto_scope_silent():
    bad = """
        def check(a_digest, b_digest):
            return a_digest == b_digest
    """
    assert codes(run(bad, path=NEUTRAL_PATH)) == []


def test_ra201_structural_guards_silent():
    good = """
        def valid(tag):
            return len(tag) == 65 and tag is not None
    """
    assert codes(run(good, path=CRYPTO_PATH)) == []


def test_ra202_fires_on_secret_dependent_branch():
    bad = """
        def sign(digest, private_key):
            if private_key > 100:
                return fast_path(digest)
            return slow_path(digest)
    """
    assert codes(run(bad, path=CRYPTO_PATH)) == ["RA202"]


def test_ra203_fires_on_secret_multiplication():
    bad = """
        def sign_s(z, r, k_inv, private_key, N):
            return k_inv * (z + r * private_key) % N
    """
    assert codes(run(bad, path=CRYPTO_PATH)) == ["RA203"]


def test_ra203_public_arithmetic_silent():
    good = """
        def verify_u(z, r, w, N):
            return (z * w % N, r * w % N)
    """
    assert codes(run(good, path=CRYPTO_PATH)) == []


def test_ra2xx_noqa_suppresses():
    bad = """
        def check(a_digest, b_digest):
            return a_digest == b_digest  # noqa: RA201
    """
    report = run(bad, path=CRYPTO_PATH)
    assert codes(report) == []
    assert [f.rule for f in report.suppressed] == ["RA201"]


# ---------------------------------------------------------------------------
# RA3xx — JAX tracing hygiene
# ---------------------------------------------------------------------------

def test_ra301_fires_on_print_in_jit():
    bad = """
        import jax
        @jax.jit
        def step(x):
            print("tracing", x)
            return x * 2
    """
    assert codes(run(bad, path=NEUTRAL_PATH)) == ["RA301"]


def test_ra301_fires_on_closure_mutation_in_scan_body():
    bad = """
        import jax
        from jax import lax
        trace_log = []
        def body(carry, x):
            trace_log.append(x)
            return carry + x, x
        def run(xs):
            return lax.scan(body, 0.0, xs)
    """
    assert codes(run(bad, path=NEUTRAL_PATH)) == ["RA301"]


def test_ra301_local_mutation_silent():
    good = """
        import jax
        @jax.jit
        def step(x):
            acc = []
            acc.append(x)
            return acc[0] * 2
    """
    assert codes(run(good, path=NEUTRAL_PATH)) == []


def test_ra302_fires_on_python_cast_of_tracer():
    bad = """
        import jax
        @jax.jit
        def step(x):
            return float(x) * 2
    """
    assert codes(run(bad, path=NEUTRAL_PATH)) == ["RA302"]


def test_ra302_cast_outside_traced_fn_silent():
    good = """
        def host_side(x):
            return float(x) * 2
    """
    assert codes(run(good, path=NEUTRAL_PATH)) == []


def test_ra303_fires_on_non_literal_static_argnames():
    bad = """
        import jax, functools
        NAMES = compute_names()
        @functools.partial(jax.jit, static_argnames=NAMES)
        def step(x, mode):
            return x
    """
    assert codes(run(bad, path=NEUTRAL_PATH)) == ["RA303"]


def test_ra303_literal_static_argnames_silent():
    good = """
        import jax, functools
        @functools.partial(jax.jit, static_argnames=("mode", "block"))
        def step(x, mode, block):
            return x
    """
    assert codes(run(good, path=NEUTRAL_PATH)) == []


def test_ra304_fires_on_global_x64_flip():
    bad = """
        import jax
        jax.config.update("jax_enable_x64", True)
    """
    assert codes(run(bad, path=NEUTRAL_PATH)) == ["RA304"]


def test_ra304_scoped_enable_x64_silent():
    good = """
        import jax.numpy as jnp
        from jax.experimental import enable_x64
        def limbs(x):
            with enable_x64():
                return jnp.asarray(x, jnp.float64)
    """
    assert codes(run(good, path=NEUTRAL_PATH)) == []


def test_ra304_fires_on_unscoped_float64():
    bad = """
        import jax.numpy as jnp
        def limbs(x):
            return jnp.asarray(x, jnp.float64)
    """
    assert codes(run(bad, path=NEUTRAL_PATH)) == ["RA304"]


# ---------------------------------------------------------------------------
# RA4xx — domain separation
# ---------------------------------------------------------------------------

def test_ra401_fires_on_unregistered_kind():
    bad = """
        from repro.core.envelope import SignedEnvelope
        def announce(round, sender, digest, sk):
            return SignedEnvelope.seal("gossip", round, sender, digest, sk)
    """
    assert codes(run(bad)) == ["RA401"]


def test_ra401_registered_kind_silent():
    good = """
        from repro.core.envelope import SignedEnvelope
        def announce(round, sender, digest, sk):
            return SignedEnvelope.seal("vote", round, sender, digest, sk)
    """
    assert codes(run(good)) == []


def test_ra402_fires_on_non_literal_kind():
    bad = """
        from repro.core.envelope import SignedEnvelope
        def announce(kind, round, sender, digest, sk):
            return SignedEnvelope.seal(kind, round, sender, digest, sk)
    """
    assert codes(run(bad)) == ["RA402"]


def test_ra403_fires_on_raw_digest_dsign():
    bad = """
        from repro.core import crypto
        def sign_gossip(payload, sk):
            return crypto.dsign(crypto.sha256_digest(payload), sk)
    """
    assert codes(run(bad)) == ["RA403"]


def test_ra403_domained_dsign_silent():
    good = """
        from repro.core import crypto
        from repro.core.envelope import signing_digest
        def sign_vote(round, sender, payload_digest, sk):
            return crypto.dsign(
                signing_digest("vote", round, sender, payload_digest), sk)
    """
    assert codes(run(good)) == []


def test_ra403_out_of_repro_scope_silent():
    bad = """
        from repro.core import crypto
        def bench_sign(payload, sk):
            return crypto.dsign(crypto.sha256_digest(payload), sk)
    """
    assert codes(run(bad, path="benchmarks/bench_sign.py")) == []


def test_ra404_fires_on_duplicate_registry_kind():
    fake_registry = textwrap.dedent("""
        KINDS = ("commit", "reveal", "vote", "vote")
        _DOMAIN = b"pofel-envelope-v1"
    """)
    ctx = FileContext.parse(fake_registry, "src/repro/core/envelope.py")
    report = analyze_contexts([ctx])
    assert "RA404" in codes(report)


def test_ra404_fires_on_domain_tag_redefined_elsewhere():
    registry = textwrap.dedent("""
        KINDS = ("commit", "reveal", "vote", "block")
        _DOMAIN = b"pofel-envelope-v1"
    """)
    offender = textwrap.dedent("""
        GOSSIP_DOMAIN = b"pofel-envelope-v1"
    """)
    ctxs = [FileContext.parse(registry, "src/repro/core/envelope.py"),
            FileContext.parse(offender, "src/repro/core/gossip.py")]
    report = analyze_contexts(ctxs)
    assert "RA404" in codes(report)
    assert any(f.path.endswith("gossip.py") for f in report.findings)


# ---------------------------------------------------------------------------
# noqa mechanics
# ---------------------------------------------------------------------------

def test_bare_noqa_suppresses_everything_on_line():
    bad = """
        import numpy as np
        def pick():
            return np.random.randint(10)  # noqa
    """
    report = run(bad)
    assert codes(report) == []
    assert len(report.suppressed) == 1


def test_noqa_for_other_rule_does_not_suppress():
    bad = """
        import numpy as np
        def pick():
            return np.random.randint(10)  # noqa: RA201
    """
    assert codes(run(bad)) == ["RA101"]


def test_noqa_inside_string_literal_is_inert():
    bad = '''
        import numpy as np
        def pick():
            doc = "suppress with # noqa: RA101"
            return np.random.randint(10)
    '''
    assert codes(run(bad)) == ["RA101"]


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------

def test_baseline_grandfathers_matching_finding(tmp_path):
    bad = textwrap.dedent("""
        import numpy as np
        def pick():
            return np.random.randint(10)
    """)
    report = run(bad)
    assert codes(report) == ["RA101"]
    path = tmp_path / "baseline.json"
    save_baseline(str(path), report.findings, justification="known legacy")
    entries = load_baseline(str(path))
    kept, grandfathered, stale = apply_baseline(report.findings, entries)
    assert kept == [] and len(grandfathered) == 1 and stale == []


def test_baseline_dies_when_flagged_line_changes(tmp_path):
    bad = textwrap.dedent("""
        import numpy as np
        def pick():
            return np.random.randint(10)
    """)
    report = run(bad)
    path = tmp_path / "baseline.json"
    save_baseline(str(path), report.findings, justification="legacy")
    entries = load_baseline(str(path))
    changed = run(bad.replace("randint(10)", "randint(99)"))
    kept, grandfathered, stale = apply_baseline(changed.findings, entries)
    assert len(kept) == 1 and grandfathered == [] and len(stale) == 1


def test_baseline_requires_justification(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"version": 1, "entries": [{
        "rule": "RA101", "path": "x.py", "snippet": "y",
        "justification": "   "}]}))
    with pytest.raises(BaselineError, match="justification"):
        load_baseline(str(path))


def test_baseline_rejects_placeholder_justification(tmp_path):
    """The save_baseline default ("TODO: justify or fix") must not pass the
    loader — regenerating the baseline alone can never silence the gate."""
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"version": 1, "entries": [{
        "rule": "RA101", "path": "x.py", "snippet": "y",
        "justification": "TODO: justify or fix"}]}))
    with pytest.raises(BaselineError, match="placeholder justification"):
        load_baseline(str(path))
    # case and padding don't smuggle it through either
    path.write_text(json.dumps({"version": 1, "entries": [{
        "rule": "RA101", "path": "x.py", "snippet": "y",
        "justification": "  todo — fill this in later  "}]}))
    with pytest.raises(BaselineError, match="placeholder justification"):
        load_baseline(str(path))


def test_baseline_roundtrip_of_fresh_save_is_rejected(tmp_path):
    """save_baseline's own default output must fail load_baseline until a
    human edits the justification in."""
    bad = textwrap.dedent("""
        import numpy as np
        def pick():
            return np.random.randint(10)
    """)
    report = run(bad)
    path = tmp_path / "baseline.json"
    save_baseline(str(path), report.findings)   # default placeholder
    with pytest.raises(BaselineError, match="placeholder justification"):
        load_baseline(str(path))


def test_repo_baseline_entries_all_carry_justifications():
    entries = load_baseline(os.path.join(REPO_ROOT,
                                         "analysis-baseline.json"))
    assert entries, "repo baseline should record the deliberate exceptions"
    for e in entries:
        assert len(e.justification.strip()) > 20


# ---------------------------------------------------------------------------
# self-run + the repo gate
# ---------------------------------------------------------------------------

def test_analyzer_is_clean_on_itself():
    report = analyze_paths(["src/repro/analysis"], root=REPO_ROOT)
    assert report.files_analyzed >= 7
    assert report.findings == [] and report.errors == []


def test_repo_gate_src_tests_is_clean():
    """The acceptance criterion: `python -m repro.analysis src tests`
    exits 0 — zero unsuppressed findings with the checked-in baseline,
    and no stale baseline entries."""
    baseline = load_baseline(os.path.join(REPO_ROOT,
                                          "analysis-baseline.json"))
    report = analyze_paths(["src", "tests"], root=REPO_ROOT,
                           baseline=baseline)
    assert report.errors == []
    assert report.findings == [], [f"{f.path}:{f.line} {f.rule}"
                                   for f in report.findings]
    assert report.stale_baseline == []
    # the known deliberate exceptions are recorded, not silently absent
    assert {f.rule for f in report.grandfathered} == {"RA203"}


def test_cli_exit_codes(tmp_path, capsys):
    from repro.analysis.__main__ import main
    bad = tmp_path / "src" / "repro" / "core" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import numpy as np\nROUND = np.random.randint(9)\n")
    old = os.getcwd()
    os.chdir(tmp_path)
    try:
        assert main(["src", "--format", "text"]) == 1
        assert main(["src", "--format", "github"]) == 1
        out = capsys.readouterr().out
        assert "::error file=src/repro/core/bad.py" in out
        bad.write_text("import numpy as np\n"
                       "RNG = np.random.default_rng(0)\n")
        assert main(["src"]) == 0
        assert main(["nonexistent-dir"]) == 2
    finally:
        os.chdir(old)
