"""repro.core.recovery: durable WAL, crash/replay, ledger snapshots (ISSUE 7).

The WAL's contract: appending the statement you already logged is
idempotent; appending a *conflicting* statement for an already-logged
(kind, round) raises WALConflict. Replay is idempotent (restart twice ≡
restart once), so a node that reboots mid-round re-broadcasts exactly
what it signed before the crash.
"""

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import crypto
from repro.core.consensus import PoFELConsensus
from repro.core.hcds import HCDSNode
from repro.core.recovery import (LedgerSnapshot, NodeWAL, WALConflict,
                                 load_snapshot, rejoin_ledger, replay_wal,
                                 restore_ledger, save_snapshot,
                                 snapshot_ledger, wipe_volatile)


# ---------------------------------------------------------------------------
# NodeWAL semantics
# ---------------------------------------------------------------------------

def test_wal_append_is_idempotent_and_refuses_conflicts():
    wal = NodeWAL(0)
    rec = wal.append("vote", 3, "5")
    assert wal.append("vote", 3, "5") is rec          # identical: idempotent
    assert len(wal) == 1
    with pytest.raises(WALConflict):
        wal.append("vote", 3, "4")                    # conflicting: refused
    assert wal.append("vote", 4, "4").round == 4      # other rounds fine
    assert wal.lookup("vote", 3).digest == "5"


def test_wal_file_backing_survives_reopen(tmp_path):
    path = tmp_path / "node0.wal"
    wal = NodeWAL(0, path=path)
    wal.log_vote(0, 2)
    wal.log_block(0, "ab" * 32)
    # a NEW process opening the same file sees the same records and
    # enforces the same conflicts
    reopened = NodeWAL(0, path=path)
    assert [(r.kind, r.round, r.digest) for r in reopened.records()] == \
           [(r.kind, r.round, r.digest) for r in wal.records()]
    with pytest.raises(WALConflict):
        reopened.log_vote(0, 3)
    assert reopened.log_vote(0, 2).digest == "2"      # re-log: idempotent


def test_wal_commit_record_conflict_on_different_model():
    wal = NodeWAL(7)
    node = HCDSNode(7, wal=wal)
    c = node.commit(None, round=0, model_bytes=b"model-A")
    # same round, same model: the WAL re-issues the identical statement
    again = node.commit(None, round=0, model_bytes=b"model-A")
    assert again == c
    # same round, DIFFERENT model: the double-sign the WAL must refuse
    with pytest.raises(WALConflict):
        node.commit(None, round=0, model_bytes=b"model-B")


# ---------------------------------------------------------------------------
# Crash + replay
# ---------------------------------------------------------------------------

def _committed_node(rounds=3):
    wal = NodeWAL(1)
    node = HCDSNode(1, wal=wal)
    commits = {k: node.commit(None, round=k,
                              model_bytes=b"model-%d" % k)
               for k in range(rounds)}
    return node, wal, commits


def test_replay_reissues_identical_commitments():
    node, wal, commits = _committed_node()
    wipe_volatile(node)                      # the crash
    assert node._own == {} and node._commits == {}
    applied = replay_wal(node, wal)          # the restart
    assert applied == len(commits)
    for k, c in commits.items():
        assert node._commits[k][1] == c      # byte-identical statement
        r = node.reveal(k)                   # reveal still binds
        assert crypto.sha256_digest(r.nonce, r.model_bytes) == c.digest


def test_replay_is_idempotent_restart_twice_equals_once():
    node, wal, _ = _committed_node()

    def state(n):
        return (dict(n._own),
                {k: dict(v) for k, v in n._commits.items()},
                {k: dict(v) for k, v in n._commit_order.items()})

    wipe_volatile(node)
    replay_wal(node, wal)
    once = state(node)
    wipe_volatile(node)
    replay_wal(node, wal)
    replay_wal(node, wal)                    # restart twice
    assert state(node) == once


@settings(max_examples=12, deadline=None)
@given(rounds=st.integers(min_value=1, max_value=5),
       crashes=st.integers(min_value=1, max_value=3))
def test_replay_idempotence_property(rounds, crashes):
    """Property form: any number of crash/replay cycles leaves the node in
    the single-replay state, and every re-commit is the logged one."""
    node, wal, commits = _committed_node(rounds=rounds)
    for _ in range(crashes):
        wipe_volatile(node)
        replay_wal(node, wal)
    for k, c in commits.items():
        # a post-restart commit() re-issues the logged statement
        assert node.commit(None, round=k,
                           model_bytes=b"model-%d" % k) == c
    # exactly one commit record per round, no duplicates from the cycles
    assert sum(1 for r in wal.records() if r.kind == "commit") == rounds
    assert len(wal) == rounds


def test_consensus_nodes_carry_wals_by_default():
    cons = PoFELConsensus(n_nodes=3)
    assert set(cons.wals) == {0, 1, 2}
    assert all(cons.hcds_nodes[i].wal is cons.wals[i] for i in range(3))


# ---------------------------------------------------------------------------
# Ledger snapshot / restore / rejoin
# ---------------------------------------------------------------------------

def _mini_chain(n_nodes=3, rounds=2):
    """A tiny real chain via the ideal-mode consensus driver."""
    cons = PoFELConsensus(n_nodes=n_nodes)
    rng = np.random.default_rng(0)
    for _ in range(rounds):
        models = [{"w": rng.normal(size=4).astype(np.float32)}
                  for _ in range(n_nodes)]
        cons.run_round(models, data_sizes=[1.0] * n_nodes)
    return cons


def test_ledger_snapshot_roundtrip_and_tamper_detection():
    cons = _mini_chain()
    led = cons.ledgers[0]
    snap = snapshot_ledger(led)
    restored = restore_ledger(snap, cons.public_keys)
    assert restored.height == led.height
    assert restored.head_hash == led.head_hash
    # a tampered payload fails the checkpoint-style integrity digest
    bad = LedgerSnapshot(snap.node_id, snap.height, snap.head, snap.digest,
                         snap.payload.replace("leader_id", "leader_1d"))
    with pytest.raises(Exception):
        restore_ledger(bad, cons.public_keys)


def test_snapshot_directory_roundtrip(tmp_path):
    cons = _mini_chain()
    led = cons.ledgers[1]
    model = {"w": np.arange(4, dtype=np.float32)}
    save_snapshot(tmp_path, led, model_tree=model)
    restored, restored_model = load_snapshot(
        tmp_path, node_id=1, public_keys=cons.public_keys,
        model_template=model)
    assert restored.head_hash == led.head_hash
    np.testing.assert_array_equal(restored_model["w"], model["w"])


def test_rejoin_ledger_adopts_best_reachable_chain():
    cons = _mini_chain(rounds=3)
    stale = snapshot_ledger(cons.ledgers[0])
    behind = restore_ledger(stale, cons.public_keys)
    behind.blocks = behind.blocks[:1]        # the node missed two rounds
    adopted = rejoin_ledger(behind, [cons.ledgers[1], cons.ledgers[2]],
                            cons.public_keys)
    assert adopted == 2
    assert behind.head_hash == cons.ledgers[1].head_hash
    # already caught up: nothing to adopt, and no peers is a no-op
    assert rejoin_ledger(behind, [cons.ledgers[1]], cons.public_keys) == 0
    assert rejoin_ledger(behind, [], cons.public_keys) == 0


# ---------------------------------------------------------------------------
# A commit's model in a memory-only WAL
# ---------------------------------------------------------------------------

def _models(n=3, d=64, seed=0):
    rng = np.random.default_rng(seed)
    return [np.asarray(rng.normal(size=d), np.float32) for _ in range(n)]


def test_pathless_wal_holds_the_commit_bytes_and_no_hex():
    wal = NodeWAL(1)
    node = HCDSNode(1, wal=wal)
    model = b"\x01" * 1000
    node.commit(None, round=0, model_bytes=model)
    rec = wal.lookup("commit", 0)
    assert "model" not in rec.data
    assert all(len(v) < 200 for v in rec.data.values())
    assert wal.commit_model(rec) is model            # the object, no copy
    assert wal.held_bytes == 1000


def test_durable_wal_still_writes_the_model_into_its_file(tmp_path):
    wal = NodeWAL(1, path=tmp_path / "n1.wal")
    node = HCDSNode(1, wal=wal)
    node.commit(None, round=0, model_bytes=b"model-A")
    again = NodeWAL(1, path=tmp_path / "n1.wal")
    rec = again.lookup("commit", 0)
    assert rec.data["model"] == b"model-A".hex()
    assert again.commit_model(rec) == b"model-A" and again.held_bytes == 0


def test_wal_releases_the_model_once_the_block_is_on_the_ledger():
    from repro import obs
    cons = PoFELConsensus(n_nodes=3)
    rec = obs.TraceRecorder("t")
    with obs.use_recorder(rec):
        cons.run_round(_models(), [1.0, 1.0, 1.0])
    assert all(w.held_bytes == 0 for w in cons.wals.values())
    assert all(w.lookup("commit", 0) is not None for w in cons.wals.values())
    held = [s.attrs["held_bytes"] for s in rec.spans
            if s.name == "wal.log" and s.attrs.get("kind") == "commit"]
    assert len(held) == 3 and held[0] > 0
    # the commit record still refuses a conflicting re-commit
    with pytest.raises(WALConflict):
        cons.hcds_nodes[0].commit(None, round=0, model_bytes=b"other")
    # the nodes keep the newest minted round's models, none older
    node = cons.hcds_nodes[1]
    assert len(node.accepted_models(0)) == 3
    cons.run_round(_models(seed=1), [1.0, 1.0, 1.0])
    assert node.accepted_models(0) == {} and 0 not in node._own
    assert len(node.accepted_models(1)) == 3


def test_crash_before_reveal_replays_byte_identical_with_held_bytes():
    wal = NodeWAL(2)
    node = HCDSNode(2, wal=wal)
    model = bytes(range(256)) * 4
    c = node.commit(None, round=5, model_bytes=model)
    wipe_volatile(node)                              # crash before reveal
    assert replay_wal(node, wal) == 1
    assert node._commits[5][2] == c
    r = node.reveal(5)
    assert r.model_bytes == model
    assert crypto.sha256_digest(r.nonce, r.model_bytes) == c.digest
    # after the round's block, the payload is gone and replay skips it
    wal.release_model(5)
    wipe_volatile(node)
    assert replay_wal(node, wal) == 0 and wal.held_bytes == 0
