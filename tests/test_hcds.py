"""HCDS commit/reveal protocol + adversary models (paper §3.2.1, §6.1)."""

import numpy as np
import pytest

from repro.core.hcds import HCDSNode, Reveal, run_hcds_round
from repro.core import crypto
from repro.core.serialization import serialize_pytree


def _models(n, rng, shape=(8, 4)):
    return [{"w": rng.normal(size=shape).astype(np.float32)} for _ in range(n)]


def test_honest_round_all_accepted(rng):
    nodes = [HCDSNode(i) for i in range(4)]
    results = run_hcds_round(nodes, _models(4, rng), round=0)
    for recv, senders in results.items():
        assert all(r.accepted for r in senders.values())
    for n in nodes:
        assert len(n.accepted_models(0)) == 4  # incl. own


def test_reveal_without_commit_rejected(rng):
    nodes = [HCDSNode(i) for i in range(2)]
    models = _models(2, rng)
    nodes[0].commit(models[0], 0)
    # node 1 never committed; its reveal must be rejected by node 0
    fake = Reveal(1, 0, b"\x00" * 32, serialize_pytree(models[1]),
                  (1, 1))
    res = nodes[0].receive_reveal(fake, nodes[1].keypair.public_key)
    assert not res.accepted and res.reason == "no-commitment"


def test_byte_identical_plagiarism_detected(rng):
    """Adversary copies a victim's model verbatim (paper §3.2.1 'direct
    copying'): both commit, but the duplicate reveal is rejected."""
    nodes = [HCDSNode(i) for i in range(3)]
    models = _models(3, rng)
    models[2] = models[0]          # node 2 plagiarizes node 0
    commits = [n.commit(m, 0) for n, m in zip(nodes, models)]
    pks = {n.node_id: n.keypair.public_key for n in nodes}
    for c in commits:
        for n in nodes:
            if n.node_id != c.node_id:
                n.receive_commit(c, pks[c.node_id])
    reveals = [n.reveal(0) for n in nodes]
    # deliver victim first, then plagiarist — receiver flags the duplicate
    receiver = nodes[1]
    assert receiver.receive_reveal(reveals[0], pks[0]).accepted
    res = receiver.receive_reveal(reveals[2], pks[2])
    assert not res.accepted and res.reason == "plagiarized-model"


def test_equivocation_rejected(rng):
    """A node cannot reveal a different model than it committed to
    (binding property, paper §6.1)."""
    nodes = [HCDSNode(i) for i in range(2)]
    models = _models(2, rng)
    pks = {n.node_id: n.keypair.public_key for n in nodes}
    c0 = nodes[0].commit(models[0], 0)
    nodes[1].receive_commit(c0, pks[0])
    r0 = nodes[0].reveal(0)
    # swap in different model bytes after commitment
    evil = Reveal(0, 0, r0.nonce, serialize_pytree(_models(1, rng)[0]), r0.tag)
    res = nodes[1].receive_reveal(evil, pks[0])
    assert not res.accepted and res.reason == "digest-mismatch"


def test_commit_with_bad_signature_rejected(rng):
    nodes = [HCDSNode(i) for i in range(2)]
    c = nodes[0].commit(_models(1, rng)[0], 0)
    # verify against the wrong public key
    res = nodes[1].receive_commit(c, nodes[1].keypair.public_key)
    assert not res.accepted and res.reason == "bad-signature"


def test_hiding_commit_reveals_nothing(rng):
    """The digest is 32 bytes regardless of model size — the model cannot
    be recovered from the commit-stage broadcast."""
    node = HCDSNode(0)
    big = {"w": rng.normal(size=(256, 256)).astype(np.float32)}
    c = node.commit(big, 0)
    assert len(c.digest) == 32


def test_plagiarism_blame_is_delivery_order_independent(rng):
    """Commit record order — not reveal arrival order — decides who the
    plagiarist is: even when the copy's reveal arrives FIRST, the receiver
    retroactively evicts it once the earlier committer's reveal lands."""
    nodes = [HCDSNode(i) for i in range(3)]
    models = _models(3, rng)
    models[2] = models[0]          # node 2 plagiarizes node 0
    commits = [n.commit(m, 0) for n, m in zip(nodes, models)]
    pks = {n.node_id: n.keypair.public_key for n in nodes}
    for c in commits:
        for n in nodes:
            if n.node_id != c.node_id:
                n.receive_commit(c, pks[c.node_id])
    for n in nodes:
        n.finalize_commit_stage(0)
    reveals = [n.reveal(0) for n in nodes]
    receiver = nodes[1]
    # adversarial delivery: the copy arrives before the victim's reveal
    assert receiver.receive_reveal(reveals[2], pks[2]).accepted
    res = receiver.receive_reveal(reveals[0], pks[0])
    assert res.accepted                 # the victim is never rejected
    assert res.evicted == 2             # the copy is retroactively blamed
    accepted = receiver.accepted_models(0)
    assert 0 in accepted and 2 not in accepted


def test_plagiarism_blame_agrees_across_delivery_orders(rng):
    """Two receivers seeing opposite reveal arrival orders converge on the
    same accepted set and the same guilty node."""
    nodes = [HCDSNode(i) for i in range(4)]
    models = _models(4, rng)
    models[3] = models[1]          # node 3 plagiarizes node 1
    commits = [n.commit(m, 0) for n, m in zip(nodes, models)]
    pks = {n.node_id: n.keypair.public_key for n in nodes}
    for c in commits:
        for n in nodes:
            if n.node_id != c.node_id:
                n.receive_commit(c, pks[c.node_id])
    for n in nodes:
        n.finalize_commit_stage(0)
    reveals = {n.node_id: n.reveal(0) for n in nodes}
    orders = {0: [1, 3, 2], 2: [3, 1, 0]}   # receiver -> arrival order
    for recv, order in orders.items():
        for sender in order:
            nodes[recv].receive_reveal(reveals[sender], pks[sender])
    for recv in orders:
        accepted = nodes[recv].accepted_models(0)
        assert 1 in accepted and 3 not in accepted, recv


def _consensus_round(mode, models, rec):
    """One honest consensus round over ``models`` under the recorder
    ``rec``: ``ideal`` (synchronous, every node present) or ``networked``
    (the fault-free ``ideal`` scenario's message bus). Returns the
    consensus driver."""
    from repro import obs, sim
    from repro.core.consensus import PoFELConsensus
    n = len(models)
    cons = PoFELConsensus(n_nodes=n)
    env = None
    if mode == "networked":
        env = sim.build_env(sim.get_scenario("ideal"), n_nodes=n, seed=0)
        env.bind(cons)
        env.begin_round(0)
    with obs.use_recorder(rec):
        cons.run_round(models, [1.0] * n, env=env)
    return cons


@pytest.mark.parametrize("mode", ["ideal", "networked"])
def test_a_round_hashes_each_model_three_times(mode):
    """SHA-256 reads each model's bytes three times a round — the
    commitment H(r‖w), sha256(w) shared by the WAL and the block, and the
    receivers' one H(r‖w) a reveal — and gw once; everything else hashed
    is small (nonces, envelope, vote and block digests)."""
    from repro import obs
    n, d = 4, 32768                 # 128 KB a model: above the slack
    rng = np.random.default_rng(11)
    models = [np.asarray(rng.normal(size=d), np.float32) for _ in range(n)]
    sizes = [len(serialize_pytree(m)) for m in models]
    rec = obs.TraceRecorder("hashes")
    _consensus_round(mode, models, rec)
    hashed = rec.metrics.counters["crypto.sha256_bytes"]
    small = hashed - (3 * sum(sizes) + d * 4)
    assert 0 <= small < 64 * 1024, small
    passes = [s for s in rec.spans if s.name == "crypto.sha256"
              and s.attrs["bytes"] >= d * 4]
    assert len(passes) == 3 * n + 1


def test_own_reveal_is_checked_with_the_digest_over_its_own_nonce(rng):
    """A node with no WAL that commits twice in one round keeps its first
    commitment but holds the second nonce: its own reveal must still fail
    to bind in its own store, with no hash of the model at reveal."""
    from repro import obs
    node = HCDSNode(0)
    model = _models(1, rng)[0]
    first = node.commit(model, 0)
    second = node.commit(model, 0)
    assert first.digest != second.digest
    assert node._commits[0][0] == first
    rec = obs.TraceRecorder("reveal")
    with obs.use_recorder(rec):
        r = node.reveal(0)
    assert "crypto.sha256_bytes" not in rec.metrics.counters
    assert node.accepted_models(0) == {}
    assert crypto.sha256_digest(r.nonce, r.model_bytes) == second.digest
    res = node.receive_reveal(r, node.keypair.public_key)
    assert not res.accepted and res.reason == "digest-mismatch"


@pytest.mark.parametrize("mode", ["ideal", "networked"])
def test_block_wal_and_model_bytes_digests_agree(mode):
    """The digest computed once a round is the one every place records:
    the block's ``model_digests[i]`` is the WAL commit record's key and
    sha256 of the model's bytes, and the commitment binds the logged
    nonce to those bytes."""
    from repro import obs
    rng = np.random.default_rng(5)
    models = [np.asarray(rng.normal(size=256), np.float32) for _ in range(4)]
    cons = _consensus_round(mode, models, obs.TraceRecorder("digests"))
    block = cons.ledgers[0].blocks[-1]
    assert sorted(block.model_digests) == [0, 1, 2, 3]
    for i, m in enumerate(models):
        model_bytes = serialize_pytree(m)
        rec = cons.wals[i].lookup("commit", 0)
        assert block.model_digests[i] == rec.digest == \
            crypto.sha256_digest(model_bytes).hex()
        commitment = cons.hcds_nodes[0]._commits[0][i].digest
        assert commitment.hex() == rec.data["commitment"] == \
            crypto.sha256_digest(bytes.fromhex(rec.data["nonce"]),
                                 model_bytes).hex()


def test_handed_digest_keeps_the_wal_refusal_and_the_replay():
    """A commit given its model digest logs the same record as one that
    hashes; a re-commit of other bytes for the round is still refused, and
    a replay after a crash restores the same commitment, whose own reveal
    binds."""
    from repro.core.recovery import (NodeWAL, WALConflict, replay_wal,
                                     wipe_volatile)
    model, other = b"model-A" * 100, b"model-B" * 100
    digest = crypto.sha256_digest(model)
    wal = NodeWAL(3)
    node = HCDSNode(3, wal=wal)
    c = node.commit(None, 0, model_bytes=model, model_digest=digest)
    assert wal.lookup("commit", 0).digest == digest.hex()
    assert node.commit(None, 0, model_bytes=model, model_digest=digest) == c
    with pytest.raises(WALConflict):
        node.commit(None, 0, model_bytes=other,
                    model_digest=crypto.sha256_digest(other))
    with pytest.raises(WALConflict):
        node.commit(None, 0, model_bytes=other)
    wipe_volatile(node)
    assert replay_wal(node, wal) == 1
    assert node._commits[0][3] == c
    r = node.reveal(0)
    assert crypto.sha256_digest(r.nonce, r.model_bytes) == c.digest
    assert node.accepted_models(0) == {3: model}
