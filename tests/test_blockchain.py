"""Block / ledger / smart-contract mechanics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.blockchain.block import GENESIS_HASH, Block, block_hash
from repro.blockchain.ledger import InvalidBlock, Ledger
from repro.blockchain.smart_contract import (ContractError, VoteSubmission,
                                             VoteTallyContract)
from repro.core import crypto
from repro.core.btsv import btsv_round, init_history


def _block(index=0, prev=GENESIS_HASH, leader=0):
    return Block(index=index, round=index, leader_id=leader, prev_hash=prev,
                 model_digests={0: "aa", 1: "bb"}, global_model_digest="cc",
                 votes={0: 0, 1: 0}, vote_weights={0: 1.0, 1: 1.0},
                 advotes={0: 2.0, 1: 0.0})


def test_append_and_verify_chain():
    kp = crypto.ECDSAKeyPair.generate(b"leader")
    led = Ledger(0)
    b0 = _block().signed(kp)
    led.append(b0, leader_pk=kp.public_key)
    b1 = _block(index=1, prev=block_hash(b0)).signed(kp)
    led.append(b1, leader_pk=kp.public_key)
    assert led.verify_chain() and led.height == 2


def test_chain_break_rejected():
    led = Ledger(0)
    led.append(_block())
    with pytest.raises(InvalidBlock):
        led.append(_block(index=1, prev="deadbeef"))


def test_tampered_signature_rejected():
    kp = crypto.ECDSAKeyPair.generate(b"leader")
    other = crypto.ECDSAKeyPair.generate(b"imposter")
    led = Ledger(0)
    with pytest.raises(InvalidBlock):
        led.append(_block().signed(other), leader_pk=kp.public_key)


def test_retally_mismatch_rejected():
    led = Ledger(0)
    with pytest.raises(InvalidBlock):
        led.append(_block(leader=1), retally=lambda b: 0)


def test_ledger_persistence_roundtrip(tmp_path):
    kp = crypto.ECDSAKeyPair.generate(b"leader")
    led = Ledger(0)
    led.append(_block().signed(kp), leader_pk=kp.public_key)
    led.save(tmp_path / "chain.json")
    led2 = Ledger.load(tmp_path / "chain.json")
    assert led2.height == 1
    assert led2.blocks[0].verify_signature(kp.public_key)


def _chain(kp, n, leader=0, salt=""):
    """A valid signed chain of n blocks."""
    blocks, prev = [], GENESIS_HASH
    for i in range(n):
        b = Block(index=i, round=i, leader_id=leader, prev_hash=prev,
                  model_digests={0: "aa" + salt}, global_model_digest="cc",
                  votes={0: 0}, vote_weights={0: 1.0},
                  advotes={0: 1.0}).signed(kp)
        blocks.append(b)
        prev = block_hash(b)
    return blocks


def test_node_that_missed_a_round_rejects_stale_prev_hash():
    """A node at height 1 must reject the network's height-2 block (its
    prev_hash names a block the node never saw) — then converge via
    catch-up sync instead of blind append."""
    kp = crypto.ECDSAKeyPair.generate(b"leader")
    chain = _chain(kp, 3)
    behind = Ledger(1)
    behind.append(chain[0], leader_pk=kp.public_key)
    with pytest.raises(InvalidBlock, match="prev_hash mismatch"):
        behind.append(chain[2], leader_pk=kp.public_key)
    adopted = behind.sync_from(chain, public_keys={0: kp.public_key})
    assert adopted == 2
    assert behind.height == 3 and behind.verify_chain()
    assert behind.head_hash == block_hash(chain[-1])


def test_sync_from_diverged_history_raises():
    kp = crypto.ECDSAKeyPair.generate(b"leader")
    ours = Ledger(0)
    for b in _chain(kp, 2, salt="x"):
        ours.append(b, leader_pk=kp.public_key)
    theirs = _chain(kp, 3, salt="y")       # longer, different history
    with pytest.raises(InvalidBlock):
        ours.sync_from(theirs, public_keys={0: kp.public_key})
    # equal-length divergence must raise too, not silently "sync" nothing
    with pytest.raises(InvalidBlock, match="diverges"):
        ours.sync_from(_chain(kp, 2, salt="y"),
                       public_keys={0: kp.public_key})
    assert ours.height == 2


def test_fork_choice_adopts_longer_valid_chain():
    kp = crypto.ECDSAKeyPair.generate(b"leader")
    ours = Ledger(0)
    for b in _chain(kp, 2, salt="x"):
        ours.append(b, leader_pk=kp.public_key)
    longer = _chain(kp, 4, salt="y")
    assert ours.fork_choice(longer, public_keys={0: kp.public_key})
    assert ours.height == 4 and ours.verify_chain()
    # a shorter chain never replaces ours
    assert not ours.fork_choice(_chain(kp, 3, salt="z"),
                                public_keys={0: kp.public_key})
    assert ours.height == 4


def test_fork_choice_equal_height_tie_breaks_on_head_hash():
    kp = crypto.ECDSAKeyPair.generate(b"leader")
    a, b = _chain(kp, 2, salt="a"), _chain(kp, 2, salt="b")
    small, big = sorted((a, b), key=lambda c: block_hash(c[-1]))
    led = Ledger(0)
    for blk in big:
        led.append(blk, leader_pk=kp.public_key)
    assert led.fork_choice(small)          # smaller head hash wins the tie
    assert not led.fork_choice(big)        # and the loser cannot flap back
    assert led.head_hash == block_hash(small[-1])


def test_fork_choice_rejects_tampered_candidate():
    kp = crypto.ECDSAKeyPair.generate(b"leader")
    imposter = crypto.ECDSAKeyPair.generate(b"imposter")
    led = Ledger(0)
    led.append(_chain(kp, 1)[0], leader_pk=kp.public_key)
    forged = _chain(imposter, 3)           # longer but wrongly signed
    assert not led.fork_choice(forged, public_keys={0: kp.public_key})
    assert led.height == 1


def test_contract_partial_tally_with_quorum():
    """Networked mode: the tally proceeds on >= min_submissions votes,
    treating absent voters as neutral abstentions."""
    n = 4
    c = VoteTallyContract(n)
    preds = np.full((n,), (1 - 0.99) / (n - 1), np.float32)
    preds[2] = 0.99
    for i in range(3):                     # node 3's vote never landed
        c.submit(VoteSubmission(i, 0, 2, preds))
    with pytest.raises(ContractError):     # strict mode still demands all N
        c.tally(0)
    res = c.tally(0, min_submissions=3)
    assert int(res.leader) == 2
    assert float(res.advotes[2]) > 0


def test_contract_drop_round_clears_partial_state():
    c = VoteTallyContract(3)
    c.submit(VoteSubmission(0, 0, 1, np.asarray([0.005, 0.99, 0.005])))
    c.drop_round(0)
    # a retry of the same round may resubmit without tripping the
    # duplicate-submission guard
    c.submit(VoteSubmission(0, 0, 1, np.asarray([0.005, 0.99, 0.005])))


def test_contract_requires_all_submissions():
    c = VoteTallyContract(3)
    c.submit(VoteSubmission(0, 0, 1, np.asarray([0.005, 0.99, 0.005])))
    with pytest.raises(ContractError):
        c.tally(0)


def test_contract_rejects_bad_submissions():
    c = VoteTallyContract(3)
    with pytest.raises(ContractError):
        c.submit(VoteSubmission(0, 0, 5, np.asarray([1, 0, 0.0])))  # vote OOR
    with pytest.raises(ContractError):
        c.submit(VoteSubmission(0, 0, 1, np.asarray([0.5, 0.1, 0.1])))  # sum≠1
    c.submit(VoteSubmission(0, 0, 1, np.asarray([0.005, 0.99, 0.005])))
    with pytest.raises(ContractError):  # duplicate
        c.submit(VoteSubmission(0, 0, 1, np.asarray([0.005, 0.99, 0.005])))


def test_contract_tally_deterministic_and_cached():
    n = 4
    c = VoteTallyContract(n)
    preds = np.full((n,), (1 - 0.99) / (n - 1), np.float32)
    preds[2] = 0.99
    for i in range(n):
        c.submit(VoteSubmission(i, 0, 2, preds))
    r1 = c.tally(0)
    r2 = c.tally(0)     # cached
    assert int(r1.leader) == 2 and r1 is r2


def _tally_rounds(absent):
    """Three rounds of one contract, each with its own votes and
    predictions; voter ``absent`` (if any) never submits, and the tally
    runs on the quorum of those that did. Returns the contract and each
    round's (votes, P, present) as the contract builds them."""
    n = 4
    rng = np.random.default_rng(7)
    c = VoteTallyContract(n)
    inputs = []
    for r in range(3):
        votes = rng.integers(0, n, size=n).astype(np.int32)
        P = rng.dirichlet(np.ones(n), size=n).astype(np.float32)
        P /= P.sum(axis=1, keepdims=True)
        present = np.ones(n, np.float32)
        for i in range(n):
            if i == absent:
                votes[i], P[i], present[i] = -1, 1.0 / n, 0.0
            else:
                c.submit(VoteSubmission(i, r, int(votes[i]), P[i]))
        if absent is None:
            c.tally(r)
        else:
            c.tally(r, min_submissions=n - 1)
        inputs.append((votes, P, None if absent is None else present))
    return c, inputs


@pytest.mark.parametrize("absent", [None, 3], ids=["strict", "quorum"])
def test_contract_results_are_host_numpy(absent):
    """The tally pulls its whole result to the host in one copy: every
    field a consumer reads is a NumPy array, never a device array."""
    c, _ = _tally_rounds(absent)
    for r in range(3):
        res = c.result(r)
        for name, x in zip(res._fields, res):
            assert isinstance(x, (np.ndarray, np.generic)), name
            assert not isinstance(x, jax.Array), name


@pytest.mark.parametrize("absent", [None, 3], ids=["strict", "quorum"])
def test_contract_tally_matches_btsv_round_bit_for_bit(absent):
    """The contract's host results are the jitted tally's outputs, bit for
    bit: the same float32 inputs, each uploaded on its own here, with the
    score history threaded round to round."""
    c, inputs = _tally_rounds(absent)
    history = init_history(c.n_nodes, c.cfg)
    for r, (votes, P, present) in enumerate(inputs):
        want, history = btsv_round(
            jnp.asarray(votes), jnp.stack([jnp.asarray(p) for p in P]),
            history, c.cfg,
            present=None if present is None else jnp.asarray(present))
        want = jax.device_get(want)
        got = c.result(r)
        for name, w, g in zip(want._fields, want, got):
            assert g.dtype == w.dtype and g.shape == w.shape, name
            assert g.tobytes() == w.tobytes(), (r, name)
