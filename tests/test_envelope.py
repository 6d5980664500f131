"""Signed-envelope message layer + batch crypto backends.

Property pins (via the optional-hypothesis shim):

* ``verify_batch`` accepts iff every signature individually verifies —
  the batch RLC equation and the per-message dverify loop agree on every
  forged-subset pattern;
* bisection returns exactly the forged indices (attribution);
* ``Signature.to_bytes``/``from_bytes`` round-trips canonically through
  envelope, block, and ledger dict I/O.
"""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.blockchain.block import GENESIS_HASH, Block, block_hash
from repro.blockchain.ledger import Ledger
from repro.blockchain.smart_contract import (VoteSubmission,
                                             VoteTallyContract,
                                             vote_payload_digest)
from repro.core import crypto
from repro.core.envelope import (SignedEnvelope, commit_signing_digest,
                                 verify_envelopes)

_KPS = [crypto.ECDSAKeyPair.generate(bytes([i, 0xEE])) for i in range(8)]


def _batch(n):
    """n distinct (tag, pk, digest) items signed by distinct keys."""
    items = []
    for i in range(n):
        d = crypto.sha256_digest(b"payload", bytes([i]))
        items.append((crypto.dsign(d, _KPS[i].private_key),
                      _KPS[i].public_key, d))
    return items


def _forge(item, mode):
    """One forged variant of a valid item; must fail individual dverify."""
    tag, pk, d = item
    if mode == 0:       # tampered s
        return (crypto.Signature(tag.r, tag.s ^ 0x2, tag.v), pk, d)
    if mode == 1:       # signature transplanted onto a different digest
        return (tag, pk, crypto.sha256_digest(d))
    return (tag, _KPS[7].public_key, d)   # wrong public key


# ---------------------------------------------------------------------------
# verify_batch: accept-iff-all-individually-valid + exact attribution
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(n=st.integers(1, 6), mask=st.integers(0, 63),
       mode=st.integers(0, 2))
def test_verify_batch_accepts_iff_each_verifies(n, mask, mode):
    items = _batch(n)
    forged = [i for i in range(n) if (mask >> i) & 1]
    for i in forged:
        items[i] = _forge(items[i], mode)
    individually = [crypto.dverify(t, pk, d) for t, pk, d in items]
    res = crypto.verify_batch(items, backend="batch")
    assert res.ok == all(individually)
    assert list(res.bad) == [i for i, ok in enumerate(individually) if not ok]
    assert list(res.bad) == forged          # bisection attribution is exact
    # and identical under the per-message reference backend
    assert crypto.verify_batch(items, backend="naive") == res


def test_batch_dedups_receiver_copies():
    """A round's N×(N−1) receiver copies of N distinct tags verify as one
    deduplicated batch, with per-copy attribution preserved."""
    items = _batch(4)
    items[2] = _forge(items[2], 0)
    copies = [it for it in items for _ in range(3)]
    res = crypto.verify_batch(copies, backend="batch")
    assert not res.ok
    assert list(res.bad) == [6, 7, 8]       # all three copies of item 2


def test_tampered_recovery_bit_still_accepts():
    """The recovery bit is a batching hint, not part of the signed
    statement: flipping it defeats the fast batch equation but bisection
    must still accept the (individually valid) signature."""
    (tag, pk, d), = _batch(1)
    flipped = crypto.Signature(tag.r, tag.s, tag.v ^ 1)
    assert crypto.dverify(flipped, pk, d)
    assert crypto.verify_batch([(flipped, pk, d)], backend="batch").ok


def test_legacy_two_tuple_signatures_batch_verify():
    """Pre-envelope (r, s) pairs lack a recovery bit; verify_batch routes
    them through individual verification without rejecting them."""
    items = [(tuple(t)[:2], pk, d) for t, pk, d in _batch(3)]
    assert crypto.verify_batch(items, backend="batch").ok


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown crypto backend"):
        crypto.verify_batch([], backend="quantum")
    for name in ("quantum", "jax", "auto", "windowed"):
        with pytest.raises(ValueError, match="unknown crypto backend"):
            crypto.set_backend(name)


# ---------------------------------------------------------------------------
# envelopes
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(kind=st.sampled_from(["commit", "reveal", "vote", "block"]),
       round=st.integers(0, 1000), sender=st.integers(0, 7))
def test_envelope_seal_verify_and_dict_roundtrip(kind, round, sender):
    payload = crypto.sha256_digest(b"payload", kind.encode())
    # (kind is drawn from the registry by the strategy above)
    env = SignedEnvelope.seal(kind, round, sender, payload,  # noqa: RA402
                              _KPS[sender].private_key)
    assert env.verify(_KPS[sender].public_key)
    assert not env.verify(_KPS[(sender + 1) % 8].public_key)
    again = SignedEnvelope.from_dict(env.to_dict())
    assert again == env and again.verify(_KPS[sender].public_key)


def test_envelope_domain_separation():
    """The same payload digest signed as a commit must not verify as a
    vote/block envelope — the kind is bound into the signing digest."""
    payload = crypto.sha256_digest(b"w")
    commit = SignedEnvelope.seal("commit", 3, 1, payload,
                                 _KPS[1].private_key)
    for other in ("reveal", "vote", "block"):
        # deliberately replays the tag under a different (registered) kind
        replayed = SignedEnvelope(other, 3, 1, payload, commit.signature)  # noqa: RA402
        assert not replayed.verify(_KPS[1].public_key)
    assert commit_signing_digest(3, 1, payload) == commit.signing_digest()


def test_verify_envelopes_attributes_forged_senders():
    envs = []
    for i in range(5):
        payload = crypto.sha256_digest(bytes([i]))
        key = _KPS[7] if i in (1, 4) else _KPS[i]     # 1 and 4 forge
        envs.append(SignedEnvelope.seal("commit", 0, i, payload,
                                        key.private_key))
    pks = {i: _KPS[i].public_key for i in range(5)}
    res = verify_envelopes(envs, pks)
    assert not res.ok
    assert list(res.bad) == [1, 4]
    assert res.bad_senders(envs) == [1, 4]


def test_verify_envelopes_flags_unknown_sender():
    env = SignedEnvelope.seal("vote", 0, 9, crypto.sha256_digest(b"v"),
                              _KPS[0].private_key)
    res = verify_envelopes([env], {0: _KPS[0].public_key})
    assert not res.ok and res.bad == (0,)


# ---------------------------------------------------------------------------
# Signature canonical serialization (block + ledger I/O)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_signature_bytes_roundtrip(seed):
    kp = crypto.ECDSAKeyPair.generate(seed.to_bytes(4, "big"))
    sig = crypto.dsign(crypto.sha256_digest(seed.to_bytes(4, "big")),
                       kp.private_key)
    assert crypto.Signature.from_bytes(sig.to_bytes()) == sig
    assert crypto.Signature.coerce(sig.to_bytes().hex()) == sig
    assert crypto.Signature.coerce(list(sig)) == sig
    assert crypto.Signature.coerce((sig.r, sig.s)) == (sig.r, sig.s, 0)


def test_block_signature_canonical_in_ledger_io(tmp_path):
    kp = crypto.ECDSAKeyPair.generate(b"leader")
    block = Block(index=0, round=0, leader_id=0, prev_hash=GENESIS_HASH,
                  model_digests={0: "aa"}, global_model_digest="cc",
                  votes={0: 0}, vote_weights={0: 1.0},
                  advotes={0: 1.0}).signed(kp)
    assert isinstance(block.leader_signature, crypto.Signature)
    assert block.verify_signature(kp.public_key)
    assert block.envelope().verify(kp.public_key)
    led = Ledger(0)
    led.append(block, leader_pk=kp.public_key)
    led.save(tmp_path / "chain.json")
    led2 = Ledger.load(tmp_path / "chain.json")
    assert led2.blocks[0].leader_signature == block.leader_signature
    assert isinstance(led2.blocks[0].leader_signature, crypto.Signature)
    assert block_hash(led2.blocks[0]) == block_hash(block)
    assert led2.verify_chain(public_keys={0: kp.public_key})


# ---------------------------------------------------------------------------
# signed vote envelopes in the tally contract
# ---------------------------------------------------------------------------

def _preds(n, vote, g=0.99):
    p = np.full(n, (1 - g) / (n - 1), np.float32)
    p[vote] = g
    return p


def test_contract_drops_forged_vote_with_attribution():
    n = 4
    pks = {i: _KPS[i].public_key for i in range(n)}
    c = VoteTallyContract(n, public_keys=pks)
    for i in range(n):
        sub = VoteSubmission.signed(i, 0, 2, _preds(n, 2),
                                    _KPS[i].private_key)
        if i == 3:      # node 3 signs with a key it does not own
            forged = SignedEnvelope.seal(
                "vote", 0, 3, sub.envelope.payload_digest,
                _KPS[7].private_key)
            sub = VoteSubmission(3, 0, 2, _preds(n, 2), forged)
        c.submit(sub)
    res = c.tally(0, min_submissions=3)
    assert int(res.leader) == 2
    assert c.rejected_votes[0] == {3: "forged-envelope"}


def test_contract_rejects_unbound_envelope_at_submit():
    n = 3
    c = VoteTallyContract(n, public_keys={i: _KPS[i].public_key
                                          for i in range(n)})
    env = SignedEnvelope.seal("vote", 0, 0,
                              vote_payload_digest(0, 0, 2, _preds(n, 2)),
                              _KPS[0].private_key)
    with pytest.raises(Exception, match="does not bind"):
        c.submit(VoteSubmission(0, 0, 1, _preds(n, 1), env))


def test_contract_forged_vote_cannot_prop_up_quorum():
    n = 3
    pks = {i: _KPS[i].public_key for i in range(n)}
    c = VoteTallyContract(n, public_keys=pks)
    c.submit(VoteSubmission.signed(0, 0, 1, _preds(n, 1),
                                   _KPS[0].private_key))
    forged = SignedEnvelope.seal(
        "vote", 0, 1, vote_payload_digest(1, 0, 1, _preds(n, 1)),
        _KPS[7].private_key)
    c.submit(VoteSubmission(1, 0, 1, _preds(n, 1), forged))
    with pytest.raises(Exception, match="1/2 submissions"):
        c.tally(0, min_submissions=2)
