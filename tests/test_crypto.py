"""HCDS crypto primitives: SHA-256 commitment + secp256k1 ECDSA."""

import hashlib

import pytest

from repro.core import crypto
from repro.core.crypto import curve, field


def test_sha256_matches_hashlib():
    assert crypto.sha256_digest(b"ab", b"cd") == hashlib.sha256(b"abcd").digest()


def test_keypair_deterministic_from_seed():
    k1 = crypto.ECDSAKeyPair.generate(b"seed")
    k2 = crypto.ECDSAKeyPair.generate(b"seed")
    assert k1 == k2
    k3 = crypto.ECDSAKeyPair.generate(b"other")
    assert k1.private_key != k3.private_key


def test_sign_verify_roundtrip():
    kp = crypto.ECDSAKeyPair.generate(b"node-0")
    d = crypto.sha256_digest(b"model bytes")
    tag = crypto.dsign(d, kp.private_key)
    assert crypto.dverify(tag, kp.public_key, d)


def test_verify_rejects_wrong_digest():
    kp = crypto.ECDSAKeyPair.generate(b"node-0")
    tag = crypto.dsign(crypto.sha256_digest(b"m"), kp.private_key)
    assert not crypto.dverify(tag, kp.public_key, crypto.sha256_digest(b"m2"))


def test_verify_rejects_wrong_key():
    kp0 = crypto.ECDSAKeyPair.generate(b"node-0")
    kp1 = crypto.ECDSAKeyPair.generate(b"node-1")
    d = crypto.sha256_digest(b"m")
    tag = crypto.dsign(d, kp0.private_key)
    assert not crypto.dverify(tag, kp1.public_key, d)


def test_verify_rejects_malformed_signature():
    kp = crypto.ECDSAKeyPair.generate(b"node-0")
    d = crypto.sha256_digest(b"m")
    assert not crypto.dverify((0, 1), kp.public_key, d)
    assert not crypto.dverify((1, 0), kp.public_key, d)


def test_signature_deterministic_rfc6979():
    kp = crypto.ECDSAKeyPair.generate(b"node-0")
    d = crypto.sha256_digest(b"m")
    assert crypto.dsign(d, kp.private_key) == crypto.dsign(d, kp.private_key)


def test_public_key_on_curve():
    kp = crypto.ECDSAKeyPair.generate(b"x")
    x, y = kp.public_key
    p = field.P
    assert (y * y - (x * x * x + 7)) % p == 0


def test_dsign_retry_signs_the_original_digest(monkeypatch):
    """The r==0/s==0 retry must re-randomize the RFC-6979 nonce, NOT the
    message: a retried signature still verifies against the caller's
    digest. Forced here by handing dsign k=1 for a private key crafted so
    that s = z + r·priv ≡ 0 (mod n)."""
    digest = crypto.sha256_digest(b"retry me")
    z = crypto._bits2int(digest)
    r = curve.GX % curve.N                      # k=1 → R = G
    priv = (curve.N - z) * field.inv_mod(r, curve.N) % curve.N
    pub = curve.point_mul_naive(priv, curve.G)

    calls = []
    real = crypto._rfc6979_k

    def forced(msg_hash, key, extra=b""):
        calls.append((msg_hash, extra))
        if len(calls) == 1:
            return 1                            # s == 0 → must retry
        return real(msg_hash, key, extra=extra)

    monkeypatch.setattr(crypto, "_rfc6979_k", forced)
    tag = crypto.dsign(digest, priv)
    assert len(calls) == 2
    # the retry re-seeded the DRBG instead of mutating the message
    assert [h for h, _ in calls] == [digest, digest]
    assert calls[0][1] != calls[1][1]
    assert crypto.dverify(tag, pub, digest)
    # and the retried tag is batch-compatible like any other
    assert crypto.verify_batch([(tag, pub, digest)]).ok
