"""Differential backend parity for the secp256k1 point-arithmetic seam.

Every backend — ``naive`` (Jacobian double-and-add), ``batch``
(fixed-window tables per message, GLV + wNAF/Pippenger MSM behind the RLC
batch equation) and ``glv`` (a uniform-schedule signing ladder, the same
MSM forced onto the interleaved-wNAF engine) — must agree with the single
source of truth, the per-message ``dverify`` predicate under ``naive``,
on every input shape: valid tags, forged tags, tampered recovery bits,
and bare ``(r, s)`` pairs. Property-driven via the optional-hypothesis
shim.

The curve layer is pinned separately: the Jacobian formulas (including
the batched-inversion window-table build) must reproduce the affine
formulas bit-for-bit.
"""

import random

import pytest
from _hypothesis_compat import given, settings, st

from repro.core import crypto
from repro.core.crypto import curve, field

BACKENDS = list(crypto.BACKENDS)

_KPS = [crypto.ECDSAKeyPair.generate(bytes([i, 0xBE])) for i in range(6)]


def _batch(n):
    items = []
    for i in range(n):
        d = crypto.sha256_digest(b"parity", bytes([i]))
        items.append((crypto.dsign(d, _KPS[i].private_key),
                      _KPS[i].public_key, d))
    return items


def _mutate(item, shape):
    """One input shape per code path verify_batch must route correctly."""
    tag, pk, d = item
    if shape == "valid":
        return item
    if shape == "bare-pair":            # legacy (r, s): singles fallback
        return ((tag.r, tag.s), pk, d)
    if shape == "flipped-v":            # defeats the equation, not dverify
        return (crypto.Signature(tag.r, tag.s, tag.v ^ 1), pk, d)
    if shape == "forged-s":
        return (crypto.Signature(tag.r, tag.s ^ 0x2, tag.v), pk, d)
    if shape == "forged-digest":
        return (tag, pk, crypto.sha256_digest(d))
    raise AssertionError(shape)


_SHAPES = ("valid", "bare-pair", "flipped-v", "forged-s", "forged-digest")
_ACCEPTED = {"valid", "bare-pair", "flipped-v"}


# ---------------------------------------------------------------------------
# dsign / dverify parity
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000))
def test_dsign_dverify_agree_across_backends(seed):
    kp = crypto.ECDSAKeyPair.generate(seed.to_bytes(4, "big"))
    d = crypto.sha256_digest(seed.to_bytes(4, "big"))
    tags = {}
    for be in BACKENDS:
        with crypto.use_backend(be):
            tags[be] = crypto.dsign(d, kp.private_key)
            assert crypto.dverify(tags[be], kp.public_key, d), be
            assert not crypto.dverify(tags[be], kp.public_key,
                                      crypto.sha256_digest(d)), be
    # deterministic RFC-6979 nonces ⇒ bit-identical tags everywhere
    assert len(set(tags.values())) == 1


# ---------------------------------------------------------------------------
# verify_batch parity: accept-iff-dverify under every backend
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(n=st.integers(1, 4), mask=st.integers(0, 15),
       shape=st.sampled_from(_SHAPES[1:]))
def test_verify_batch_parity_across_backends(n, mask, shape):
    items = _batch(n)
    mutated = [i for i in range(n) if (mask >> i) & 1]
    for i in mutated:
        items[i] = _mutate(items[i], shape)
    with crypto.use_backend("naive"):
        individually = [crypto.dverify(t, pk, d) for t, pk, d in items]
    expected_bad = [i for i, ok in enumerate(individually) if not ok]
    if shape in _ACCEPTED:
        assert not expected_bad
    results = {be: crypto.verify_batch(items, backend=be)
               for be in BACKENDS}
    for be, res in results.items():
        assert res.ok == all(individually), (be, shape)
        assert list(res.bad) == expected_bad, (be, shape)


@settings(max_examples=6, deadline=None)
@given(n=st.integers(2, 4), forged=st.integers(0, 3))
def test_deduplicated_receiver_copies_parity(n, forged):
    """The round workload — every receiver re-checks every sender — yields
    identical per-copy attribution under every backend."""
    forged %= n
    items = _batch(n)
    items[forged] = _mutate(items[forged], "forged-s")
    copies = [it for it in items for _ in range(n - 1)]
    expected = tuple(range(forged * (n - 1), (forged + 1) * (n - 1)))
    for be in BACKENDS:
        res = crypto.verify_batch(copies, backend=be)
        assert not res.ok and res.bad == expected, be


def test_mixed_shapes_one_batch_all_backends():
    """Valid, bare-pair, flipped-v, and two forgery kinds in ONE batch:
    the accept set is exactly the individually-valid items everywhere."""
    items = [_mutate(it, shape)
             for it, shape in zip(_batch(len(_SHAPES)), _SHAPES)]
    expected = (3, 4)                   # the two forged shapes
    for be in BACKENDS:
        res = crypto.verify_batch(items, backend=be)
        assert res.bad == expected, be


# ---------------------------------------------------------------------------
# curve-layer pins: Jacobian == affine baseline
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_jacobian_matches_affine_scalar_mul(seed):
    k = (seed * 0x9E3779B97F4A7C15 + 1) % curve.N
    table = curve.g_table()
    affine = curve.affine_point_mul_windowed(k, table)
    assert curve.point_mul_windowed(k, table) == affine
    assert curve.point_mul_naive(k, curve.G) == affine


@settings(max_examples=8, deadline=None)
@given(a=st.integers(1, 1 << 128), b=st.integers(1, 1 << 128))
def test_jacobian_multi_scalar_matches_affine(a, b):
    pk = _KPS[0].public_key
    pairs = [(a, curve.G), (b, pk)]
    assert curve.multi_scalar(pairs) == curve.affine_multi_scalar(pairs)
    assert curve.strauss_shamir(a, curve.G, b, pk) == \
        curve.affine_multi_scalar(pairs)


def test_window_table_batched_inversion_matches_affine_build():
    """The Jacobian table build (one batched inversion for all 64×15
    entries) must produce exactly the affine baseline's table: d·16^w·Q
    via repeated affine adds."""
    q = _KPS[1].public_key
    table = curve.build_window_table(q)
    base = q
    for w in range(4):                  # spot-check the first few windows
        expect = curve.INF
        for d in range(15):
            expect = curve.affine_point_add(expect, base)
            assert table[w][d] == expect
        for _ in range(curve._WINDOW_BITS):
            base = curve.affine_point_add(base, base)


# ---------------------------------------------------------------------------
# GLV decomposition + endomorphism
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 1 << 62))
def test_glv_decompose_recomposes_with_short_halves(seed):
    """k₁ + k₂·λ ≡ k (mod n) and both halves fit in 129 signed bits —
    the property that lets the ladders run half-length."""
    k = (seed * 0x9E3779B97F4A7C15 + seed + 1) % curve.N
    k1, k2 = curve.glv_decompose(k)
    assert (k1 + k2 * curve.GLV_LAMBDA) % curve.N == k % curve.N
    assert abs(k1) < 1 << 129 and abs(k2) < 1 << 129


def test_glv_decompose_edge_scalars():
    for k in (0, 1, 2, curve.N - 1, curve.N // 2, curve.GLV_LAMBDA,
              curve.N + 7):
        k1, k2 = curve.glv_decompose(k)
        assert (k1 + k2 * curve.GLV_LAMBDA) % curve.N == k % curve.N, k
        assert abs(k1) < 1 << 129 and abs(k2) < 1 << 129, k


def test_endomorphism_is_lambda_mul():
    """φ(P) = (β·x, y) must equal λ·P — one field mul standing in for a
    whole scalar multiplication."""
    pk = _KPS[0].public_key
    assert curve.endo(pk) == curve.point_mul_naive(curve.GLV_LAMBDA, pk)
    assert curve.endo(curve.INF) == curve.INF


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(1, 1 << 62))
def test_wnaf_digits_reconstruct(seed):
    """Sparse wNAF invariants: Σ d·2^pos == k, digits odd and in
    (−2^(w−1), 2^(w−1))."""
    k = (seed * 0xD1B54A32D192ED03 + 1) % curve.N or 1
    for w in (curve._FRESH_W, curve._MSM_W):
        digits = curve.wnaf_digits(k, w)
        assert sum(d << pos for pos, d in digits) == k
        assert all(d & 1 and abs(d) < 1 << (w - 1) for _, d in digits)


# ---------------------------------------------------------------------------
# MSM engines vs the naive reference
# ---------------------------------------------------------------------------

def _msm_cases():
    pk0, pk1 = _KPS[0].public_key, _KPS[1].public_key
    neg1 = (pk1[0], (-pk1[1]) % field.P)
    big = curve.N - 2
    return [
        [],
        [(0, curve.G)],                              # k = 0 drops out
        [(5, curve.INF)],                            # P = ∞ drops out
        [(1, curve.G)],
        [(big, pk0), (3, pk0), (3, pk0)],            # duplicate points
        [(123456789, curve.G), (big, pk0), (0, pk1), (7, curve.INF)],
        [(curve.N - 1, pk0), (curve.N + 5, pk1)],    # k ≥ N reduces
        [(big, pk1), (big, neg1)],                   # pair cancels to ∞
    ]


def test_msm_engines_match_naive_multi_scalar():
    for pairs in _msm_cases():
        ref = curve.multi_scalar(pairs)
        assert curve.msm(fresh_pairs=pairs, engine="wnaf") == ref, pairs
        assert curve.msm(fresh_pairs=pairs, engine="pippenger") == ref, pairs
        assert curve.msm(base_pairs=pairs) == ref, pairs


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_msm_random_matches_naive(seed):
    rng = random.Random(seed)
    points = [curve.G, _KPS[0].public_key, _KPS[1].public_key,
              curve.endo(_KPS[2].public_key)]
    pairs = [(rng.randrange(0, curve.N), p) for p in points]
    ref = curve.multi_scalar(pairs)
    assert curve.msm(fresh_pairs=pairs, engine="pippenger") == ref
    assert curve.msm(fresh_pairs=pairs, engine="wnaf") == ref
    assert curve.msm(base_pairs=pairs) == ref
    split = len(pairs) // 2
    assert curve.msm(base_pairs=pairs[:split],
                     fresh_pairs=pairs[split:]) == ref


def test_msm_rejects_unknown_engine():
    with pytest.raises(ValueError):
        curve.msm_jc(fresh_pairs=[(1, curve.G)], engine="strauss")


# ---------------------------------------------------------------------------
# uniform-schedule fixed-base ladder
# ---------------------------------------------------------------------------

def test_ct_base_mul_matches_naive_edges():
    for k in (0, 1, 2, 3, curve.N - 1, curve.N // 2, (1 << 255) % curve.N):
        assert curve.point_mul_base_ct(k) == \
            curve.point_mul_naive(k, curve.G), k


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1 << 62))
def test_ct_base_mul_matches_naive_random(seed):
    k = (seed * 0xA0761D6478BD642F + seed) % curve.N
    assert curve.point_mul_base_ct(k) == curve.point_mul_naive(k, curve.G)


# ---------------------------------------------------------------------------
# cache bounds: the per-key tables must not grow without bound
# ---------------------------------------------------------------------------

def _distinct_points(n):
    return [curve.point_mul_naive(i + 2, curve.G) for i in range(n)]


def test_msm_table_cache_is_bounded_lru(monkeypatch):
    monkeypatch.setattr(curve, "_MSM_CACHE_MAX", 4)
    curve._MSM_TABLES.clear()
    pts = _distinct_points(6)
    for p in pts:
        curve.msm_table(p)
    assert len(curve._MSM_TABLES) == 4
    assert pts[0] not in curve._MSM_TABLES      # oldest evicted
    assert pts[-1] in curve._MSM_TABLES
    curve.msm_table(pts[2])                     # touch → most recent
    curve.msm_table(_distinct_points(7)[-1])    # insert → evicts pts[3]
    assert pts[2] in curve._MSM_TABLES
    assert pts[3] not in curve._MSM_TABLES
    curve._MSM_TABLES.clear()


def test_pk_table_cache_is_bounded_lru(monkeypatch):
    monkeypatch.setattr(curve, "_PK_CACHE_MAX", 4)
    curve._PK_TABLES.clear()
    pts = _distinct_points(6)
    for p in pts:
        curve.pk_table(p)
    assert len(curve._PK_TABLES) == 4
    assert pts[0] not in curve._PK_TABLES
    curve.pk_table(pts[2])                      # touch → survives the next
    curve.pk_table(_distinct_points(7)[-1])     # insert (evicts pts[3])
    assert pts[2] in curve._PK_TABLES
    assert pts[3] not in curve._PK_TABLES
    curve._PK_TABLES.clear()


def test_g_msm_table_not_in_lru():
    """The base-point table is pinned module-global — it must never
    occupy (or be evicted from) the bounded public-key LRU."""
    t = curve.msm_table(curve.G)
    assert t is curve.g_msm_table()
    assert curve.G not in curve._MSM_TABLES


# ---------------------------------------------------------------------------
# Pippenger engine drives the full verify path
# ---------------------------------------------------------------------------

def test_bisection_parity_with_pippenger_engine(monkeypatch):
    """Force the bucket engine under the batch backend's RLC equation:
    forgery attribution must be identical to the default engine."""
    from repro.core.crypto.backends.python import BatchOps
    monkeypatch.setattr(BatchOps, "msm_engine", "pippenger")
    items = _batch(6)
    items[2] = _mutate(items[2], "forged-s")
    items[5] = _mutate(items[5], "forged-digest")
    res = crypto.verify_batch(items, backend="batch")
    assert not res.ok and res.bad == (2, 5)
    assert crypto.verify_batch(_batch(5), backend="batch").ok
