"""JAX limb-vectorized secp256k1 backend (``set_backend("jax")``).

The round-level RLC batch equation

    (Σ aᵢ·u1ᵢ)·G + Σ (aᵢ·u2ᵢ)·PKᵢ − Σ aᵢ·Rᵢ == ∞

is evaluated as ONE jitted multi-scalar program over all N deduplicated
signatures — the first time the blockchain control plane rides the same
JAX substrate as the FEL engine. Representation:

* a field element is 8 little-endian 32-bit limbs held in uint64 lanes,
  shape ``(lanes, 8)`` — products of two limbs fit a uint64, and the 8×8
  schoolbook columns accumulate lazily as split lo/hi halves (bounded by
  2^36) before one carry propagation;
* reduction mod p = 2^256 − 2^32 − 977 folds the high half as
  H·(2^32 + 977) (two foldings + one conditional subtract; every field op
  returns a fully reduced element);
* points are Jacobian ``(X, Y, Z)`` limb triples; add/double are the same
  inversion-free formulas as ``curve.py``. The mixed-add ladder step
  deliberately omits the P == Q exceptional branch: for honest inputs the
  accumulator collides with a table point with probability ~2^-250 under
  the fresh random batch coefficients, a collision only *fails* the
  equation (H = 0 zeroes Z3), and a failing equation falls back through
  bisection to the Python ``dverify`` predicate — wrong-but-safe, never
  falsely accepting;
* each signature is one lane running a joint GLV Strauss–Shamir ladder.
  The PK scalar a·u2 splits into two ~128-bit halves against the
  secp256k1 endomorphism (``curve.glv_decompose``), so a lane's three
  logical terms are b₁·(±PK) + b₂·(±φPK) + a·(−R) with every scalar
  ≤ 130 bits: the ladder runs 130 shared double steps (down from 256)
  over a per-lane 8-entry subset-sum table
  ``[∅, P₁, P₂, P₁+P₂, P₃, P₁+P₃, P₂+P₃, P₁+P₂+P₃]`` with one masked
  mixed add per step. The combination tables are built host-side in
  Jacobian form and normalized with a single zero-skipping
  ``field.batch_inv`` (an adversarial PK = R collision makes a combo
  the point at infinity — its lanes mask off, which is exactly "add
  nothing"). Per-lane accumulators are folded on the host (≤ lanes
  big-int adds — not worth a device kernel).

Lanes are padded to the next power of two, so the kernel compiles once
per size bucket (the same shape-bucketing contract as the batched FEL
engine). Compiled buckets are AOT-cached on disk via ``..aotcache``:
``jax.export`` blobs skip trace+lowering, and the persistent XLA
compilation cache skips the backend compile — a fresh process warm
starts in well under a second instead of ~15 s. Per-message operations
(``dsign``/``dverify``) delegate to the windowed Python path — a single
scalar multiplication has no lanes to vectorize over.

Everything runs under ``jax.experimental.enable_x64`` scoped contexts:
the global x64 flag stays off, so the FEL engine's float32 programs are
untouched.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

try:  # gate: the crypto API must import fine on jax-less installs
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import enable_x64
    HAS_JAX = True
    _IMPORT_ERROR: Exception | None = None
except Exception as e:  # pragma: no cover - exercised on jax-less installs
    HAS_JAX = False
    _IMPORT_ERROR = e

from ..curve import (JPoint, Point, endo, g_table, glv_decompose, jc_add,
                     jc_is_inf, point_mul_windowed_jc)
from ..curve import N as _N
from ..field import P as _P
from ..field import batch_inv
from .python import BatchOps, RLCItem, rlc_coefficient
from repro.obs import get_recorder

_LIMBS = 8
_LBITS = 32
_MASK32 = (1 << 32) - 1
_FOLD = 977          # 2^256 ≡ 2^32 + 977 (mod p)

_P_LIMBS_HOST = [(_P >> (_LBITS * i)) & _MASK32 for i in range(_LIMBS)]


# ---------------------------------------------------------------------------
# host <-> limb conversion
# ---------------------------------------------------------------------------

def to_limbs(x: int) -> np.ndarray:
    return np.array([(x >> (_LBITS * i)) & _MASK32 for i in range(_LIMBS)],
                    dtype=np.uint64)


def from_limbs(arr) -> int:
    out = 0
    for i, limb in enumerate(np.asarray(arr, dtype=np.uint64).tolist()):
        out |= int(limb) << (_LBITS * i)
    return out


def scalar_bits(k: int) -> np.ndarray:
    """(256,) uint8, most-significant bit first."""
    return np.unpackbits(
        np.frombuffer((k % (1 << 256)).to_bytes(32, "big"), dtype=np.uint8))


def scalar_bits_n(k: int, nbits: int) -> np.ndarray:
    """(nbits,) uint8, most-significant bit first (GLV half scalars)."""
    nbytes = (nbits + 7) // 8
    bits = np.unpackbits(
        np.frombuffer((k % (1 << nbits)).to_bytes(nbytes, "big"),
                      dtype=np.uint8))
    return bits[-nbits:]


# ---------------------------------------------------------------------------
# field arithmetic on (..., 8) uint64 limb arrays (fully reduced invariant)
# ---------------------------------------------------------------------------
# Carry/borrow chains unroll statically at trace time over Python lists of
# per-limb lane arrays; everything else stays stacked.

def _split(a) -> List:
    return [a[..., i] for i in range(a.shape[-1])]


def _join(limbs: List):
    return jnp.stack(limbs, axis=-1)


def _carry_chain(cols: List, n_out: int) -> Tuple[List, "jax.Array"]:
    """Propagate carries over column sums (each < 2^37); returns ``n_out``
    32-bit limbs plus the final carry."""
    out = []
    carry = jnp.zeros_like(cols[0])
    for i in range(n_out):
        v = (cols[i] if i < len(cols) else jnp.zeros_like(cols[0])) + carry
        out.append(v & _MASK32)
        carry = v >> _LBITS
    return out, carry


def _sub_chain(al: List, bl: List) -> Tuple[List, "jax.Array"]:
    """Limbwise a − b with borrow propagation; borrow is 0/1."""
    out = []
    borrow = jnp.zeros_like(al[0])
    for i in range(_LIMBS):
        bi = bl[i] + borrow
        out.append((al[i] - bi) & _MASK32)
        borrow = (al[i] < bi).astype(al[0].dtype)
    return out, borrow


def _cond_sub_p(limbs: List, overflow) -> List:
    """Subtract p iff ``limbs + overflow·2^256 >= p`` (value < 2p)."""
    p = [jnp.full_like(limbs[0], _P_LIMBS_HOST[i]) for i in range(_LIMBS)]
    d, borrow = _sub_chain(limbs, p)
    need = ((overflow > 0) | (borrow == 0))
    return [jnp.where(need, d[i], limbs[i]) for i in range(_LIMBS)]


def _fold_overflow(limbs: List, overflow) -> Tuple[List, "jax.Array"]:
    """Add ``overflow·(2^32 + 977)`` into the low limbs (2^256 ≡ that)."""
    cols = list(limbs)
    cols[0] = cols[0] + overflow * _FOLD
    cols[1] = cols[1] + overflow
    return _carry_chain(cols, _LIMBS)


def ff_add(a, b):
    limbs, carry = _carry_chain([x + y for x, y in zip(_split(a), _split(b))],
                                _LIMBS)
    return _join(_cond_sub_p(limbs, carry))


def ff_sub(a, b):
    d, borrow = _sub_chain(_split(a), _split(b))
    cols = [d[i] + borrow * _P_LIMBS_HOST[i] for i in range(_LIMBS)]
    limbs, _ = _carry_chain(cols, _LIMBS)   # carry-out cancels the borrow
    return _join(limbs)


def ff_small(a, m: int):
    """a·m for a small constant m (2, 3, 4, 8): limbwise multiply + fold."""
    limbs, carry = _carry_chain([x * m for x in _split(a)], _LIMBS)
    limbs, carry = _fold_overflow(limbs, carry)          # carry < m
    limbs, carry = _fold_overflow(limbs, carry)          # carry now 0/1
    return _join(_cond_sub_p(limbs, carry))


def ff_mul(a, b):
    # 8×8 schoolbook with lazily-split columns: lo halves land in column
    # i+j, hi halves in i+j+1; each column sums ≤ 16 values < 2^32.
    prod = a[..., :, None] * b[..., None, :]             # (..., 8, 8)
    lo = prod & _MASK32
    hi = prod >> _LBITS
    cols = jnp.zeros(a.shape[:-1] + (2 * _LIMBS,), dtype=a.dtype)
    for i in range(_LIMBS):
        cols = cols.at[..., i:i + _LIMBS].add(lo[..., i, :])
        cols = cols.at[..., i + 1:i + 1 + _LIMBS].add(hi[..., i, :])
    m, _ = _carry_chain(_split(cols), 2 * _LIMBS)        # < p² < 2^512
    # fold the high half: v = L + H·(2^32 + 977)  (≤ 10 limbs)
    lo8, hi8 = m[:_LIMBS], m[_LIMBS:]
    cols2 = [jnp.zeros_like(lo8[0]) for _ in range(_LIMBS + 2)]
    for i in range(_LIMBS):
        cols2[i] = cols2[i] + lo8[i] + hi8[i] * _FOLD
        cols2[i + 1] = cols2[i + 1] + hi8[i]
    v, _ = _carry_chain(cols2, _LIMBS + 2)
    top = v[_LIMBS] + (v[_LIMBS + 1] << _LBITS)          # value >> 256, < 2^33
    limbs, carry = _fold_overflow(v[:_LIMBS], top)
    limbs, carry = _fold_overflow(limbs, carry)
    return _join(_cond_sub_p(limbs, carry))


def ff_sqr(a):
    return ff_mul(a, a)


def ff_is_zero(a):
    return jnp.all(a == 0, axis=-1)


# ---------------------------------------------------------------------------
# Jacobian point ops on limb lanes
# ---------------------------------------------------------------------------

def _sel(mask, a, b):
    """Lane-masked select over limb arrays (mask shape (...,))."""
    return jnp.where(mask[..., None], a, b)


def jc_double_v(X, Y, Z):
    """dbl-2009-l (a = 0); an infinity lane (Z = 0) stays at infinity."""
    A_ = ff_sqr(X)
    B_ = ff_sqr(Y)
    C = ff_sqr(B_)
    D = ff_small(ff_sub(ff_sub(ff_sqr(ff_add(X, B_)), A_), C), 2)
    E = ff_small(A_, 3)
    X3 = ff_sub(ff_sqr(E), ff_small(D, 2))
    Y3 = ff_sub(ff_mul(E, ff_sub(D, X3)), ff_small(C, 8))
    Z3 = ff_small(ff_mul(Y, Z), 2)
    return X3, Y3, Z3


def jc_add_mixed_v(X1, Y1, Z1, x2, y2, use):
    """Per-lane P + (x2, y2) (madd-2007-bl); ``use`` masks lanes that add.

    Handles P at infinity and P == −Q (H = 0 zeroes Z3). The P == Q case
    also lands on Z3 = 0 — *wrong* (it should double) but safe: the sum
    stops matching, the equation fails, and bisection's dverify leaves
    decide. See the module docstring for why that trade is sound.
    """
    Z1Z1 = ff_sqr(Z1)
    U2 = ff_mul(x2, Z1Z1)
    S2 = ff_mul(y2, ff_mul(Z1, Z1Z1))
    H = ff_sub(U2, X1)
    r = ff_small(ff_sub(S2, Y1), 2)
    HH = ff_sqr(H)
    I = ff_small(HH, 4)
    J = ff_mul(H, I)
    V = ff_mul(X1, I)
    X3 = ff_sub(ff_sub(ff_sqr(r), J), ff_small(V, 2))
    Y3 = ff_sub(ff_mul(r, ff_sub(V, X3)), ff_small(ff_mul(Y1, J), 2))
    Z3 = ff_sub(ff_sub(ff_sqr(ff_add(Z1, H)), Z1Z1), HH)
    p_inf = ff_is_zero(Z1)
    one = jnp.zeros_like(X1).at[..., 0].set(1)
    X3 = _sel(p_inf, x2, X3)
    Y3 = _sel(p_inf, y2, Y3)
    Z3 = _sel(p_inf, one, Z3)
    keep = ~use
    return (_sel(keep, X1, X3), _sel(keep, Y1, Y3), _sel(keep, Z1, Z3))


# ---------------------------------------------------------------------------
# the batch-equation kernel
# ---------------------------------------------------------------------------

def _rlc_kernel(step_x, step_y, step_use):
    """Joint Strauss–Shamir ladder over every lane.

    The per-step addends are pre-gathered on the host (digit lookup into
    each lane's [∅, PK, −R, PK−R] table is cheap numpy fancy indexing, and
    hoisting it out of the loop body keeps the compiled step pure limb
    arithmetic):

    step_x/step_y: (256, L, 8) uint64 — MSB-first ladder addends;
    step_use:      (256, L) bool — False steps add nothing.
    Returns per-lane Jacobian (X, Y, Z) limbs; the host folds the lanes.
    """
    L = step_x.shape[1]
    zeros = jnp.zeros((L, _LIMBS), dtype=step_x.dtype)
    one = zeros.at[:, 0].set(1)
    state = (one, one, zeros)           # all lanes start at infinity

    def body(j, state):
        X, Y, Z = jc_double_v(*state)
        return jc_add_mixed_v(X, Y, Z, step_x[j], step_y[j], step_use[j])

    return lax.fori_loop(0, step_x.shape[0], body, state)


# GLV ladder length: half scalars are < 2^129, the −R coefficient is
# 128-bit — 130 steps covers both with margin.
_GLV_STEPS = 130
_SLOTS = 8

# pow-2 lane counts the kernel has already been readied for — the first
# call in a new bucket pays AOT load (or XLA compilation), later calls
# only execute. Tracked here (not in the recorder) so the
# compile/execute attribution is correct across recorder swaps within
# one process.
_COMPILED_LANE_BUCKETS: set = set()

# L -> (callable, source) where source is "aot" (deserialized export
# blob) or "jit" (freshly traced this process, then exported to disk)
_KERNELS: dict = {}


def _get_compiled(lanes: int, steps: int = _GLV_STEPS):
    """The compiled ladder for a lane bucket, AOT-cached on disk.

    Cache discipline (must hold under ``enable_x64``): try the
    serialized ``jax.export`` blob first — deserialization skips
    trace + lowering; a miss traces and jits, then best-effort exports
    the blob for the next process. Either way the persistent XLA
    compilation cache (``repro.compile_cache``) absorbs the
    backend-compile step across processes.
    """
    ent = _KERNELS.get(lanes)
    if ent is not None:
        return ent
    from repro import compile_cache
    from .. import aotcache
    compile_cache.enable()
    fn = None
    source = "jit"
    blob = aotcache.load_kernel(steps, lanes)
    if blob is not None:
        try:
            from jax import export as jax_export
            fn = jax_export.deserialize(blob).call
            source = "aot"
        except Exception:  # pragma: no cover - stale/corrupt blob
            fn = None
    if fn is None:
        jitted = jax.jit(_rlc_kernel)
        fn = jitted
        try:
            from jax import export as jax_export
            sds = jax.ShapeDtypeStruct
            exported = jax_export.export(jitted)(
                sds((steps, lanes, _LIMBS), jnp.uint64),
                sds((steps, lanes, _LIMBS), jnp.uint64),
                sds((steps, lanes), jnp.bool_))
            aotcache.save_kernel(steps, lanes, exported.serialize())
            # execute through the exported kernel here too: its XLA
            # compile caches under the same persistent-cache key a
            # future process's *deserialized* blob will look up (the
            # plain jit path hashes differently and would leave that
            # process cold)
            fn = exported.call
        except Exception:  # pragma: no cover - export unsupported
            pass
    _KERNELS[lanes] = (fn, source)
    return fn, source


def warm_bucket(lanes: int) -> dict:
    """Ready one lane bucket and run it once on dummy inputs, timing the
    load and first-call (compile-absorbing) steps — the aotcache CLI's
    warm/smoke primitive and the bench sweep's cold-vs-warm probe."""
    import time
    info: dict = {"lanes": lanes, "steps": _GLV_STEPS}
    try:
        with enable_x64():
            t0 = time.perf_counter()
            fn, source = _get_compiled(lanes)
            info["source"] = source
            info["load_s"] = time.perf_counter() - t0
            zeros = jnp.zeros((_GLV_STEPS, lanes, _LIMBS), dtype=jnp.uint64)
            use = jnp.zeros((_GLV_STEPS, lanes), dtype=bool)
            t0 = time.perf_counter()
            X, _Y, _Z = fn(zeros, zeros, use)
            np.asarray(X)  # block until ready
            info["first_call_s"] = time.perf_counter() - t0
        _COMPILED_LANE_BUCKETS.add(lanes)
    except Exception as exc:  # pragma: no cover - device/export failure
        info["error"] = f"{type(exc).__name__}: {exc}"
    return info


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


class JaxOps(BatchOps):
    """``batch`` semantics with the RLC equation on the JAX limb kernel."""

    name = "jax"
    batch_equation = True
    #: below this lane count the ladder cannot amortize kernel dispatch —
    #: the Python Jacobian equation wins (bisection leaves land here)
    min_lanes = 2

    def __init__(self):
        if not HAS_JAX:
            raise RuntimeError(
                "crypto backend 'jax' requires jax, which failed to "
                f"import: {_IMPORT_ERROR!r}")

    def rlc_check(self, group: Sequence[RLCItem]) -> bool:
        if len(group) < self.min_lanes:
            return super().rlc_check(group)
        rec = get_recorder()
        if rec.enabled:
            return self._rlc_check_traced(group)
        return self._rlc_check_jax(group)

    def _rlc_check_traced(self, group: Sequence[RLCItem]) -> bool:
        # the kernel is readied once per pow-2 lane bucket (AOT load or
        # XLA compile); splitting that first call out is the
        # compile-vs-execute latency decomposition
        rec = get_recorder()
        L = _next_pow2(len(group))
        warm = L in _COMPILED_LANE_BUCKETS
        with rec.span("crypto.rlc_jax", cat="crypto", group=len(group),
                      lanes=L, compile=not warm):
            result = self._rlc_check_jax(group)
        if not warm:
            _COMPILED_LANE_BUCKETS.add(L)
            _fn, source = _get_compiled(L)
            rec.counter("crypto.jax_lane_bucket_compiles")
            rec.counter(f"crypto.jax_bucket_source_{source}")
        rec.counter("crypto.rlc_jax_calls")
        rec.observe("crypto.rlc_jax_lanes", L)
        return result

    def _rlc_check_jax(self, group: Sequence[RLCItem]) -> bool:
        coeffs = [rlc_coefficient() for _ in group]
        sg = 0
        n = len(group)
        L = _next_pow2(n)
        tx = np.zeros((L, _SLOTS, _LIMBS), dtype=np.uint64)
        ty = np.zeros((L, _SLOTS, _LIMBS), dtype=np.uint64)
        use = np.zeros((L, _SLOTS), dtype=bool)
        digits = np.zeros((_GLV_STEPS, L), dtype=np.int64)
        # per lane: P1 = ±PK, P2 = ±φPK (GLV halves of a·u2, signs folded
        # into the points), P3 = −R with the 128-bit coefficient a
        combos: List[JPoint] = []   # slots 3,5,6,7 per lane, Jacobian
        for lane, (a, (u1, u2, pk, R)) in enumerate(zip(coeffs, group)):
            sg = (sg + a * u1) % _N
            b1, b2 = glv_decompose(a * u2 % _N)
            phi = endo(pk)
            p1 = (pk[0], pk[1] if b1 >= 0 else _P - pk[1])
            p2 = (phi[0], phi[1] if b2 >= 0 else _P - phi[1])
            p3 = (R[0], (-R[1]) % _P)
            j1: JPoint = (p1[0], p1[1], 1)
            j3: JPoint = (p3[0], p3[1], 1)
            c12 = jc_add(j1, (p2[0], p2[1], 1))
            combos.extend((c12, jc_add(j1, j3),
                           jc_add((p2[0], p2[1], 1), j3), jc_add(c12, j3)))
            for slot, pt in ((1, p1), (2, p2), (4, p3)):
                tx[lane, slot] = to_limbs(pt[0])
                ty[lane, slot] = to_limbs(pt[1])
                use[lane, slot] = True
            digits[:, lane] = (scalar_bits_n(abs(b1), _GLV_STEPS)
                               + 2 * scalar_bits_n(abs(b2), _GLV_STEPS)
                               + 4 * scalar_bits_n(a, _GLV_STEPS))
        # one zero-skipping batch inversion normalizes every combo; a
        # Z = 0 combo (adversarial PK/R alignment) stays masked off —
        # adding the point at infinity is exactly "add nothing"
        zinv = batch_inv([c[2] for c in combos])
        for i, ((X, Y, Z), zi) in enumerate(zip(combos, zinv)):
            if Z == 0:
                continue
            lane, slot = divmod(i, 4)
            slot = (3, 5, 6, 7)[slot]
            zi2 = zi * zi % _P
            tx[lane, slot] = to_limbs(X * zi2 % _P)
            ty[lane, slot] = to_limbs(Y * zi2 * zi % _P)
            use[lane, slot] = True
        lanes = np.arange(L)
        step_x = tx[lanes[None, :], digits]           # (130, L, 8)
        step_y = ty[lanes[None, :], digits]
        step_use = use[lanes[None, :], digits]
        with enable_x64():
            fn, _source = _get_compiled(L)
            X, Y, Z = fn(jnp.asarray(step_x), jnp.asarray(step_y),
                         jnp.asarray(step_use))
            X, Y, Z = np.asarray(X), np.asarray(Y), np.asarray(Z)
        _COMPILED_LANE_BUCKETS.add(L)
        # fold the per-lane accumulators + the shared G term on the host
        acc: JPoint = point_mul_windowed_jc(sg, g_table())
        for lane in range(n):
            acc = jc_add(acc, (from_limbs(X[lane]), from_limbs(Y[lane]),
                               from_limbs(Z[lane])))
        return jc_is_inf(acc)
