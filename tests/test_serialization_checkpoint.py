"""Deterministic pytree serialization + checkpointing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.core.serialization import (deserialize_pytree_flat, serialize_pytree,
                                      serialize_pytrees)


def _tree(rng):
    return {"a": rng.normal(size=(3, 4)).astype(np.float32),
            "nested": {"b": rng.integers(0, 9, size=(5,)).astype(np.int32),
                       "c": rng.normal(size=()).astype(np.float64)}}


def test_roundtrip(rng):
    t = _tree(rng)
    flat = deserialize_pytree_flat(serialize_pytree(t))
    assert len(flat) == 3
    by_suffix = {k.split("'")[-2]: v for k, v in flat.items()}
    np.testing.assert_array_equal(by_suffix["a"], t["a"])
    np.testing.assert_array_equal(by_suffix["b"], t["nested"]["b"])
    np.testing.assert_array_equal(by_suffix["c"], t["nested"]["c"])


def test_serialization_key_order_invariant(rng):
    a = rng.normal(size=(2, 2)).astype(np.float32)
    b = rng.normal(size=(3,)).astype(np.float32)
    t1 = {"x": a, "y": b}
    t2 = dict([("y", b), ("x", a)])      # different insertion order
    assert serialize_pytree(t1) == serialize_pytree(t2)


def test_serialization_sensitive_to_values(rng):
    t = _tree(rng)
    s1 = serialize_pytree(t)
    t["a"][0, 0] += 1e-3
    assert serialize_pytree(t) != s1


@settings(deadline=None, max_examples=15)
@given(n=st.integers(1, 30), m=st.integers(1, 7))
def test_serialization_roundtrip_property(n, m):
    rng = np.random.default_rng(n * 100 + m)
    t = {"w": rng.normal(size=(n, m)).astype(np.float32)}
    flat = deserialize_pytree_flat(serialize_pytree(t))
    (arr,) = flat.values()
    np.testing.assert_array_equal(arr, t["w"])


def test_checkpoint_roundtrip(tmp_path, rng):
    t = _tree(rng)
    save_checkpoint(tmp_path, 7, t, metadata={"loss": 1.5})
    assert latest_step(tmp_path) == 7
    loaded = load_checkpoint(tmp_path, 7, t)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_integrity_check(tmp_path, rng):
    t = _tree(rng)
    save_checkpoint(tmp_path, 1, t)
    # corrupt the payload
    import numpy as _np
    path = tmp_path / "step_1.npz"
    data = dict(_np.load(path))
    data["leaf_0"] = data["leaf_0"] + 1.0
    _np.savez(path, **data)
    with pytest.raises(ValueError):
        load_checkpoint(tmp_path, 1, t)


def test_checkpoint_multiple_steps(tmp_path, rng):
    t = _tree(rng)
    for s in (1, 5, 3):
        save_checkpoint(tmp_path, s, t)
    assert latest_step(tmp_path) == 5


def _reference_bytes(tree):
    """The canonical layout written out plainly: each leaf copied by
    ``tobytes`` (the encoding every commitment, digest and block was
    made with before the packing went to zero-copy views)."""
    import struct
    leaves = sorted(jax.tree_util.tree_flatten_with_path(tree)[0],
                    key=lambda kv: jax.tree_util.keystr(kv[0]))
    out = [b"RPR0", struct.pack("<I", len(leaves))]
    for path, leaf in leaves:
        arr = np.asarray(leaf)
        path_b = jax.tree_util.keystr(path).encode()
        dtype_b = arr.dtype.str.encode()
        raw = np.ascontiguousarray(arr).tobytes()
        out += [struct.pack("<I", len(path_b)), path_b,
                struct.pack("<I", len(dtype_b)), dtype_b,
                struct.pack("<I", arr.ndim),
                struct.pack(f"<{arr.ndim}q", *arr.shape),
                struct.pack("<Q", len(raw)), raw]
    return b"".join(out)


_LEAVES = {
    "float32": lambda r: r.normal(size=(3, 5)).astype(np.float32),
    "bfloat16": lambda r: r.normal(size=(4, 3)).astype(jnp.bfloat16),
    "bool": lambda r: r.integers(0, 2, size=(7,)).astype(bool),
    "int8": lambda r: r.integers(-128, 128, size=(2, 3, 2)).astype(np.int8),
    "0-d": lambda r: np.asarray(r.normal(), dtype=np.float32),
    "transposed": lambda r: r.normal(size=(3, 4)).astype(np.float32).T,
}


@pytest.mark.parametrize("on_device", [False, True], ids=["numpy", "jax"])
@pytest.mark.parametrize("kind", sorted(_LEAVES))
def test_packing_matches_the_tobytes_encoding(kind, on_device):
    """The zero-copy packing gives the old ``tobytes`` bytes for every
    dtype and layout, alone and in the batched ``serialize_pytrees``, and
    the bytes read back to the same values."""
    rng = np.random.default_rng(len(kind))
    leaf = _LEAVES[kind](rng)
    if kind == "transposed":
        assert not leaf.flags.c_contiguous
    trees = [{"w": leaf, "b": np.arange(3, dtype=np.float32) + i}
             for i in range(3)]
    if on_device:
        trees = [jax.tree.map(jnp.asarray, t) for t in trees]
    want = [_reference_bytes(t) for t in trees]
    assert [serialize_pytree(t) for t in trees] == want
    assert serialize_pytrees(trees) == want
    back, orig = deserialize_pytree_flat(want[0])["['w']"], np.asarray(
        trees[0]["w"])
    # bfloat16 has no dtype string of its own: it reads back as '<V2',
    # the same shape and bytes
    assert back.shape == orig.shape and back.tobytes() == orig.tobytes()
    if kind != "bfloat16":
        assert back.dtype == orig.dtype
        np.testing.assert_array_equal(back, orig)
