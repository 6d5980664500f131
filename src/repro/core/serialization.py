"""Deterministic serialization of JAX pytrees for hashing/commitment.

HCDS commits to H(nonce || model); the model is a pytree of arrays, so we
need a canonical byte encoding that is stable across processes: sorted
key-paths, dtype/shape headers, and raw little-endian array bytes.

The same sorted-keypath ordering also defines the canonical flat-vector
view of a model (``flatten_pytree`` / ``unflatten_pytree``) used by ME,
the sharded consensus, and every ``ModelAdapter`` — keeping the byte
encoding and the vector encoding in one module guarantees they agree.
"""

from __future__ import annotations

import struct
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import Recorder, device_nbytes, get_recorder

_MAGIC = b"RPR0"


def _keystr(path) -> str:
    return jax.tree_util.keystr(path)


def _sorted_leaves(tree: Any) -> list:
    """(path, leaf) pairs in canonical sorted-keypath order."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return sorted(leaves, key=lambda kv: _keystr(kv[0]))


def flatten_pytree(tree: Any) -> jax.Array:
    """Canonical (sorted key-path) float32 flat vector of a parameter pytree.

    This ordering matches :func:`serialize_pytree`, so the HCDS commitment
    and the ME similarity computation see the same vector.
    """
    return jnp.concatenate(
        [jnp.ravel(leaf).astype(jnp.float32) for _, leaf in _sorted_leaves(tree)])


def _unflatten_with(flat: Any, template: Any, make_leaf) -> Any:
    """Shared sorted-keypath offset walk for the unflatten variants.

    ``make_leaf(chunk, leaf)`` materializes one leaf from the flat slice
    ``chunk`` (shaped like ``leaf``); the ordering/offset logic — the part
    that must stay in lockstep with :func:`flatten_pytree` and
    :func:`serialize_pytree` — lives only here.
    """
    paths = jax.tree_util.tree_flatten_with_path(template)[0]
    sizes = [int(np.prod(leaf.shape, dtype=np.int64)) if leaf.shape else 1
             for _, leaf in paths]
    n_flat = flat.shape[0] if hasattr(flat, "shape") else flat.size
    if sum(sizes) != n_flat:
        raise ValueError(
            f"flat vector has {n_flat} elements; template needs {sum(sizes)}")
    order = sorted(range(len(paths)), key=lambda i: _keystr(paths[i][0]))
    leaves = [None] * len(paths)
    off = 0
    for i in order:
        leaf = paths[i][1]
        n = sizes[i]
        leaves[i] = make_leaf(flat[off:off + n].reshape(leaf.shape), leaf)
        off += n
    treedef = jax.tree_util.tree_structure(template)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def unflatten_pytree(flat: Any, template: Any) -> Any:
    """Inverse of :func:`flatten_pytree`: rebuild a pytree shaped/dtyped
    like ``template`` from a flat vector (sorted-keypath order)."""
    return _unflatten_with(np.asarray(flat), template,
                           lambda chunk, leaf: jnp.asarray(chunk,
                                                           dtype=leaf.dtype))


def unflatten_pytree_device(flat: Any, template: Any) -> Any:
    """Jit-traceable :func:`unflatten_pytree`: identical sorted-keypath
    layout, but pure jnp slicing so the flat vector never leaves the
    device. The batched FEL runtime uses this to adopt gw(k) without a
    flatten→host→unflatten roundtrip."""
    return _unflatten_with(jnp.asarray(flat), template,
                           lambda chunk, leaf: chunk.astype(leaf.dtype))


def serialize_pytree(tree: Any) -> bytes:
    """Canonical bytes of a pytree of arrays/scalars.

    Layout: MAGIC | n_leaves | for each leaf (sorted by keypath):
    len(path) path | len(dtype) dtype | ndim shape... | nbytes raw-bytes.
    """
    rec = get_recorder()
    if not rec.enabled:
        return _serialize(tree, rec)
    # the wait for every device leaf's host value, and the packing
    with rec.span("serialize", cat="serialization",
                  d2h_bytes=device_nbytes(tree)):
        return _serialize(tree, rec)


def serialize_pytrees(trees: Sequence[Any]) -> list[bytes]:
    """``[serialize_pytree(t) for t in trees]``, byte for byte, with the
    host copy of every device leaf started before the first tree is
    packed: the pulls of trees 1..N-1 run while tree 0 is packed.
    Host (NumPy) leaves pack exactly as :func:`serialize_pytree` packs
    them."""
    trees = list(trees)
    get_recorder().counter("serialize.prefetch_bytes", _prefetch(trees))
    return [serialize_pytree(t) for t in trees]


def _prefetch(trees: Sequence[Any]) -> int:
    """Start the device-to-host copy of every ``jax.Array`` leaf whose
    host value is not cached yet; the bytes started."""
    started = 0
    seen: set[int] = set()
    for leaf in jax.tree.leaves(trees):
        if (isinstance(leaf, jax.Array) and id(leaf) not in seen
                and getattr(leaf, "_npy_value", None) is None):
            seen.add(id(leaf))
            leaf.copy_to_host_async()
            started += leaf.nbytes
    return started


def _serialize(tree: Any, rec: Recorder) -> bytes:
    leaves = _sorted_leaves(tree)
    with rec.span("serialize.pull", cat="serialization"):
        arrays = [np.asarray(leaf) for _, leaf in leaves]
    out = [_MAGIC, struct.pack("<I", len(leaves))]
    for (path, _), arr in zip(leaves, arrays):
        path_b = _keystr(path).encode()
        dtype_b = arr.dtype.str.encode()
        out.append(struct.pack("<I", len(path_b)))
        out.append(path_b)
        out.append(struct.pack("<I", len(dtype_b)))
        out.append(dtype_b)
        out.append(struct.pack("<I", arr.ndim))
        out.append(struct.pack(f"<{arr.ndim}q", *arr.shape))
        # a zero-copy byte view (a memoryview cannot export bfloat16), so
        # the join below makes the only host copy of the leaf
        raw = np.ascontiguousarray(arr).reshape(-1).view(np.uint8)
        out.append(struct.pack("<Q", raw.nbytes))
        out.append(raw)
    return b"".join(out)


def deserialize_pytree_flat(data: bytes) -> dict[str, np.ndarray]:
    """Inverse of :func:`serialize_pytree`, returning {keypath: array}."""
    if data[:4] != _MAGIC:
        raise ValueError("bad magic — not a repro-serialized pytree")
    off = 4
    (n,) = struct.unpack_from("<I", data, off)
    off += 4
    out: dict[str, np.ndarray] = {}
    for _ in range(n):
        (plen,) = struct.unpack_from("<I", data, off)
        off += 4
        path = data[off : off + plen].decode()
        off += plen
        (dlen,) = struct.unpack_from("<I", data, off)
        off += 4
        dtype = np.dtype(data[off : off + dlen].decode())
        off += dlen
        (ndim,) = struct.unpack_from("<I", data, off)
        off += 4
        shape = struct.unpack_from(f"<{ndim}q", data, off)
        off += 8 * ndim
        (nbytes,) = struct.unpack_from("<Q", data, off)
        off += 8
        arr = np.frombuffer(data[off : off + nbytes], dtype=dtype).reshape(shape)
        off += nbytes
        out[path] = arr
    return out
