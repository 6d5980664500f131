"""The canonical flat layout of a parameter pytree, as the benchmark sees it.

Leaves in sorted key-path order, each raveled into float32 and
concatenated: the layout in which the consensus commits to, aggregates
and compares models. Written here from that definition, not imported.
"""

from __future__ import annotations

from typing import Any, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Layout:
    def __init__(self, template: Any):
        flat = jax.tree_util.tree_flatten_with_path(template)[0]
        self.treedef = jax.tree_util.tree_structure(template)
        keyed = [(jax.tree_util.keystr(path), i, tuple(leaf.shape))
                 for i, (path, leaf) in enumerate(flat)]
        self.entries: List[Tuple[str, int, tuple, int, int]] = []
        off = 0
        for name, i, shape in sorted(keyed):
            size = int(np.prod(shape, dtype=np.int64))
            self.entries.append((name, i, shape, off, size))
            off += size
        self.size = off
        self.names = [e[0] for e in self.entries]
        self.flatten = jax.jit(self._flatten)
        self.unflatten = jax.jit(self._unflatten)
        self.change_norms = jax.jit(self._change_norms)

    def _flatten(self, tree: Any) -> jax.Array:
        leaves = jax.tree_util.tree_leaves(tree)
        return jnp.concatenate([jnp.ravel(leaves[i]).astype(jnp.float32)
                                for _, i, _, _, _ in self.entries])

    def _unflatten(self, flat: jax.Array) -> Any:
        leaves: List[Any] = [None] * len(self.entries)
        for _, i, shape, off, size in self.entries:
            leaves[i] = flat[off:off + size].reshape(shape)
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def _change_norms(self, flat: jax.Array, start: jax.Array) -> jax.Array:
        """(leaves,) L2 norm of each leaf's slice of ``flat - start``."""
        d = flat.astype(jnp.float32) - start.astype(jnp.float32)
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(d[off:off + size])))
                          for _, _, _, off, size in self.entries])
