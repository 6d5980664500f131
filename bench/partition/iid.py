"""IID split: a seeded permutation of the training rows cut into nearly
equal consecutive chunks, one per client (a copy of the program's
``repro.data.partition.partition_iid``). Every seed gives every client the
same number of rows."""

from __future__ import annotations

from typing import List

import numpy as np


def split(train: dict, n_parts: int, params: dict,
          seed: int) -> List[np.ndarray]:
    """Row indices of each client in the ``train`` columns."""
    if params:
        raise ValueError(f"the iid split takes no parameters, got {params}")
    n_rows = len(next(iter(train.values())))
    order = np.random.default_rng(seed).permutation(n_rows)
    return np.array_split(order, n_parts)
