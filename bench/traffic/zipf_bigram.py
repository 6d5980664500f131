"""Token rows from a seeded Zipf-weighted bigram stream, from a traffic
file's ``n_train``, ``n_test``, ``seq_len``, ``vocab`` and ``zipf_s``.

Each row starts at a uniform token; every next token is the successor of
rank r of the one before, r drawn with weight 1/(r+1)^s. Token a ranks
its successors as (offset[a] + order[r]) mod vocab, with ``offset`` and
``order`` seeded permutations, so each token has its own preferred
followers. Rows hold ``seq_len`` + 1 tokens: the inputs and, shifted by
one, the labels. The program receives only the arrays.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

KEYS = {"kind", "n_train", "n_test", "seq_len", "vocab", "zipf_s"}


def make(traffic: dict, seed: int) -> Tuple[dict, dict]:
    """(train, test) columns ``rows``: int32 (n, seq_len + 1)."""
    vocab = traffic["vocab"]
    rng = np.random.default_rng(seed)
    offset = rng.permutation(vocab)
    order = rng.permutation(vocab)
    weights = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** traffic["zipf_s"]
    cdf = np.cumsum(weights / weights.sum())

    def gen(n: int, s: int) -> dict:
        r = np.random.default_rng(s)
        rows = np.empty((n, traffic["seq_len"] + 1), np.int64)
        rows[:, 0] = r.integers(0, vocab, size=n)
        ranks = np.minimum(np.searchsorted(cdf, r.random((n, traffic["seq_len"]))),
                           vocab - 1)
        for t in range(traffic["seq_len"]):
            rows[:, t + 1] = (offset[rows[:, t]] + order[ranks[:, t]]) % vocab
        return {"rows": rows.astype(np.int32)}

    return gen(traffic["n_train"], seed + 1), gen(traffic["n_test"], seed + 2)
