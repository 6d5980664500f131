"""MNIST-shaped classification data, from a traffic file's ``n_train``,
``n_test``, ``n_classes``, ``dim`` and ``noise``.

A copy of the program's generator (``repro.data.synthetic.make_mnist_like``),
kept here so that no later change to the program can move the data a cell
is measured on: a fixed random template per class plus gaussian noise,
squashed into [0, 1] like pixel intensities. The program receives only the
arrays, wrapped in its own dataset type.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

KEYS = {"kind", "n_train", "n_test", "n_classes", "dim", "noise"}


def make(traffic: dict, seed: int) -> Tuple[dict, dict]:
    """(train, test) columns ``x`` (float32) and ``y`` (int32 labels)."""
    n_classes, dim = traffic["n_classes"], traffic["dim"]
    noise = traffic["noise"]
    rng = np.random.default_rng(seed)
    templates = rng.normal(0.0, 1.0, size=(n_classes, dim)).astype(np.float32)

    def gen(n: int, s: int) -> dict:
        r = np.random.default_rng(s)
        y = r.integers(0, n_classes, size=n).astype(np.int32)
        x = templates[y] + r.normal(0.0, noise, size=(n, dim)).astype(np.float32)
        x = 1.0 / (1.0 + np.exp(-x))
        return {"x": x.astype(np.float32), "y": y}

    return gen(traffic["n_train"], seed + 1), gen(traffic["n_test"], seed + 2)
