"""mnist-mlp: the paper's MLP 784-128-10 (ReLU, dropout 0.2), its plain
reference, how the program's adapter and data sets are built, and the
operations a round needs.

Reference forward: h = relu(x W1 + b1), dropout with the keep mask of row
i drawn as ``bernoulli(fold_in(step_key, i), 0.8, (hidden,))`` and the
kept units scaled by 1/0.8, logits = h W2 + b2, per-sample softmax cross
entropy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init(cfg: dict, key: jax.Array) -> dict:
    """He-normal weights, zero biases, float32."""
    m = cfg["model"]
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (m["in_dim"], m["hidden"]), jnp.float32)
        * jnp.sqrt(2.0 / m["in_dim"]),
        "b1": jnp.zeros((m["hidden"],), jnp.float32),
        "w2": jax.random.normal(k2, (m["hidden"], m["n_classes"]), jnp.float32)
        * jnp.sqrt(2.0 / m["hidden"]),
        "b2": jnp.zeros((m["n_classes"],), jnp.float32),
    }


def round_start(params: dict) -> dict:
    """Every leaf is stored in float32: nothing is rounded between rounds."""
    return params


def _logits(params, x, prec, dropout_key=None, dropout=0.0):
    h = jax.nn.relu(prec.dot("bi,ih->bh", x, params["w1"])
                    + params["b1"].astype(prec.act))
    if dropout_key is not None and dropout > 0.0:
        keep = 1.0 - dropout
        mask = jax.vmap(lambda i: jax.random.bernoulli(
            jax.random.fold_in(dropout_key, i), keep, h.shape[1:]))(
            jnp.arange(h.shape[0]))
        h = jnp.where(mask, h / keep, 0.0).astype(prec.act)
    return (prec.dot("bh,hc->bc", h, params["w2"])
            + params["b2"].astype(prec.act)).astype(jnp.float32)


def _ce(logits, y):
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]


def make_per_example_loss(cfg: dict):
    dropout = cfg["model"]["dropout"]

    def per_example_loss(params, batch, key, prec):
        return _ce(_logits(params, batch["x"], prec, key, dropout), batch["y"])
    return per_example_loss


def make_evaluate(cfg: dict):
    def evaluate(params, test, prec):
        logits = _logits(params, test["x"], prec)
        acc = jnp.mean((jnp.argmax(logits, -1) == test["y"])
                       .astype(jnp.float32))
        return acc, jnp.mean(_ce(logits, test["y"]))
    return evaluate


def program_adapter(cfg: dict):
    from repro.fl.adapters import MLPAdapter
    from repro.models.mlp import MLPConfig
    m, o = cfg["model"], cfg["optimizer"]
    return MLPAdapter(
        cfg=MLPConfig(m["in_dim"], m["hidden"], m["n_classes"], m["dropout"]),
        local_epochs=cfg["local_epochs"], batch_size=cfg["batch_size"],
        lr=o["lr"], momentum=o["momentum"], decay=o["decay"])


def program_dataset(cfg: dict, columns: dict):
    from repro.data.synthetic import SyntheticImageDataset
    return SyntheticImageDataset(columns["x"], columns["y"],
                                 cfg["model"]["n_classes"])


def matmul_params(cfg: dict) -> int:
    m = cfg["model"]
    return m["in_dim"] * m["hidden"] + m["hidden"] * m["n_classes"]


def flops(cfg: dict, train_rows: int, test_rows: int) -> dict:
    """Operations of one round's training (forward and backward, 6 per
    weight of a matrix product and sample) and of its evaluation (2 per
    weight and test sample). Biases, activations and the loss are
    elementwise and left out."""
    p = matmul_params(cfg)
    return {"train": 6.0 * p * train_rows, "eval": 2.0 * p * test_rows}


def row_units(columns: dict) -> int:
    """Samples per data row."""
    return 1
