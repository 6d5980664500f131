"""Plain reference of one BHFL round, independent of the program.

The same semantics as the system under test, written out directly in
``jax.numpy``: every client runs momentum SGD with Keras-style time decay
over its own drop-remainder batches (the permutation of
``numpy.random.default_rng(client_seed + epoch)``), each edge server
averages its clients by data size after every FEL iteration, and the
consensus evaluates the N server models (Eq. 1 aggregate, Eq. 2 cosine
similarities, vote = the most similar) and tallies the votes by BTSV
(Eqs. 3-10). The model itself (loss, evaluation, initial weights) comes
from the configuration's module beside its JSON file.

Precisions (``PRECISIONS``): ``reference`` is float32 with every matrix
product at HIGHEST precision; ``default`` is float32 at the backend's
default matrix-product precision (one bfloat16 pass on a TPU). The
control sits one step below what a configuration states: ``bf16`` stores
parameters and optimiser state and computes in bfloat16.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from compare import Trajectory

HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Precision:
    name: str
    act: Any            # dtype of activations
    param: Any          # dtype parameters and momentum are stored in
    matmul: Any = HIGHEST   # precision of every matrix product

    def dot(self, eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
        return jnp.einsum(eq, a.astype(self.act), b.astype(self.act),
                          precision=self.matmul)


PRECISIONS = {
    "reference": Precision("reference", jnp.float32, jnp.float32),
    "default": Precision("default", jnp.float32, jnp.float32, None),
    "bf16": Precision("bf16", jnp.bfloat16, jnp.bfloat16),
}


@dataclass(frozen=True)
class Optim:
    lr: float
    momentum: float
    decay: float
    batch_size: int
    local_epochs: int


def batch_plan(size: int, optim: Optim, seed: int) -> np.ndarray:
    """(steps, batch) row indices of one client's local training."""
    bs = min(optim.batch_size, size)
    rows = []
    for ep in range(optim.local_epochs):
        order = np.random.default_rng(seed + ep).permutation(size)
        rows += [order[s:s + bs] for s in range(0, size - bs + 1, bs)]
    return np.stack(rows).astype(np.int32)


@partial(jax.jit, static_argnames=("loss", "prec", "optim"))
def train_client(params, data, idx, seed, *, loss: Callable, prec: Precision,
                 optim: Optim):
    """Local SGD of one client over its batch plan ``idx``."""
    key0 = jax.random.key(seed)
    params = jax.tree.map(lambda p: p.astype(prec.param), params)
    mom0 = jax.tree.map(jnp.zeros_like, params)

    def step(carry, sel):
        p, mom, t, key = carry
        key, sub = jax.random.split(key)
        batch = jax.tree.map(lambda a: a[sel], data)

        def batch_loss(pp):
            pe = loss(pp, batch, sub, prec)
            return jnp.mean(pe.astype(jnp.float32))

        g = jax.grad(batch_loss)(p)
        lr_t = optim.lr / (1.0 + optim.decay * t.astype(jnp.float32))
        mom = jax.tree.map(
            lambda m, gg: (optim.momentum * m.astype(jnp.float32)
                           + gg.astype(jnp.float32)).astype(prec.param),
            mom, g)
        p = jax.tree.map(
            lambda a, m: (a.astype(jnp.float32)
                          - lr_t * m.astype(jnp.float32)).astype(prec.param),
            p, mom)
        return (p, mom, t + 1, key), None

    (p, _, _, _), _ = jax.lax.scan(
        step, (params, mom0, jnp.zeros((), jnp.int32), key0), idx)
    return jax.tree.map(lambda a: a.astype(jnp.float32), p)


@jax.jit
def weighted_mean(trees: Sequence[Any], weights: jax.Array) -> Any:
    """sum_i weights_i * tree_i, elementwise in float32."""
    def avg(*leaves):
        acc = jnp.zeros(leaves[0].shape, jnp.float32)
        for w, leaf in zip(weights, leaves):
            acc = acc + w * leaf.astype(jnp.float32)
        return acc
    return jax.tree.map(avg, *trees)


def model_evaluation(flat_rows: Sequence[jax.Array], sizes: np.ndarray):
    """Eq. 1 and Eq. 2 over flat float32 models: (gw, similarities)."""
    lam = jnp.asarray(np.asarray(sizes, np.float64) / np.sum(sizes),
                      jnp.float32)
    gw = weighted_mean(list(flat_rows), lam)
    dist = np.asarray(jnp.stack([_cosine_distance(r, gw) for r in flat_rows]),
                      np.float64)
    return gw, 1.0 - dist


@jax.jit
def _cosine_distance(a: jax.Array, b: jax.Array) -> jax.Array:
    """1 - cos(a, b) as half the squared distance of the unit vectors: the
    models lie within about 1e-5 of one another in angle, where 1 - a.b
    in float32 would be all rounding."""
    na = jnp.sqrt(jnp.sum(jnp.square(a)))
    nb = jnp.sqrt(jnp.sum(jnp.square(b)))
    return 0.5 * jnp.sum(jnp.square(a / na - b / nb))


def btsv_leaders(votes: Sequence[Sequence[int]], g_max: float,
                 btsv: dict) -> List[int]:
    """BTSV leader of every round from the votes cast in it, every voter
    predicting G_max for its own vote and G_min for the rest (Alg. 4)."""
    leaders, history = [], []
    eps = btsv["eps"]
    for v in votes:
        v = np.asarray(v, np.int64)
        n = len(v)
        A = np.eye(n)[v]
        g_min = (1.0 - g_max) / (n - 1) if n > 1 else 0.0
        P = np.where(A > 0, g_max, g_min) if n > 1 else np.ones((1, 1))
        x_bar = A.mean(axis=0)
        y_bar = np.exp(np.log(np.maximum(P, eps)).mean(axis=0))
        info = A @ (np.log(np.maximum(x_bar, eps))
                    - np.log(np.maximum(y_bar, eps)))
        log_p = np.log(np.maximum(P, eps))
        log_x = np.log(np.maximum(x_bar, eps))
        pred = btsv["alpha"] * np.sum(
            np.where(x_bar > 0, x_bar * (log_p - log_x), 0.0), axis=1)
        score = info + pred
        window = history[-btsv["history"]:]
        chs = np.sum(window, axis=0) + score if window else score
        wv = btsv["beta"] / (1.0 + np.exp(-btsv["theta"] * chs
                                          - btsv["epsilon"]))
        leaders.append(int(np.argmax(wv @ A)))
        history.append(score)
    return leaders


class ReferenceBHFL:
    """Runs the first rounds of a cell in plain ``jax.numpy``.

    ``servers`` holds, per edge server, its clients as
    ``(client_id, columns)``. ``loss`` (per-example, jit-static),
    ``evaluate`` (jitted) and ``round_start`` are the configuration's
    model.
    """

    def __init__(self, loss: Callable, evaluate: Callable,
                 round_start: Callable, cfg: dict,
                 servers: Sequence[Sequence[tuple]], test: dict,
                 fel_iterations: int, base_seed: int,
                 prec: str = "reference"):
        self.loss, self.evaluate, self.round_start = loss, evaluate, round_start
        o = cfg["optimizer"]
        self.optim = Optim(o["lr"], o["momentum"], o["decay"],
                           cfg["batch_size"], cfg["local_epochs"])
        self.prec = PRECISIONS[prec]
        self.servers = [[(cid, jax.tree.map(jnp.asarray, cols))
                         for cid, cols in clients] for clients in servers]
        self.sizes = [[len(next(iter(cols.values()))) for _, cols in clients]
                      for clients in servers]
        self.test = jax.tree.map(jnp.asarray, test)
        self.fel_iterations = fel_iterations
        self.base_seed = base_seed

    def _fel(self, start: Any, round_seed: int, clients, sizes) -> Any:
        params = start
        lam = jnp.asarray(np.asarray(sizes, np.float64) / np.sum(sizes),
                          jnp.float32)
        for it in range(self.fel_iterations):
            local = []
            for (cid, cols), size in zip(clients, sizes):
                seed = round_seed * 1000 + cid * 10 + it
                idx = jnp.asarray(batch_plan(size, self.optim, seed))
                local.append(train_client(
                    params, cols, idx, seed, loss=self.loss,
                    prec=self.prec, optim=self.optim))
            params = weighted_mean(local, lam)
        return params

    def run(self, params: Any, rounds: int, layout) -> Trajectory:
        """``rounds`` BHFL rounds from ``params``; each round starts from
        the last round's gw(k) in the configuration's storage dtypes."""
        traj = Trajectory()
        start_flat = layout.flatten(params)
        prev, gw_tree = start_flat, params
        server_sizes = np.asarray([sum(s) for s in self.sizes], np.float64)
        for k in range(rounds):
            start = self.round_start(gw_tree)
            rows = [layout.flatten(self._fel(start, self.base_seed + k + 1,
                                             clients, sizes))
                    for clients, sizes in zip(self.servers, self.sizes)]
            gw, sims = model_evaluation(rows, server_sizes)
            traj.updates.append(np.stack(
                [np.asarray(layout.change_norms(r, prev)) for r in rows]))
            del rows
            gw_tree = layout.unflatten(gw)
            _, loss = self.evaluate(self.round_start(gw_tree), self.test,
                                    self.prec)
            traj.add_round(sims, float(loss))
            prev = gw
        traj.change = np.asarray(layout.change_norms(prev, start_flat))
        return traj
