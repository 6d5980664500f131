"""BTSV — Bayesian Truth Serum-based Voting (paper §4.3, Alg. 4), in JAX.

Inputs per round k: the vote matrix A (A[i, j] = 1 iff e_i voted for e_j)
and the prediction matrix P (P[i, j] = p_j^i, each row sums to 1).

  x̄_j   = mean_i A[i, j]                                     (Eq. 3)
  ȳ_j   = exp(mean_i log P[i, j])  (geometric mean)          (Eq. 4)
  info_i = Σ_j A[i, j] log(x̄_j / ȳ_j)                        (Eq. 5)
  pred_i = α Σ_j x̄_j log(P[i, j] / x̄_j)                      (Eq. 6)
  score_i = info_i + pred_i, α = 1 (zero-sum)                 (Eq. 7)
  CHS_i(k) = Σ_{max(0,k-c)}^{k} score_i                       (Eq. 8)
  WV_i = β / (1 + exp(−θ·CHS_i − ε))                          (Eq. 9)
  advotes_j = Σ_i WV_i A[i, j]                                (Eq. 10)
  leader = argmax_j advotes_j
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp


class BTSVConfig(NamedTuple):
    alpha: float = 1.0    # prediction-score weight (zero-sum at 1.0)
    beta: float = 1.3     # WV upper limit
    theta: float = 0.4    # WV gradient vs CHS
    epsilon: float = 1.2  # WV(CHS=0) ≈ 1
    history: int = 20     # c — CHS window length
    eps: float = 1e-12    # numerical floor inside logs


class BTSVResult(NamedTuple):
    """One tally's outputs. :func:`btsv_round` returns them as device
    arrays; ``VoteTallyContract`` pulls them to the host in one copy, so
    its results hold host NumPy arrays."""

    leader: jax.Array        # () int32 — e*(k)
    scores: jax.Array        # (N,) — score^i(k)
    weights: jax.Array       # (N,) — WV^i(k)
    advotes: jax.Array       # (N,) — adjusted tallied votes
    chs: jax.Array           # (N,) — cumulative historical score used


def votes_to_matrix(votes: jax.Array, n: int) -> jax.Array:
    """E_best(k) (N,) int votes → (N, N) one-hot matrix A (Alg. 4 lines 1-8)."""
    return jax.nn.one_hot(votes, n, dtype=jnp.float32)


def bts_scores(A: jax.Array, P: jax.Array, cfg: BTSVConfig = BTSVConfig(),
               present: "jax.Array | None" = None) -> jax.Array:
    """Eq. 3-7 — per-node BTS score for one round.

    ``present`` (an (N,) 0/1 mask, default all-present) restricts the
    population means to the voters whose submissions actually arrived —
    a fault-dropped vote must be *neutral*: excluded from x̄/ȳ and scored
    exactly 0, so network loss never erodes an honest node's CHS the way
    a bad vote would.
    """
    if present is None:
        present = jnp.ones(A.shape[0], jnp.float32)
    m = jnp.maximum(jnp.sum(present), 1.0)
    x_bar = jnp.sum(A * present[:, None], axis=0) / m             # (N,)
    y_bar = jnp.exp(jnp.sum(present[:, None] *
                            jnp.log(jnp.maximum(P, cfg.eps)), axis=0) / m)
    log_ratio = jnp.log(jnp.maximum(x_bar, cfg.eps)) - jnp.log(jnp.maximum(y_bar, cfg.eps))
    info = A @ log_ratio                                          # (N,)
    # prediction score: α Σ_j x̄_j log(p_j^i / x̄_j); terms with x̄_j = 0 vanish
    log_p = jnp.log(jnp.maximum(P, cfg.eps))
    log_x = jnp.log(jnp.maximum(x_bar, cfg.eps))
    pred = cfg.alpha * jnp.sum(jnp.where(x_bar > 0, x_bar * (log_p - log_x), 0.0), axis=1)
    return (info + pred) * present


def vote_weights(chs: jax.Array, cfg: BTSVConfig = BTSVConfig()) -> jax.Array:
    """Eq. 9 — sigmoid mapping of cumulative score to vote weight."""
    return cfg.beta / (1.0 + jnp.exp(-cfg.theta * chs - cfg.epsilon))


@partial(jax.jit, static_argnames=("cfg",))
def btsv_round(votes: jax.Array, P: jax.Array, score_history: jax.Array,
               cfg: BTSVConfig = BTSVConfig(),
               present: "jax.Array | None" = None,
               ) -> tuple[BTSVResult, jax.Array]:
    """One smart-contract tally (Alg. 4).

    ``score_history`` is a (c, N) rolling buffer of past scores (zeros when
    unused); it is shifted and returned updated so the caller can thread it
    through rounds functionally. ``present`` masks out voters whose
    submissions never landed (see :func:`bts_scores`); a vote of ``-1``
    one-hots to a zero row, so an absent voter neither tallies votes nor
    collects adjusted ones.
    """
    n = P.shape[0]
    A = votes_to_matrix(votes, n)
    scores = bts_scores(A, P, cfg, present=present)
    chs = jnp.sum(score_history, axis=0) + scores                 # Eq. 8
    wv = vote_weights(chs, cfg)
    advotes = wv @ A                                              # Eq. 10
    leader = jnp.argmax(advotes).astype(jnp.int32)
    new_history = jnp.concatenate([score_history[1:], scores[None]], axis=0)
    return BTSVResult(leader, scores, wv, advotes, chs), new_history


def init_history(n_nodes: int, cfg: BTSVConfig = BTSVConfig()) -> jax.Array:
    return jnp.zeros((cfg.history, n_nodes), jnp.float32)
