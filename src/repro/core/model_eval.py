"""ME — Model Evaluation (paper §4.2, Alg. 3), in JAX.

Given the N FEL models W(k) and per-cluster dataset sizes |DS_m|:

  gw(k) = Σ_m |DS_m| w^m(k) / |DS|                      (Eq. 1)
  s_m   = <w^m, gw> / (‖w^m‖ ‖gw‖)                      (Eq. 2)
  vote  = argmax_m s_m
  P^i   : G_max for the voted node, G_min for the rest   (Alg. 3 lines 6-12)

Two layouts are supported:

* stacked — ``W`` as an (N, D) array of flattened models (paper scale,
  and the layout the Pallas ``cosine_sim`` kernel consumes);
* pytree — a list of parameter pytrees, flattened on the fly.

``partial_terms``/``similarity_from_partials`` expose the decomposition used
by the sharded in-graph consensus (DESIGN.md §3): cosine similarity reduces
over the parameter axis, so each model-parallel shard contributes three
partial scalars and the full models never travel over the network.
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp


class MEResult(NamedTuple):
    global_model: jax.Array      # (D,) — gw(k)
    similarities: jax.Array      # (N,) — s_m
    vote: jax.Array              # ()  int32 — e_best
    predictions: jax.Array       # (N,) — P^i


def flatten_model(tree: Any) -> jax.Array:
    """Deterministic (sorted key-path) flattening of a parameter pytree.

    Alias of :func:`repro.core.serialization.flatten_pytree` — the single
    canonical flatten/unflatten roundtrip lives in ``core.serialization``.
    """
    from repro.core.serialization import flatten_pytree
    return flatten_pytree(tree)


def aggregate_global(W: jax.Array, data_sizes: jax.Array) -> jax.Array:
    """Eq. 1 — data-size-weighted aggregation of (N, D) stacked models."""
    weights = data_sizes.astype(jnp.float32) / jnp.sum(data_sizes)
    return jnp.einsum("n,nd->d", weights, W.astype(jnp.float32))


def cosine_similarities(W: jax.Array, gw: jax.Array, eps: float = 1e-12) -> jax.Array:
    """Eq. 2 — cosine similarity of every row of W against gw."""
    W = W.astype(jnp.float32)
    gw = gw.astype(jnp.float32)
    dots = W @ gw
    wn = jnp.sqrt(jnp.sum(W * W, axis=-1))
    gn = jnp.sqrt(jnp.sum(gw * gw))
    return dots / jnp.maximum(wn * gn, eps)


def make_predictions(vote: jax.Array, n: int, g_max: float = 0.99) -> jax.Array:
    """Alg. 3 lines 6-12 — G_max on the voted index, G_min elsewhere.

    G_min = (1 - G_max)/(N - 1) so that Σ_j p_j = 1 (paper §7.4); a
    single-node network has no "rest", so the row is one-hot.
    """
    if n == 1:
        return jnp.ones((1,))
    g_min = (1.0 - g_max) / (n - 1)
    return jnp.full((n,), g_min).at[vote].set(g_max)


@partial(jax.jit, static_argnames=("g_max", "use_kernel", "interpret"))
def model_evaluation(W: jax.Array, data_sizes: jax.Array,
                     g_max: float = 0.99, *, use_kernel: "bool | None" = None,
                     interpret: "bool | None" = None) -> MEResult:
    """Full ME (Alg. 3) over stacked (N, D) models.

    Backend-aware Eq. 2 routing: where the fused Pallas ``cosine_partials``
    kernel compiles natively (TPU) it does all three reductions
    (dot/‖w‖²/‖gw‖²) in one HBM pass; elsewhere the pure-jnp path runs —
    interpret-mode emulation is ~100× slower than jnp at paper scale on
    CPU, so it is opt-in only (``use_kernel=True``).
    """
    from repro.kernels.cosine_sim import cosine_partials, interpret_default
    if use_kernel is None:
        use_kernel = not interpret_default()
    gw = aggregate_global(W, data_sizes)
    if use_kernel:
        dot, wsq, gsq = cosine_partials(W.astype(jnp.float32),
                                        gw, interpret=interpret)
        sims = dot / jnp.maximum(jnp.sqrt(wsq) * jnp.sqrt(gsq), 1e-12)
    else:
        sims = cosine_similarities(W, gw)
    vote = jnp.argmax(sims).astype(jnp.int32)
    preds = make_predictions(vote, W.shape[0], g_max=g_max)
    return MEResult(gw, sims, vote, preds)


@jax.jit
def stack_models(models: Sequence[Any]) -> jax.Array:
    """(N, D) float32 W from N parameter pytrees, in one program: op by
    op, each flatten and each row's reshape would hold a copy of W."""
    return jnp.stack([flatten_model(m) for m in models])


def model_evaluation_pytrees(models: Sequence[Any], data_sizes: Sequence[float],
                             g_max: float = 0.99) -> MEResult:
    """ME over a list of parameter pytrees (paper-faithful runtime path)."""
    W = stack_models(list(models))
    return model_evaluation(W, jnp.asarray(data_sizes, jnp.float32), g_max=g_max)


# ---------------------------------------------------------------------------
# Decomposed similarity for the sharded consensus (beyond-paper optimization)
# ---------------------------------------------------------------------------

class PartialTerms(NamedTuple):
    dot: jax.Array      # <w_shard, gw_shard>
    w_sq: jax.Array     # ‖w_shard‖²
    gw_sq: jax.Array    # ‖gw_shard‖²


def partial_terms(w_shard: jax.Array, gw_shard: jax.Array) -> PartialTerms:
    """Per-shard partial reductions; sum across shards then combine."""
    w = w_shard.astype(jnp.float32)
    g = gw_shard.astype(jnp.float32)
    return PartialTerms(jnp.vdot(w, g), jnp.vdot(w, w), jnp.vdot(g, g))


def similarity_from_partials(t: PartialTerms, eps: float = 1e-12) -> jax.Array:
    """Combine (already summed-across-shards) partials into s_m."""
    return t.dot / jnp.maximum(jnp.sqrt(t.w_sq) * jnp.sqrt(t.gw_sq), eps)
