"""Faults planted in the timed path underneath a run, to show that the
check of ``correct`` catches each of them. Each takes the cell and a
``pytest.MonkeyPatch`` and breaks the program where the answer is made;
``bench/tests/test_check.py`` and ``bench/control.py`` plant them.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np


def state_unchanged(cell, monkeypatch) -> None:
    """Every server's FEL hands back the model it started from."""
    from repro.fl import batched_fel

    def unchanged(self, global_flat, round_seed):
        return jnp.broadcast_to(global_flat,
                                (self.n_clusters,) + global_flat.shape)
    monkeypatch.setattr(batched_fel.BatchedFELEngine, "run_round", unchanged)


def half_batch(cell, monkeypatch) -> None:
    """Each step's loss is the mean over the first half of its batch."""
    spec = cell.adapter.batched_train_spec()
    loss = spec.per_example_loss

    def first_half(params, batch, key):
        pe = loss(params, batch, key)
        return jnp.resize(pe[: pe.shape[0] // 2], pe.shape)
    monkeypatch.setattr(cell.adapter, "_batched_spec",
                        dataclasses.replace(spec, per_example_loss=first_half))


def similarity_altered(cell, monkeypatch) -> None:
    """ME reports the first server's similarity 1e-3 too high."""
    from repro.core import phases
    evaluate = phases.model_evaluation_pytrees

    def altered(models, sizes, g_max=0.99):
        res = evaluate(models, sizes, g_max=g_max)
        return res._replace(similarities=res.similarities.at[0].add(1e-3))
    monkeypatch.setattr(phases, "model_evaluation_pytrees", altered)


def vote_altered(cell, monkeypatch) -> None:
    """Every node votes for the server ME finds least similar."""
    from repro.core import phases
    collect = phases.VoteCollection.run

    def run(self, ctx):
        worst = int(np.argmin(np.asarray(ctx.evaluation.similarities)))
        ctx.vote_hook = lambda i, vote, preds: (worst, preds)
        collect(self, ctx)
    monkeypatch.setattr(phases.VoteCollection, "run", run)


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch,
                                  similarity_altered, vote_altered)}
