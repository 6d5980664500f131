"""The numbers that decide ``correct``, and their limits.

A cell's first rounds are run twice: by the program (through the timed
path, during set-up) and by the plain reference. Each side is summarised
as a ``Trajectory``, and ``numbers`` reduces the pair to a few gaps:

* ``w_update``  - W(k): per round, server and leaf, the gap between the
  norms of the server model's change from the round's starting gw(k-1),
  over the reference's norm of that leaf or of the median leaf,
  whichever is larger; the worst of them. ``w_update_median`` takes the
  median leaf of each round and server, and the worst of those.
* ``gw_change`` - the same for gw after the last checked round against
  the initial weights.
* ``test_loss`` - the relative gap of the evaluated test loss, worst round.
* ``sim_gap``   - the widest gap between a similarity s_m (Eq. 2) of the
  program and of the reference, any round and server. The servers' models
  lie within about 1e-5 of gw(k) in cosine, so this is an absolute gap.
* ``vote_faults`` - votes cast for another server than the one the
  program's own similarities rank first (an honest node votes for the
  most similar model), counted over every round and node: exact, limit 0.
  ``sim_gap`` ties those similarities to the reference.

Leaves that the reference leaves unmoved to rounding (a change under a
thousandth of the median leaf's) are left out of ``w_update`` and
``gw_change``. The exact checks of the chain (``leader_mismatch``,
``block_faults``) are counts with the limit 0.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

MOVED = 1e-3    # a leaf whose change is under this share of the median is unmoved


@dataclass
class Trajectory:
    updates: List[np.ndarray] = field(default_factory=list)  # (N, leaves) per round
    sims: List[np.ndarray] = field(default_factory=list)     # (N,) per round
    losses: List[float] = field(default_factory=list)
    votes: List[np.ndarray] = field(default_factory=list)     # votes cast per round
    change: Optional[np.ndarray] = None                       # (leaves,)

    def add_round(self, sims, loss: float, votes=None) -> None:
        """``votes``: every voter's vote; by default the most similar."""
        sims = np.asarray(sims, np.float64)
        self.sims.append(sims)
        self.losses.append(float(loss))
        self.votes.append(np.asarray([np.argmax(sims)] if votes is None
                                     else votes, np.int64))


def leaf_gaps(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """|got - ref| / max(ref, median(ref)) per leaf; NaN where the
    reference leaves the leaf unmoved."""
    med = float(np.median(ref))
    gaps = np.abs(got - ref) / np.maximum(np.maximum(ref, med), 1e-300)
    return np.where(ref >= MOVED * med, gaps, np.nan)


def _finite(reduce, gaps: np.ndarray) -> float:
    """``reduce`` over the moved leaves; inf where no leaf moved."""
    return float(reduce(gaps)) if np.any(np.isfinite(gaps)) else float("inf")


def numbers(prog: Trajectory, ref: Trajectory) -> Dict[str, float]:
    rounds = len(ref.losses)
    if len(prog.losses) != rounds:
        raise ValueError(f"program ran {len(prog.losses)} checked rounds, "
                         f"reference {rounds}")
    gaps = [[leaf_gaps(p[n], r[n]) for n in range(r.shape[0])]
            for p, r in zip(prog.updates, ref.updates)]
    worst = [[_finite(np.nanmax, g) for g in row] for row in gaps]
    median = [[_finite(np.nanmedian, g) for g in row] for row in gaps]
    change = leaf_gaps(prog.change, ref.change)
    sim = max(float(np.max(np.abs(sp - sr)))
              for sp, sr in zip(prog.sims, ref.sims))
    vote_faults = sum(int(np.sum(vp != np.argmax(sp)))
                      for sp, vp in zip(prog.sims, prog.votes))
    loss = max(abs(lp - lr) / abs(lr) for lp, lr in zip(prog.losses, ref.losses))
    return {
        "w_update": max(max(row) for row in worst),
        "w_update_median": max(max(row) for row in median),
        "gw_change": _finite(np.nanmax, change),
        "test_loss": float(loss),
        "sim_gap": sim,
        "vote_faults": float(vote_faults),
    }


def judge(values: Dict[str, float], limits: Dict[str, float]) -> Dict[str, dict]:
    """{name: {value, limit, ok}} for every number that has a limit."""
    out = {}
    for name, limit in limits.items():
        v = values.get(name)
        ok = v is not None and np.isfinite(v) and v <= limit
        out[name] = {"value": v, "limit": limit, "ok": bool(ok)}
    return out
