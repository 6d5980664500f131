"""Quickstart: the PoFEL consensus in 60 lines, on the ``repro.api`` facade.

Five BCFL nodes train tiny local models, run one full PoFEL round through
the five-phase pipeline (HCDS commit/reveal → ME similarity voting → vote
submission → BTSV tally → block mint), and every ledger ends up with the
same verified block. A phase hook watches the pipeline run — the API for
experiments that tap individual protocol stages.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import numpy as np

from repro import api, compile_cache
from repro.models.mlp import MLPConfig, mlp_init

compile_cache.enable()

N_NODES = 5

# 1. Each edge server trained an intermediate FEL model (here: random init
#    + a node-specific perturbation standing in for local training).
cfg = MLPConfig(hidden=32)
base = mlp_init(cfg, jax.random.key(0))
rng = np.random.default_rng(0)
models = [
    jax.tree.map(lambda p: np.asarray(p) + 0.01 * rng.normal(size=p.shape)
                 .astype(np.float32), base)
    for _ in range(N_NODES)
]
data_sizes = [100.0, 150.0, 120.0, 80.0, 200.0]   # |DS_m| per cluster

# 2. One PoFEL consensus round (Alg. 1) — five phases over a RoundContext.
consensus = api.PoFELConsensus(N_NODES)
print("phases:", [p.name for p in consensus.phases])
consensus.add_phase_hook(
    "*", lambda name, ctx: print(f"  ✓ {name}"), when="after")
record = consensus.run_round(models, data_sizes)

print("cosine similarities s_m:", np.round(record.similarities, 5))
print("votes:", record.votes.tolist())
print(f"leader e*(k) = node {record.leader_id}")
print(f"BTS vote weights: {np.round(np.asarray(record.btsv.weights), 3)}")

# 3. Every node's ledger now holds the identical signed block.
for ledger in consensus.ledgers:
    assert ledger.height == 1 and ledger.verify_chain()
block = consensus.chain[0]
print(f"block 0: leader={block.leader_id} "
      f"digest[gw]={block.global_model_digest[:16]}… "
      f"signature valid={block.verify_signature(consensus.public_keys[block.leader_id])}")
print("all ledgers consistent ✓")

# 4. The same protocol drives a full learning task in one call:
#        api.run_bhfl(model="mlp" | "transformer" | "rwkv6", ...)
#    — see examples/full_system.py and examples/bhfl_train.py.
#    Fast path: api.run_bhfl(..., engine="batched") (or
#    BHFLConfig(engine="batched")) runs the whole FEL phase of each round
#    as ONE jitted device program (repro.fl.batched_fel) — same numbers,
#    ≥5x less wall time on CPU at paper scale, more on accelerators.
