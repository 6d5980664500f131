"""PoFEL core: the paper's primary contribution as composable JAX modules.

- crypto / serialization / hcds — Hash-based Commitment + Digital Signature
- model_eval — ME: weighted aggregation + cosine-similarity voting
- btsv — Bayesian Truth Serum-based weighted vote tallying
- incentive — two-stage Stackelberg game solver
- phases — Alg. 1 as five composable protocol stages + RoundContext
- consensus — the PoFEL round orchestrator composing the phases
- committee — committee-scoped node subsets + cross-shard checkpoints
- recovery — durable per-node protocol WAL + crash-recovery primitives

Submodule symbols are re-exported lazily (PEP 562) because the blockchain
package depends on ``repro.core.crypto`` while ``repro.core.consensus``
depends back on the blockchain package.
"""

_EXPORTS = {
    "BTSVConfig": "repro.core.btsv", "BTSVResult": "repro.core.btsv",
    "btsv_round": "repro.core.btsv", "init_history": "repro.core.btsv",
    "ConsensusRecord": "repro.core.consensus",
    "PoFELConsensus": "repro.core.consensus",
    "SignedEnvelope": "repro.core.envelope",
    "EnvelopeBatchResult": "repro.core.envelope",
    "verify_envelopes": "repro.core.envelope",
    "Signature": "repro.core.crypto",
    "verify_batch": "repro.core.crypto",
    "Committee": "repro.core.committee",
    "CheckpointStatement": "repro.core.committee",
    "checkpoint_block": "repro.core.committee",
    "checkpoint_statement_of": "repro.core.committee",
    "committee_keypair": "repro.core.committee",
    "committee_seed": "repro.core.committee",
    "make_checkpoint_validator": "repro.core.committee",
    "make_committees": "repro.core.committee",
    "sign_checkpoint": "repro.core.committee",
    "verify_checkpoint_certificate": "repro.core.committee",
    "Commitment": "repro.core.hcds", "HCDSNode": "repro.core.hcds",
    "HCDSResult": "repro.core.hcds", "Reveal": "repro.core.hcds",
    "run_hcds_round": "repro.core.hcds",
    "NodeWAL": "repro.core.recovery", "WALConflict": "repro.core.recovery",
    "WALRecord": "repro.core.recovery",
    "NodeParams": "repro.core.incentive", "PublisherParams": "repro.core.incentive",
    "StackelbergSolution": "repro.core.incentive",
    "stackelberg_equilibrium": "repro.core.incentive",
    "RoundContext": "repro.core.phases", "ConsensusPhase": "repro.core.phases",
    "CommitReveal": "repro.core.phases", "ModelEvaluation": "repro.core.phases",
    "VoteCollection": "repro.core.phases", "Tally": "repro.core.phases",
    "BlockMint": "repro.core.phases", "run_phases": "repro.core.phases",
    "flatten_pytree": "repro.core.serialization",
    "unflatten_pytree": "repro.core.serialization",
    "unflatten_pytree_device": "repro.core.serialization",
    "serialize_pytree": "repro.core.serialization",
    "serialize_pytrees": "repro.core.serialization",
    "MEResult": "repro.core.model_eval", "aggregate_global": "repro.core.model_eval",
    "cosine_similarities": "repro.core.model_eval",
    "flatten_model": "repro.core.model_eval",
    "model_evaluation": "repro.core.model_eval",
    "model_evaluation_pytrees": "repro.core.model_eval",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    if name in _EXPORTS:
        import importlib
        mod = importlib.import_module(_EXPORTS[name])
        return getattr(mod, name)
    raise AttributeError(f"module 'repro.core' has no attribute {name!r}")
