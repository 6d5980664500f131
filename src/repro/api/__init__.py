"""``repro.api`` — the single facade over the BHFL system (paper §3.1).

One call composes all four procedures:

    from repro import api

    run = api.run_bhfl(
        task=api.LearningTask("mnist-0", "owner-7", "digit classification",
                              target_loss=1.5, max_rounds=10),
        model="mlp",            # or "transformer" / "rwkv6" / a ModelAdapter
        n_nodes=6, clients_per_node=4, fel_iterations=2)

    run.history[-1].test_accuracy, run.rewards.totals(), run.chain_height

Procedures composed (each also importable individually):

1. Task Publication   — ``LearningTask`` announced on-chain (digest).
2. Incentive          — Stackelberg negotiation (``negotiate_task``)
                        fixes δ* and f_i*; a ``RewardLedger`` settles
                        leader + FEL rewards every round.
3. FEL hierarchy      — ``build_hierarchy`` partitions data into
                        clusters of clients.
4. Rounds             — ``BHFLRuntime`` drives FEL + the five-phase
                        PoFEL consensus until target loss / max rounds.

The model family is a ``ModelAdapter`` (``repro.fl.adapters``); data is
auto-synthesized per family when not supplied (MNIST-like images for the
MLP, zipf token streams for LMs).
"""

from __future__ import annotations

import dataclasses
import difflib
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

# -- facade re-exports -------------------------------------------------------
from repro.core.btsv import BTSVConfig
from repro.core.consensus import ConsensusRecord, PoFELConsensus
from repro.core.phases import (BlockMint, CommitReveal, ConsensusPhase,
                               ModelEvaluation, RoundContext, Tally,
                               VoteCollection, run_phases)
from repro.data.synthetic import make_mnist_like
from repro.data.tokens import make_token_dataset
from repro.fl.adapters import (LMAdapter, MLPAdapter, ModelAdapter,
                               finch_adapter, make_adapter, rwkv6_adapter,
                               transformer_adapter)
from repro.fl.batched_fel import BatchedFELEngine, BatchedTrainSpec
from repro.fl.hfl_runtime import (AllNodesPlagiarizeError, BHFLConfig,
                                  BHFLRuntime, RoundMetrics)
from repro.fl.hierarchy import build_hierarchy
from repro.fl.sharded_consensus import ShardedModelEvaluation
from repro.obs import get_recorder
from repro.fl.task import (LearningTask, RewardLedger, TaskAgreement,
                           negotiate_task)

__all__ = [
    "run_bhfl", "BHFLRun",
    "LearningTask", "TaskAgreement", "RewardLedger", "negotiate_task",
    "BHFLConfig", "BHFLRuntime", "RoundMetrics", "build_hierarchy",
    "ModelAdapter", "MLPAdapter", "LMAdapter", "make_adapter",
    "transformer_adapter", "rwkv6_adapter", "finch_adapter",
    "PoFELConsensus", "ConsensusRecord", "BTSVConfig",
    "RoundContext", "ConsensusPhase", "CommitReveal", "ModelEvaluation",
    "VoteCollection", "Tally", "BlockMint", "run_phases",
    "ShardedModelEvaluation", "AllNodesPlagiarizeError",
    "BatchedFELEngine", "BatchedTrainSpec",
    "make_mnist_like", "make_token_dataset",
]


@dataclass
class BHFLRun:
    """Everything a finished (or stopped) BHFL task produced."""

    task: LearningTask
    agreement: TaskAgreement
    rewards: RewardLedger
    runtime: BHFLRuntime
    # the runtime's history: only the newest record keeps gw(k)
    history: List[RoundMetrics] = field(default_factory=list)
    # set when the run was driven through a repro.sim scenario/fault env
    scenario_report: Optional[Any] = None
    # metrics rollup from the active obs recorder (None when tracing off)
    obs: Optional[Dict[str, Any]] = None

    @property
    def chain_height(self) -> int:
        return self.runtime.consensus.ledgers[0].height

    @property
    def chain_valid(self) -> bool:
        return all(led.verify_chain()
                   for led in self.runtime.consensus.ledgers)

    @property
    def leader_counts(self) -> Dict[int, int]:
        return self.runtime.leader_counts()


def _default_task(max_rounds: int) -> LearningTask:
    return LearningTask(
        task_id="bhfl-task-0", publisher_id="model-owner-0",
        description="BHFL learning task (repro.api default)",
        target_loss=0.0, max_rounds=max_rounds, block_reward=10.0)


# every keyword run_bhfl itself accepts, for the did-you-mean hint
_RUN_BHFL_KWARGS = frozenset((
    "task", "model", "data", "cfg", "n_nodes", "clients_per_node",
    "fel_iterations", "rounds", "engine", "distribution", "gamma", "mu",
    "seed", "vote_hook", "plagiarists", "on_round", "scenario", "faults",
    "committees", "checkpoint_interval"))
# BHFLConfig fields not already exposed as explicit run_bhfl kwargs
_CFG_OVERRIDES = frozenset(
    f.name for f in dataclasses.fields(BHFLConfig)) - _RUN_BHFL_KWARGS


def _check_overrides(overrides: Dict[str, Any], cfg_given: bool) -> None:
    """Reject unknown keyword arguments loudly. A typo'd ``scenario=`` or
    ``engine=`` silently swallowed by a ``**kwargs`` catch-all would run
    the ideal world while the caller believes faults are active."""
    if not overrides:
        return
    unknown = set(overrides) - _CFG_OVERRIDES
    if unknown:
        hints = []
        for k in sorted(unknown):
            close = difflib.get_close_matches(
                k, sorted(_CFG_OVERRIDES | _RUN_BHFL_KWARGS), n=1)
            hints.append(k + (f" (did you mean {close[0]!r}?)"
                              if close else ""))
        raise TypeError(
            f"run_bhfl() got unexpected keyword argument(s): "
            f"{', '.join(hints)}; valid BHFLConfig overrides are "
            f"{sorted(_CFG_OVERRIDES)}")
    if cfg_given:
        raise ValueError(
            f"config overrides {sorted(overrides)} conflict with an "
            f"explicit cfg=; set them on the BHFLConfig instead")


def _default_data(adapter: ModelAdapter, seed: int) -> Tuple[Any, Any]:
    """Per-family synthetic (train, test) when the caller brings no data."""
    if isinstance(adapter, LMAdapter):
        return make_token_dataset(n_seqs=256, seq_len=16,
                                  vocab_size=adapter.arch.vocab_size,
                                  seed=seed)
    return make_mnist_like(n_train=4000, n_test=600, seed=seed)


def run_bhfl(task: Optional[LearningTask] = None,
             model: "str | ModelAdapter" = "mlp",
             data: Optional[Tuple[Any, Any]] = None,
             *,
             cfg: Optional[BHFLConfig] = None,
             n_nodes: Optional[int] = None,
             clients_per_node: Optional[int] = None,
             fel_iterations: Optional[int] = None,
             rounds: Optional[int] = None,
             engine: Optional[str] = None,
             distribution: str = "iid",
             gamma: Optional[Dict[int, float]] = None,
             mu: Optional[Dict[int, float]] = None,
             seed: Optional[int] = None,
             vote_hook: Optional[Callable] = None,
             plagiarists: Sequence[int] = (),
             on_round: Optional[Callable[[RoundMetrics], None]] = None,
             scenario: Optional[Any] = None,
             faults: Optional[Any] = None,
             committees: Optional[int] = None,
             checkpoint_interval: Optional[int] = None,
             **overrides: Any,
             ) -> BHFLRun:
    """Publish → negotiate → build hierarchy → run PoFEL rounds → settle.

    Args:
        task: the on-chain task announcement; a default is synthesized
            (``target_loss`` and ``max_rounds`` drive termination).
        model: 'mlp' | 'transformer' | 'rwkv6' | 'rwkv6-1.6b' (RWKV-6
            "Finch" 1.6B at its published widths) or a ``ModelAdapter``.
            'mlp' trains with ``cfg``'s (paper §7.1) hyperparameters; the
            named LM families use their own LM-tuned defaults — pass an
            adapter instance (e.g. ``rwkv6_adapter(lr=...)``) to override.
        data: (train, test) datasets matching the adapter's batch format;
            synthesized per family when omitted.
        engine: FEL engine — 'reference' (paper-shaped per-client loop,
            the default), 'batched' (in-graph vmap/scan fast path — one
            jitted program per round), or 'auto' (batched when the
            adapter supports it). See ``repro.fl.batched_fel``.
        cfg: full ``BHFLConfig`` override; otherwise one is built from
            ``n_nodes``/``clients_per_node``/``fel_iterations``/``seed``
            (defaults 6/4/2/0). Passing ``cfg`` together with a
            conflicting sizing kwarg raises.
        rounds: cap on rounds this call (default ``task.max_rounds``).
        gamma/mu: per-node Stackelberg cost/weight parameters (defaults
            match the paper's §7 ranges).
        seed: governs data synthesis, partitioning, gamma draws, model
            init, and — under a scenario — the network/adversary rng
            (one seed for the whole run).
        vote_hook/plagiarists: adversary injection (paper §7.4 attacks).
        on_round: callback fired with each round's ``RoundMetrics``.
        scenario: a ``repro.sim`` scenario name (e.g.
            ``"byzantine_third"``) or ``Scenario`` object — the run's
            consensus rounds then travel the fault-injected message bus
            and the result carries ``run.scenario_report``. The scenario
            supplies sizing defaults (nodes/clients/rounds/data) that
            explicit kwargs override.
        faults: a prebuilt ``repro.sim.SimEnv`` for ad-hoc fault
            injection without a registered scenario (mutually exclusive
            with ``scenario``).
        committees: > 1 shards the run into that many committee-scoped
            PoFEL instances with cross-shard checkpoint sync
            (``repro.fl.consortium``). Defaults to the scenario's
            ``committees`` (1 without a scenario); an explicit value
            overrides the scenario, so ``committees=1`` runs a consortium
            scenario as one global committee (the K=1 benchmark
            baseline).
        checkpoint_interval: rounds between cross-shard checkpoint
            epochs; defaults to the scenario's.
        **overrides: ``BHFLConfig`` training fields forwarded by name
            (e.g. ``lr=1e-2``, ``batch_size=16``). An unknown name
            raises ``TypeError`` (with a did-you-mean hint) instead of
            being silently ignored — a typo'd ``scenario=``/``engine=``
            must not turn into an unfaulted run.

    Returns:
        ``BHFLRun`` with the negotiated agreement, settled rewards, the
        runtime (consensus, ledgers, phases), per-round metrics, and —
        for scenario runs — the ``ScenarioReport``.
    """
    _check_overrides(overrides, cfg_given=cfg is not None)
    sc = None
    if scenario is not None:
        if faults is not None:
            raise ValueError("pass scenario= or faults=, not both")
        from repro.sim import Scenario, get_scenario
        sc = get_scenario(scenario) if isinstance(scenario, str) \
            else scenario
        if not isinstance(sc, Scenario):
            raise TypeError(f"scenario= must be a name or Scenario, "
                            f"got {type(sc).__name__}")
        # scenario sizing fills whatever the caller left unspecified
        if cfg is None:
            n_nodes = n_nodes if n_nodes is not None else sc.n_nodes
            clients_per_node = (clients_per_node if clients_per_node
                                is not None else sc.clients_per_node)
            fel_iterations = (fel_iterations if fel_iterations is not None
                              else sc.fel_iterations)
        rounds = rounds if rounds is not None else sc.rounds
    cfg_given = cfg is not None
    if cfg is None:
        cfg = BHFLConfig(n_nodes=n_nodes if n_nodes is not None else 6,
                         clients_per_node=clients_per_node
                         if clients_per_node is not None else 4,
                         fel_iterations=fel_iterations
                         if fel_iterations is not None else 2,
                         seed=seed if seed is not None else 0,
                         engine=engine if engine is not None else "reference")
    else:
        for kwarg, val, cfg_val in (
                ("n_nodes", n_nodes, cfg.n_nodes),
                ("clients_per_node", clients_per_node, cfg.clients_per_node),
                ("fel_iterations", fel_iterations, cfg.fel_iterations),
                ("engine", engine, cfg.engine),
                ("seed", seed, cfg.seed)):
            if val is not None and val != cfg_val:
                raise ValueError(
                    f"{kwarg}={val} conflicts with cfg.{kwarg}={cfg_val}; "
                    f"set it on cfg or drop the kwarg")
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    n_nodes = cfg.n_nodes
    clients_per_node = cfg.clients_per_node
    seed = cfg.seed     # one seed governs data, gamma draws, and init

    # resolve the adapter. BHFLConfig's training fields are the paper's
    # MLP hyperparameters, so they drive the MLP adapter only; named LM
    # adapters keep their own LM-tuned defaults (customize by passing an
    # adapter instance) and size their vocab from the caller's token data.
    if model == "mlp":
        adapter: ModelAdapter = cfg.default_adapter()
    elif isinstance(model, str):
        lm_kwargs: Dict[str, Any] = {}
        if data is not None and hasattr(data[0], "vocab_size"):
            lm_kwargs["vocab_size"] = data[0].vocab_size
        adapter = make_adapter(model, **lm_kwargs)
    else:
        adapter = make_adapter(model)
    if (isinstance(adapter, LMAdapter) and data is not None
            and getattr(data[0], "vocab_size", 0) > adapter.arch.vocab_size):
        raise ValueError(
            f"data vocab_size {data[0].vocab_size} exceeds the adapter's "
            f"{adapter.arch.vocab_size} — token ids would clamp silently")
    max_rounds = rounds if rounds is not None else (
        task.max_rounds if task is not None else 10)
    if task is None:
        task = _default_task(max_rounds)

    # 1-2. publication + incentive negotiation
    rng = np.random.default_rng(seed)
    node_ids = list(range(n_nodes))
    if gamma is None:
        gamma = {i: float(g)
                 for i, g in enumerate(rng.uniform(0.008, 0.02, n_nodes))}
    if mu is None:
        mu = {i: 5.0 for i in node_ids}
    agreement = negotiate_task(task, node_ids, gamma, mu)
    rewards = RewardLedger(agreement)

    # 3. hierarchy over (possibly synthesized) data
    if data is None:
        if sc is not None and isinstance(adapter, MLPAdapter):
            # scenario sizing: protocol behaviour under faults is the
            # object of study, so the workload stays small
            data = make_mnist_like(n_train=sc.n_train, n_test=sc.n_test,
                                   seed=seed)
        else:
            data = _default_data(adapter, seed)
    train, test = data
    if distribution != "iid" and not hasattr(train, "n_classes"):
        raise ValueError(
            f"distribution={distribution!r} needs labelled image data "
            f"(.y/.n_classes); {type(train).__name__} workloads support "
            f"'iid' only")
    clusters = build_hierarchy(train, n_nodes, clients_per_node,
                               distribution, seed=seed)

    # 4a. sharded consortium: K committee-scoped PoFEL instances with
    # cross-shard checkpoint sync (repro.fl.consortium). committees=1
    # (explicit or default) stays on the single-committee path below —
    # byte-identical to the pre-shard behaviour.
    k_committees = committees if committees is not None else (
        sc.committees if sc is not None else 1)
    if k_committees is not None and k_committees > 1:
        if faults is not None:
            raise ValueError(
                "faults= is unsupported with committees > 1; shape the "
                "consortium via a Scenario (net / cross_net / adversaries)")
        from repro.fl.consortium import ConsortiumRuntime
        from repro.sim import Scenario as _Scenario
        csc = sc
        if csc is None:
            csc = _Scenario(
                name=f"consortium_k{k_committees}",
                description="ad-hoc consortium run (api.run_bhfl)",
                rounds=max_rounds, n_nodes=cfg.n_nodes,
                clients_per_node=cfg.clients_per_node)
        if (csc.committees != k_committees
                or (checkpoint_interval is not None
                    and csc.checkpoint_interval != checkpoint_interval)):
            csc = dataclasses.replace(
                csc, committees=k_committees,
                committee_sizes=(csc.committee_sizes
                                 if csc.committees == k_committees
                                 else None),
                checkpoint_interval=(checkpoint_interval
                                     if checkpoint_interval is not None
                                     else csc.checkpoint_interval))
        consortium = ConsortiumRuntime(clusters, cfg, test, adapter=adapter,
                                       scenario=csc, seed=seed)
        if vote_hook is not None:
            consortium.set_vote_hook(vote_hook)
        if plagiarists:
            consortium.set_plagiarists(plagiarists)
        run = BHFLRun(task, agreement, rewards, consortium,
                      consortium.history)
        for _ in range(min(max_rounds, task.max_rounds)):
            round_metrics = consortium.run_round()
            for gid in consortium.last_leaders:
                rewards.settle_round(gid)
            if on_round is not None:
                for m in round_metrics:
                    on_round(m)
            losses = [m.test_loss for m in round_metrics
                      if not np.isnan(m.test_loss)]
            if test is not None and losses \
                    and max(losses) <= task.target_loss:
                break
        run.scenario_report = consortium.finalize(
            csc.name, seed, rounds_requested=consortium.rounds_run)
        rec = get_recorder()
        if rec.enabled:
            run.obs = rec.metrics_snapshot()
        return run

    # 4b. FEL + consensus rounds until termination (single committee)
    runtime = BHFLRuntime(clusters, cfg, test, adapter=adapter)
    runtime.vote_hook = vote_hook
    runtime.plagiarists = set(plagiarists)
    env = faults
    if sc is not None:
        from repro.sim import build_env
        env = build_env(sc, n_nodes=cfg.n_nodes, seed=seed)
    if env is not None:
        if env.network.n_nodes != cfg.n_nodes:
            raise ValueError(
                f"faults/scenario env simulates {env.network.n_nodes} "
                f"nodes but the run has n_nodes={cfg.n_nodes}")
        runtime.env = env
        env.bind(runtime.consensus)
        runtime.plagiarists |= env.plagiarist_ids()
    run = BHFLRun(task, agreement, rewards, runtime, runtime.history)
    for _ in range(min(max_rounds, task.max_rounds)):
        m = runtime.run_round()
        if m.leader_id >= 0:    # aborted rounds reward no leader
            rewards.settle_round(m.leader_id)
        if on_round is not None:
            on_round(m)
        if test is not None and m.test_loss <= task.target_loss:
            break
    if env is not None:
        run.scenario_report = env.finalize(
            scenario=sc.name if sc is not None else "custom",
            seed=seed, rounds_requested=len(runtime.history))
    rec = get_recorder()
    if rec.enabled:
        run.obs = rec.metrics_snapshot()
    return run
