"""Model adapters — the pluggable-workload boundary of the BHFL runtime.

The paper's experiments use one MNIST MLP, but nothing in PoFEL depends on
the model family: HCDS commits to bytes, ME flattens to a vector, and the
chain stores digests. ``ModelAdapter`` captures exactly the contract the
runtime needs — init / train-step / eval / flatten / unflatten — so
``BHFLRuntime`` drives an MLP, a transformer, or an RWKV6 LM through the
identical consensus path.

Adapters:

* :class:`MLPAdapter`   — the paper-faithful MNIST MLP (§7.1).
* :class:`LMAdapter`    — any ``repro.models.model_api.Model`` family over
  token data; :func:`transformer_adapter` and :func:`rwkv6_adapter` build
  reduced-scale instances that run on CPU, :func:`finch_adapter` RWKV-6
  "Finch" 1.6B at its published widths (``"rwkv6-1.6b"``).

Flatten/unflatten share the canonical sorted-keypath roundtrip in
``repro.core.serialization``, so model bytes, ME vectors, and checkpoint
digests always agree.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core.serialization import flatten_pytree, unflatten_pytree
from repro.fl.client import Client
from repro.models.config import ArchConfig
from repro.models.mlp import MLPConfig, mlp_accuracy, mlp_init, mlp_loss
from repro.models.model_api import Model
from repro.obs import device_wait, get_recorder
from repro.optim.sgd import sgd_init, sgd_update


class EvalResult(NamedTuple):
    accuracy: float
    loss: float


@runtime_checkable
class ModelAdapter(Protocol):
    """What ``BHFLRuntime`` needs from a workload. All methods are pure in
    params; the adapter owns hyperparameters and batch semantics.

    ``flatten``/``unflatten`` are not free to choose any self-consistent
    layout: the consensus computes gw(k) in the CANONICAL sorted-keypath
    order (``core.serialization.flatten_pytree`` — the same order HCDS
    commits to) and the runtime adopts it via ``adapter.unflatten``, so
    both must implement that layout. Inherit them from the provided base
    (as :class:`MLPAdapter`/:class:`LMAdapter` do) unless you have a
    reason to reimplement; ``BHFLRuntime`` checks the contract at init.
    """

    name: str

    def init(self, key: jax.Array) -> Any:
        """Fresh parameter pytree."""
        ...

    def local_train(self, params: Any, client: Client, *,
                    seed: int = 0) -> tuple[Any, float]:
        """One client's local training pass; returns (params, last loss)."""
        ...

    def evaluate(self, params: Any, dataset: Any) -> EvalResult:
        """(accuracy, loss) of ``params`` on a held-out dataset."""
        ...

    def flatten(self, params: Any) -> jax.Array:
        """Canonical flat float32 vector (ME / consensus layout)."""
        ...

    def unflatten(self, flat: Any, template: Any) -> Any:
        """Inverse of :meth:`flatten`, shaped/dtyped like ``template``."""
        ...

    # Optional: adapters that can train inside the batched in-graph FEL
    # engine additionally expose
    #
    #     def batched_train_spec(self) -> repro.fl.batched_fel.BatchedTrainSpec
    #
    # (sample-major dataset stacking + a per-example loss). Adapters
    # without it simply fall back to the per-client reference loop when
    # ``BHFLConfig(engine="batched")`` is requested with engine="auto"
    # semantics — see ``repro.fl.batched_fel.engine_for``.


class _SerializationFlatten:
    """Shared flatten/unflatten via the canonical serialization roundtrip."""

    def flatten(self, params: Any) -> jax.Array:
        return flatten_pytree(params)

    def unflatten(self, flat: Any, template: Any) -> Any:
        return unflatten_pytree(flat, template)


# ---------------------------------------------------------------------------
# Paper-faithful MLP (MNIST, §7.1)
# ---------------------------------------------------------------------------

@dataclass
class MLPAdapter(_SerializationFlatten):
    """The paper's 784-hidden-10 MLP over ``SyntheticImageDataset`` shards,
    trained with SGD+momentum+decay exactly as §7.1 specifies."""

    cfg: MLPConfig = MLPConfig()
    local_epochs: int = 1
    batch_size: int = 32
    lr: float = 1e-3
    momentum: float = 0.9
    decay: float = 5e-4

    name: str = "mlp"

    def init(self, key: jax.Array) -> Any:
        return mlp_init(self.cfg, key)

    def local_train(self, params: Any, client: Client, *,
                    seed: int = 0) -> tuple[Any, float]:
        from repro.fl.client import local_train
        return local_train(params, client, self.cfg,
                           epochs=self.local_epochs,
                           batch_size=self.batch_size, lr=self.lr,
                           momentum=self.momentum, decay=self.decay,
                           seed=seed)

    def evaluate(self, params: Any, dataset: Any) -> EvalResult:
        rec = get_recorder()
        with rec.span("device.put", on="test_set") as put:
            x = jnp.asarray(dataset.x)
            y = jnp.asarray(dataset.y)
            if rec.enabled:
                put.set(h2d_bytes=sum(a.nbytes for a in (dataset.x, dataset.y)
                                      if not isinstance(a, jax.Array)))
        acc = mlp_accuracy(params, x, y, cfg=self.cfg)
        loss = mlp_loss(params, x, y, cfg=self.cfg)
        device_wait("eval", (acc, loss))
        return EvalResult(float(acc), float(loss))

    def batched_train_spec(self):
        """Batched in-graph FEL support (``repro.fl.batched_fel``).

        Memoized per adapter: the spec's ``per_example_loss`` identity keys
        the engine's shared jit cache, so runtimes rebuilt from the same
        adapter at shape-bucket-compatible scales reuse one compiled round
        program instead of re-tracing."""
        if getattr(self, "_batched_spec", None) is not None:
            return self._batched_spec
        import numpy as np
        from repro.fl.batched_fel import BatchedTrainSpec
        from repro.models.mlp import mlp_per_example_loss
        cfg = self.cfg

        def stack(dataset):
            return {"x": np.asarray(dataset.x, np.float32),
                    "y": np.asarray(dataset.y, np.int32)}

        def per_example(params, batch, key):
            return mlp_per_example_loss(params, batch["x"], batch["y"],
                                        cfg=cfg, train=True, dropout_key=key)

        self._batched_spec = BatchedTrainSpec(
            stack, per_example, self.local_epochs, self.batch_size, self.lr,
            self.momentum, self.decay)
        return self._batched_spec


# ---------------------------------------------------------------------------
# LM families (transformer / RWKV6 / hybrid) over TokenDataset shards
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("model",))
def _lm_sgd_step(model: Model, params: Any, opt_state, batch: dict,
                 lr: float, momentum: float, decay: float):
    loss, grads = jax.value_and_grad(model.loss)(params, batch)
    params, opt_state = sgd_update(grads, opt_state, params,
                                   lr=lr, momentum=momentum, decay=decay)
    return params, opt_state, loss


@dataclass
class LMAdapter(_SerializationFlatten):
    """Any ``model_api.Model`` family as a BHFL workload: FedSGD on
    next-token cross-entropy over ``TokenDataset`` client shards; eval is
    next-token top-1 accuracy + CE loss."""

    arch: ArchConfig
    local_epochs: int = 1
    batch_size: int = 8
    lr: float = 1e-2
    momentum: float = 0.9
    decay: float = 5e-4

    def __post_init__(self):
        self.model = Model(self.arch)
        self.name = self.arch.name

    def init(self, key: jax.Array) -> Any:
        return self.model.init(key)

    def local_train(self, params: Any, client: Client, *,
                    seed: int = 0) -> tuple[Any, float]:
        opt_state = sgd_init(params)
        loss = jnp.asarray(0.0)
        for ep in range(self.local_epochs):
            for batch in client.data.batches(
                    min(self.batch_size, client.data_size), seed=seed + ep):
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
                params, opt_state, loss = _lm_sgd_step(
                    self.model, params, opt_state, batch,
                    self.lr, self.momentum, self.decay)
        return params, float(loss)

    def batched_train_spec(self):
        """Batched in-graph FEL support (``repro.fl.batched_fel``): token
        rows stack densely; the per-example loss is the per-row mean token
        CE plus the (batch-global) aux term, so for the dense/ssm families
        (aux ≡ 0) the masked-mean reduction reproduces ``Model.loss``
        exactly. MoE families would see a padding-dependent aux term —
        route those through the reference loop.

        Memoized per adapter (see :meth:`MLPAdapter.batched_train_spec`)."""
        if getattr(self, "_batched_spec", None) is not None:
            return self._batched_spec
        import numpy as np
        from repro.fl.batched_fel import BatchedTrainSpec
        from repro.models.model_api import DEFAULT_AUX_WEIGHT
        model = self.model

        def stack(dataset):
            return {"rows": np.asarray(dataset.tokens, np.int32)}

        def per_example(params, batch, key):
            rows = batch["rows"]
            b = {"tokens": rows[:, :-1], "labels": rows[:, 1:]}
            logits, aux = model.forward(params, b)
            logits = logits.astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            vidx = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                            logits.ndim - 1)
            mask = vidx == b["labels"][..., None].astype(jnp.int32)
            gold = jnp.sum(jnp.where(mask, logits, 0.0), axis=-1)
            return jnp.mean(lse - gold, axis=-1) + DEFAULT_AUX_WEIGHT * aux

        self._batched_spec = BatchedTrainSpec(
            stack, per_example, self.local_epochs, self.batch_size, self.lr,
            self.momentum, self.decay)
        return self._batched_spec

    def evaluate(self, params: Any, dataset: Any) -> EvalResult:
        """Next-token top-1 accuracy and CE loss on ``dataset``'s rows, one
        jitted call per test-set shape."""
        rec = get_recorder()
        with rec.span("device.put", on="test_set") as put:
            rows = jnp.asarray(dataset.tokens)
            if rec.enabled and not isinstance(dataset.tokens, jax.Array):
                put.set(h2d_bytes=dataset.tokens.nbytes)
        acc, loss = _lm_evaluate(self.model, params, rows)
        device_wait("eval", (acc, loss))
        return EvalResult(float(acc), float(loss))


@partial(jax.jit, static_argnames=("model",))
def _lm_evaluate(model: Model, params: Any, rows: jax.Array):
    from repro.models.model_api import DEFAULT_AUX_WEIGHT, _token_ce_loss
    batch = {"tokens": rows[:, :-1], "labels": rows[:, 1:]}
    # one forward pass serves both metrics (Model.loss would rerun it)
    logits, aux = model.forward(params, batch)
    acc = jnp.mean((jnp.argmax(logits, axis=-1)
                    == batch["labels"]).astype(jnp.float32))
    loss = _token_ce_loss(logits, batch["labels"]) + DEFAULT_AUX_WEIGHT * aux
    return acc, loss


def tiny_transformer_config(vocab_size: int = 256, d_model: int = 64,
                            n_layers: int = 2) -> ArchConfig:
    """CPU-scale dense transformer for BHFL rounds and tests."""
    return ArchConfig(
        name="bhfl-transformer-tiny", family="dense",
        n_layers=n_layers, d_model=d_model, n_heads=2, n_kv_heads=2,
        head_dim=d_model // 2, d_ff=2 * d_model, vocab_size=vocab_size,
        source="repro.fl.adapters")


def tiny_rwkv6_config(vocab_size: int = 256, d_model: int = 64,
                      n_layers: int = 2) -> ArchConfig:
    """CPU-scale RWKV-6 (attention-free) for BHFL rounds and tests."""
    return ArchConfig(
        name="bhfl-rwkv6-tiny", family="ssm",
        n_layers=n_layers, d_model=d_model, n_heads=d_model // 32,
        n_kv_heads=d_model // 32, d_ff=2 * d_model, vocab_size=vocab_size,
        rwkv=True, rwkv_head_size=32, rwkv_mix_lora=8, rwkv_decay_lora=8,
        source="repro.fl.adapters")


def transformer_adapter(vocab_size: int = 256, d_model: int = 64,
                        n_layers: int = 2, **hp) -> LMAdapter:
    return LMAdapter(tiny_transformer_config(vocab_size, d_model, n_layers),
                     **hp)


def rwkv6_adapter(vocab_size: int = 256, d_model: int = 64,
                  n_layers: int = 2, **hp) -> LMAdapter:
    return LMAdapter(tiny_rwkv6_config(vocab_size, d_model, n_layers), **hp)


_HYPERPARAMETERS = ("local_epochs", "batch_size", "lr", "momentum", "decay")


def finch_adapter(**kwargs) -> LMAdapter:
    """RWKV-6 "Finch" 1.6B (``configs/rwkv6_1_6b``) at its published
    widths. ``LMAdapter`` hyperparameters pass through; any other keyword
    replaces that ``ArchConfig`` field, e.g. ``n_layers`` or
    ``vocab_size`` for a cut in depth or vocabulary."""
    from repro.configs import get_config
    hp = {k: kwargs.pop(k) for k in _HYPERPARAMETERS if k in kwargs}
    return LMAdapter(dataclasses.replace(get_config("rwkv6-1.6b"), **kwargs),
                     **hp)


_NAMED = {"mlp": MLPAdapter, "transformer": transformer_adapter,
          "rwkv6": rwkv6_adapter, "rwkv6-1.6b": finch_adapter}


def make_adapter(model: "str | ModelAdapter", **kwargs) -> ModelAdapter:
    """Resolve ``model`` to an adapter: pass through an adapter instance,
    or build one by name ('mlp' | 'transformer' | 'rwkv6' |
    'rwkv6-1.6b')."""
    if isinstance(model, str):
        try:
            return _NAMED[model](**kwargs)
        except KeyError:
            raise ValueError(
                f"unknown model {model!r}; choose from {sorted(_NAMED)} "
                f"or pass a ModelAdapter instance") from None
    if isinstance(model, ModelAdapter):
        return model
    raise TypeError(f"model must be a name or ModelAdapter, got {type(model)}")
