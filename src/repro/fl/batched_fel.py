"""Batched in-graph FEL engine — one jitted program per BCFL round.

The paper-faithful reference loop (``BHFLRuntime._run_fel``) runs a Python
quadruple loop — clusters × clients × fel_iterations × batches — of tiny
jit dispatches with host-side FedAvg between iterations. This module turns
the whole FEL phase of a round into ONE device program:

* every cluster's client shards are stacked into padded ``(C, n_max, ...)``
  device arrays (per-client sizes masked),
* one client's local SGD is a ``lax.scan`` over its epochs × batches,
* ``jax.vmap`` maps it across the C clients of a cluster,
* FedAvg (Eq. 1 at the edge) is a masked weighted reduction in-graph,
* ``lax.scan`` drives the ``fel_iterations`` train→aggregate cycles, and
* an outer ``jax.vmap`` maps the whole cluster round across the N clusters,

so one call produces the stacked flat ``(N, D)`` model matrix W(k) that
Model Evaluation consumes directly — no per-model flatten, no host hops.

Layout. The vmapped program above holds every client's training state at
once: N·C copies of the float32 parameters, momentum and gradients, and
the saved activations. The engine counts, from the shapes, the bytes of
one client in flight (:meth:`BatchedFELEngine._client_bytes`: its
parameters, momentum, gradients and gathered batch; the saved
activations are not counted) and compares N·C of them, beside the fixed
buffers, with the device's ``bytes_limit``. Where they do not fit, the
same round runs ``sequential``: clusters under ``lax.map``, clients under a
``lax.scan`` that adds each client's masked Eq. 1 share into one running
float32 sum, so one client's state lives at a time. Same seeds, batch
plan and masks; the FedAvg sum is reduced in client order (the vmapped
einsum reduces in the backend's order). A backend that reports no limit
(the CPU) keeps the vmapped program.

Numerical contract: with the same seeds the engine reproduces the
reference loop step for step — identical batch permutations (the same
numpy RNG stream, precomputed host-side into an index tensor), identical
dropout masks (``models.mlp.dropout_mask`` is batch-position-stable), an
identical per-client PRNG split sequence (masked padding steps do not
advance the key or the decay step counter), and FedAvg weights that zero
out padded/empty clients exactly. ``tests/test_batched_fel.py`` pins the
two paths against each other, including ragged/empty shards and the
plagiarist path.

Shape bucketing (``bucket=True`` / ``BHFLConfig(shape_bucketing=True)``):
the client, sample, step, and batch dimensions are padded up to the next
power of two (padding is masked, so it is bit-exact — a zero FedAvg
weight, an inactive step, or a zero-masked batch row adds exact zeros).
Together with the module-level jit cache keyed on the training spec (the
padded shapes key jax's own cache), a runtime rebuilt at a nearby scale —
one more client per cluster, a somewhat larger shard — lands in the same
bucket and reuses the already-compiled round program instead of paying a
fresh XLA compile. :func:`compile_count` exposes the trace counter so
tests can pin the cache-hit behaviour. Bucketing trades some wasted
device compute (padded client slots still run their masked steps) for
compile reuse, so it defaults OFF — turn it on when runtimes are rebuilt
frequently at many scales (the ROADMAP's sweep/serving case); exactly
matching shapes share compiles either way via the module cache.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.serialization import flatten_pytree, unflatten_pytree_device
from repro.fl.hierarchy import FELCluster
from repro.obs import get_recorder


def _next_pow2(x: int) -> int:
    """The bucket boundary: smallest power of two ≥ x (min 1)."""
    return 1 if x <= 1 else 1 << (x - 1).bit_length()


# jitted round programs shared across engine instances: keyed on the
# training spec (loss fn identity + hyperparameters) and the static build
# flags; argument shapes/dtypes key jax.jit's own cache underneath. Two
# runtimes whose bucketed shapes coincide therefore reuse one compiled
# executable — the point of the pow2 bucketing above. Bounded FIFO: the
# key contains the spec's loss closure, which is fresh per adapter
# instance, so default-adapter runs (one adapter per runtime) would
# otherwise accumulate immortal never-hit entries across a sweep.
_ROUND_FN_CACHE: "OrderedDict[Tuple, Any]" = OrderedDict()
_ROUND_FN_CACHE_MAX = 32
_TRACE_COUNT = [0]


def device_bytes_limit() -> Optional[int]:
    """Bytes the default device can hold, where its backend reports them
    (``memory_stats()["bytes_limit"]``); None elsewhere."""
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return int(limit) if limit else None


def _nbytes(tree: Any) -> int:
    return sum(int(np.prod(a.shape, dtype=np.int64)) * np.dtype(a.dtype).itemsize
               for a in jax.tree.leaves(tree))


def compile_count() -> int:
    """How many times a batched round program has been traced (≈ compiled)
    in this process — the observable for shape-bucket cache-hit tests."""
    return _TRACE_COUNT[0]


@dataclass(frozen=True)
class BatchedTrainSpec:
    """What the engine needs from a ``ModelAdapter`` to train in-graph.

    ``stack`` turns one client dataset into a sample-major pytree of numpy
    arrays (leading axis = samples; empty shards yield 0-row arrays of the
    same structure). ``per_example_loss(params, batch, key) -> (B,)``
    returns per-sample losses for a gathered batch pytree — the engine
    reduces them with the padding mask, so padded rows must simply be
    finite (they are multiplied by zero).
    """

    stack: Callable[[Any], Any]
    per_example_loss: Callable[[Any, Any, jax.Array], jax.Array]
    local_epochs: int
    batch_size: int
    lr: float
    momentum: float
    decay: float


class BatchedFELEngine:
    """Compiles the FEL phase of a BCFL round into one device program.

    Built once per runtime (shapes are fixed by the hierarchy); per round
    only the batch-permutation index tensor and the per-client seeds
    change, so every round reuses a single compiled executable.
    """

    def __init__(self, clusters: List[FELCluster], spec: BatchedTrainSpec,
                 fel_iterations: int, template_params: Any,
                 bucket: bool = False):
        if fel_iterations < 1:
            raise ValueError(f"fel_iterations must be >= 1, got {fel_iterations}")
        self.spec = spec
        self.fel_iterations = int(fel_iterations)
        self.bucket = bool(bucket)
        self.n_clusters = len(clusters)
        self.n_clients = max((len(c.clients) for c in clusters), default=0)
        if self.n_clusters == 0 or self.n_clients == 0:
            raise ValueError("batched engine needs at least one cluster "
                             "with at least one client")
        # the shapes and dtypes the round program unflattens gw(k-1) into;
        # the engine holds no parameter values of its own
        self._template = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), template_params)

        def _dim(x: int) -> int:
            """Bucketed axis extent: next pow2 under bucketing, exact else."""
            return _next_pow2(x) if self.bucket else max(1, int(x))

        # bucket the client axis: padded clients carry zero data, zero
        # FedAvg weight, and an all-False step mask, so nearby hierarchy
        # shapes share one compiled program (bit-exact — see module doc)
        N, E = self.n_clusters, spec.local_epochs
        C = _dim(self.n_clients)
        self.n_clients_padded = C
        sizes = np.zeros((N, C), np.int64)
        client_ids = np.zeros((N, C), np.int64)
        for n, cluster in enumerate(clusters):
            for c, client in enumerate(cluster.clients):
                sizes[n, c] = client.data_size
                client_ids[n, c] = client.client_id
        self._sizes = sizes
        self._client_ids = client_ids

        # per-client batch size / step count (reference semantics:
        # bs = min(batch_size, size), drop-remainder batching, E epochs)
        bs = np.where(sizes > 0, np.minimum(spec.batch_size, sizes), 1)
        nb = np.where(sizes > 0, sizes // bs, 0)
        steps = E * nb
        self._bs = bs.astype(np.int32)
        self._nb = nb
        # bucket the step and batch axes too: masked steps advance nothing
        # and zero-masked batch rows reduce to exact zeros
        self.steps_per_iteration = _dim(int(steps.max()))
        self.batch_pad = _dim(int(bs.max()))

        T, B = self.steps_per_iteration, self.batch_pad
        stepmask = np.zeros((N, C, T), bool)
        for n in range(N):
            for c in range(C):
                stepmask[n, c, : steps[n, c]] = True
        self._stepmask = jnp.asarray(stepmask)
        # static fast path: uniform shards (every client runs every step at
        # full batch width) need none of the per-step masking selects.
        # Under bucketing the masked path is forced even for a fully
        # aligned hierarchy — the flag is a static program split, and a
        # bucket must not fork its compile cache on alignment luck (the
        # masked reduction is bitwise-identical when the mask is full).
        self._uniform = (not self.bucket and bool(stepmask.all())
                         and bool((bs == B).all()))

        # stack client shards into padded (N, C, n_max, ...) device leaves
        proto = None
        for cluster in clusters:
            for client in cluster.clients:
                if client.data_size > 0:
                    proto = spec.stack(client.data)
                    break
            if proto is not None:
                break
        if proto is None:
            raise ValueError("batched engine needs at least one non-empty "
                             "client shard")
        self.n_max = _dim(int(sizes.max()))

        def padded(client) -> Any:
            stacked = (spec.stack(client.data) if client is not None
                       else jax.tree.map(lambda a: a[:0], proto))
            def pad(leaf):
                leaf = np.asarray(leaf)
                out = np.zeros((self.n_max,) + leaf.shape[1:], leaf.dtype)
                out[: leaf.shape[0]] = leaf
                return out
            return jax.tree.map(pad, stacked)

        rows = []
        for cluster in clusters:
            cl = list(cluster.clients) + [None] * (C - len(cluster.clients))
            rows.append(jax.tree.map(lambda *ls: np.stack(ls),
                                     *[padded(cli) for cli in cl]))
        self._data = jax.tree.map(lambda *ls: jnp.asarray(np.stack(ls)), *rows)
        self._sizes_f = jnp.asarray(sizes, jnp.float32)
        self._bs_dev = jnp.asarray(self._bs)

        self.layout, self.clients_in_flight = self._choose_layout()
        self._round_fn = self._cached_round_fn()

    # -- layout: every client in flight, or one --------------------------------
    def _unrolls(self) -> tuple:
        """(unroll of the step scan, unroll of the FEL-iteration scan).
        The sequential layout runs where memory is short, and a step loop
        keeps two copies of its parameter and momentum carry: there a few
        steps are unrolled."""
        T, I = self.steps_per_iteration, self.fel_iterations
        few = T == 1 or (self.layout == "sequential" and T <= 8)
        return (True if few else 1,
                True if (T == 1 and I <= 8) else 1)

    def _choose_layout(self) -> Tuple[str, int]:
        """(layout, clients in flight): ``vmap`` with all N·C when their
        training state fits the device beside the round's fixed buffers
        (W(k), one parameter carry per cluster, the global model in and
        its float32 copy, the stacked data), else ``sequential`` with one.
        """
        slots = self.n_clusters * self.n_clients_padded
        limit = device_bytes_limit()
        if limit is None:
            return "vmap", slots
        p32 = 4 * sum(int(np.prod(a.shape, dtype=np.int64))
                      for a in jax.tree.leaves(self._template))
        fixed = (2 * self.n_clusters + 2) * p32 + _nbytes(self._data)
        if fixed + slots * self._client_bytes(p32) > limit:
            return "sequential", 1
        return "vmap", slots

    def _client_bytes(self, p32: int) -> int:
        """Device bytes of one client's local training, from the shapes:
        float32 parameters, momentum and gradients, and the gathered
        batch. The saved activations are left out: the state alone
        decides for the models run so far (the paper's MLP fits many
        times over, RWKV-6 1.6B does not fit once)."""
        batch = _nbytes(self._data) * self.batch_pad // max(
            1, self.n_clusters * self.n_clients_padded * self.n_max)
        return 3 * p32 + batch

    # -- the single-device-program round ------------------------------------
    def _cached_round_fn(self):
        """The jitted round program for this engine's static configuration,
        shared across engine instances through the module-level cache.

        Everything value-dependent (the stacked data, sizes, masks) is a
        traced *argument*, so the only cache-key material is the training
        spec, the layout, the unroll flags and the parameters' shapes and
        dtypes — rebuilt runtimes whose bucketed shapes match re-enter
        jax.jit's own cache and skip compilation entirely.
        """
        spec = self.spec
        unroll_steps, unroll_iters = self._unrolls()
        leaves, treedef = jax.tree_util.tree_flatten(self._template)
        key = (spec.per_example_loss, spec.lr, spec.momentum, spec.decay,
               self._uniform, self.batch_pad, unroll_steps, unroll_iters,
               self.layout, treedef,
               tuple((a.shape, str(a.dtype)) for a in leaves))
        fn = _ROUND_FN_CACHE.get(key)
        rec = get_recorder()
        if rec.enabled:
            rec.counter("fel.round_fn_cache_hits" if fn is not None
                        else "fel.round_fn_cache_misses")
        if fn is None:
            fn = jax.jit(_build_round_fn(spec, self._uniform, self.batch_pad,
                                         unroll_steps, unroll_iters,
                                         self.layout, self._template))
            _ROUND_FN_CACHE[key] = fn
            if len(_ROUND_FN_CACHE) > _ROUND_FN_CACHE_MAX:
                _ROUND_FN_CACHE.popitem(last=False)
        return fn

    # -- host-side per-round prep (cheap: numpy permutations only) -----------
    def _batch_plan(self, round_seed: int) -> tuple[np.ndarray, np.ndarray]:
        """Replicates the reference batch stream: per (iteration, client,
        epoch) the same ``np.random.default_rng(seed + ep).permutation``
        and the same drop-remainder windows, flattened into an index
        tensor (I, N, C, T, B) plus per-client key seeds (I, N, C)."""
        I, N, C = self.fel_iterations, self.n_clusters, self.n_clients_padded
        T, B, E = self.steps_per_iteration, self.batch_pad, self.spec.local_epochs
        idx = np.zeros((I, N, C, T, B), np.int32)
        seeds = np.zeros((I, N, C), np.int64)
        for it in range(I):
            for n in range(N):
                for c in range(C):
                    seed = round_seed * 1000 + int(self._client_ids[n, c]) * 10 + it
                    seeds[it, n, c] = seed
                    size = int(self._sizes[n, c])
                    if size == 0:
                        continue
                    bs = int(self._bs[n, c])
                    t = 0
                    for ep in range(E):
                        order = np.random.default_rng(seed + ep).permutation(size)
                        for s in range(0, size - bs + 1, bs):
                            idx[it, n, c, t, :bs] = order[s:s + bs]
                            t += 1
        return idx, seeds

    def _prep(self, round_seed: int) -> tuple[jax.Array, jax.Array]:
        """The round's batch plan, on the device."""
        idx, seeds = self._batch_plan(round_seed)
        i32 = np.iinfo(np.int32)
        if np.any(seeds > i32.max) or np.any(seeds < i32.min):
            raise ValueError(
                f"per-client seed overflows int32 (round_seed={round_seed}); "
                "keep cfg.seed * 1000 + rounds within int32 range")
        return jnp.asarray(idx), jnp.asarray(seeds.astype(np.int32))

    def run_round(self, global_flat: jax.Array, round_seed: int) -> jax.Array:
        """One FEL phase: (D,) global model → stacked (N, D) W(k), all on
        device; one compiled-program dispatch."""
        rec = get_recorder()
        if not rec.enabled:
            idx, seeds = self._prep(round_seed)
            return self._round_fn(jnp.asarray(global_flat), idx, seeds,
                                  self._data, self._sizes_f, self._bs_dev,
                                  self._stepmask)
        # the host's share: the batch plan and its upload
        with rec.span("fel.prep", cat="fel") as prep:
            idx, seeds = self._prep(round_seed)
            prep.set(h2d_bytes=idx.nbytes + seeds.nbytes)
        # dispatch only — jax execution is async, so this span measures
        # trace/compile + program launch, not device runtime (that is the
        # device.wait where the host first reads W); ``compiled`` marks
        # dispatches that traced a fresh program (the jit-compile half of
        # the compile-vs-execute split)
        traces_before = _TRACE_COUNT[0]
        rec.open_span("fel.dispatch", cat="fel", layout=self.layout,
                      clients_in_flight=self.clients_in_flight)
        W = self._round_fn(jnp.asarray(global_flat), idx, seeds,
                           self._data, self._sizes_f, self._bs_dev,
                           self._stepmask)
        rec.close_span(compiled=_TRACE_COUNT[0] > traces_before)
        rec.counter("fel.dispatches")
        if self.layout == "sequential":
            rec.counter("fel.sequential_dispatches")
        return W


def _make_train_client(spec: BatchedTrainSpec, uniform: bool, B: int,
                       unroll_steps):
    """One client's local SGD, ``(params, data_c, bs_c, idx_c, smask_c,
    seed) -> params``: a ``lax.scan`` over its epochs × batches. Padding
    steps (smask False) advance neither params, momentum, the decay step
    counter, nor the PRNG key — exactly the reference loop. When every
    shard is uniform (no padding steps, full batch width — checked
    statically at engine build) the masking selects disappear from the
    compiled program entirely."""

    def train_client(params, data_c, bs_c, idx_c, smask_c, seed):
        key0 = jax.random.key(seed)
        mom0 = jax.tree.map(jnp.zeros_like, params)

        def step(carry, xs):
            p, mom, t, key = carry
            sel, real = xs
            nkey, sub = jax.random.split(key)
            batch = jax.tree.map(lambda a: a[sel], data_c)

            def loss_fn(pp):
                pe = spec.per_example_loss(pp, batch, sub)
                if uniform:
                    return jnp.mean(pe)
                m = ((jnp.arange(B) < bs_c) & real).astype(jnp.float32)
                return jnp.sum(pe * m) / jnp.maximum(jnp.sum(m), 1.0)

            loss, grads = jax.value_and_grad(loss_fn)(p)
            # sgd_update semantics: keras-style time-based decay
            lr_t = spec.lr / (1.0 + spec.decay * t.astype(jnp.float32))
            nmom = jax.tree.map(lambda m_, g: spec.momentum * m_ + g,
                                mom, grads)
            newp = jax.tree.map(lambda a, m_: a - lr_t * m_, p, nmom)
            if uniform:
                p, mom = newp, nmom
                t = t + 1
                key = nkey
            else:
                p = jax.tree.map(
                    lambda new, old: jnp.where(real, new, old), newp, p)
                mom = jax.tree.map(
                    lambda new, old: jnp.where(real, new, old), nmom, mom)
                t = t + real.astype(jnp.int32)
                key = jnp.where(real, nkey, key)
            return (p, mom, t, key), loss

        init = (params, mom0, jnp.zeros((), jnp.int32), key0)
        # unrolling pays only when the while-loop overhead dominates
        # (single-step iterations); at larger T it just inflates
        # compile time for no runtime win
        (pf, _, _, _), _ = jax.lax.scan(step, init, (idx_c, smask_c),
                                        unroll=unroll_steps)
        return pf

    return train_client


def _build_round_fn(spec: BatchedTrainSpec, uniform: bool, B: int,
                    unroll_steps, unroll_iters, layout: str, template: Any):
    """The (unjitted) round program for one static configuration.

    Everything instance-specific — the stacked client data, sizes, batch
    widths and step masks — arrives as traced arguments, so one jitted
    wrapper serves every engine whose bucketed shapes match (see
    :class:`BatchedFELEngine._cached_round_fn`). ``template`` gives the
    parameters' shapes and dtypes (``ShapeDtypeStruct`` leaves).
    ``layout`` is ``vmap`` (every client in flight) or ``sequential``
    (clusters under ``lax.map``, clients under ``lax.scan``).
    """
    train_client = _make_train_client(spec, uniform, B, unroll_steps)

    def vmapped_clients(params, data_n, sizes_n, bs_n, idx_i, smask_n,
                        seeds_i):
        locals_ = jax.vmap(train_client, in_axes=(None, 0, 0, 0, 0, 0))(
            params, data_n, bs_n, idx_i, smask_n, seeds_i)
        # Eq. 1 at the edge: data-size weights; empty/padded
        # clients carry exact zero weight so they drop out of the
        # reduction bit-for-bit
        lam = sizes_n / jnp.maximum(jnp.sum(sizes_n), 1.0)
        return jax.tree.map(
            lambda l: jnp.einsum("c,c...->...", lam,
                                 l.astype(jnp.float32)).astype(l.dtype),
            locals_)

    def sequential_clients(params, data_n, sizes_n, bs_n, idx_i, smask_n,
                           seeds_i):
        lam = sizes_n / jnp.maximum(jnp.sum(sizes_n), 1.0)

        def add_client(acc, xs):
            data_c, bs_c, idx_c, smask_c, seed, lam_c = xs
            local = train_client(params, data_c, bs_c, idx_c, smask_c, seed)
            return jax.tree.map(lambda a, l: a + lam_c * l.astype(jnp.float32),
                                acc, local), None

        acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        acc, _ = jax.lax.scan(add_client, acc0, (data_n, bs_n, idx_i,
                                                 smask_n, seeds_i, lam))
        return jax.tree.map(lambda a, p: a.astype(p.dtype), acc, params)

    fedavg_clients = (sequential_clients if layout == "sequential"
                      else vmapped_clients)

    def train_cluster(params0, data_n, sizes_n, bs_n, idx_n, smask_n,
                      seeds_n):
        """fel_iterations × (clients → masked FedAvg), in-graph."""

        def fel_iter(params, xs):
            idx_i, seeds_i = xs
            avg = fedavg_clients(params, data_n, sizes_n, bs_n, idx_i,
                                 smask_n, seeds_i)
            # a dataless cluster keeps the incoming global model; its
            # consensus weight (|DS_m| = 0) already zeroes it in Eq. 1
            tot = jnp.sum(sizes_n)
            params = jax.tree.map(lambda a, p: jnp.where(tot > 0, a, p),
                                  avg, params)
            return params, None

        final, _ = jax.lax.scan(fel_iter, params0, (idx_n, seeds_n),
                                unroll=unroll_iters)
        return flatten_pytree(final)

    def round_fn(global_flat, idx, seeds, data, sizes_f, bs_dev, stepmask):
        _TRACE_COUNT[0] += 1    # runs at trace time only: ≈ compile count
        # train in float32: the reference loop's SGD update promotes
        # low-precision (bf16) params to f32 after the first step
        # anyway, and a lax.scan carry needs one stable dtype
        def start(global_flat):
            return jax.tree.map(lambda l: l.astype(jnp.float32),
                                unflatten_pytree_device(global_flat,
                                                        template))

        # (I, N, ...) -> (N, I, ...): the cluster map is outermost,
        # the fel_iterations scan runs inside it
        idx_n = jnp.swapaxes(idx, 0, 1)
        seeds_n = jnp.swapaxes(seeds, 0, 1)
        per_cluster = (data, sizes_f, bs_dev, idx_n, stepmask, seeds_n)
        if layout == "sequential":
            # each cluster unflattens gw(k-1) itself, so no float32 copy
            # of the global model lives beside the running cluster's
            return jax.lax.map(
                lambda xs: train_cluster(start(global_flat), *xs),
                per_cluster)
        return jax.vmap(train_cluster,
                        in_axes=(None, 0, 0, 0, 0, 0, 0))(
            start(global_flat), *per_cluster)

    return round_fn


def engine_for(adapter: Any, clusters: List[FELCluster], fel_iterations: int,
               template_params: Any,
               bucket: bool = False) -> Optional[BatchedFELEngine]:
    """Build a :class:`BatchedFELEngine` if ``adapter`` exposes a
    ``batched_train_spec()``; None when the adapter has no batched path."""
    spec_fn = getattr(adapter, "batched_train_spec", None)
    if spec_fn is None:
        return None
    spec = spec_fn()
    if spec is None:
        return None
    return BatchedFELEngine(clusters, spec, fel_iterations, template_params,
                            bucket=bucket)
