"""Batched in-graph FEL engine ↔ per-client reference loop parity.

The batched engine (``repro.fl.batched_fel``) must be a pure perf
transformation of the reference loop: same seeds → (all-but-)identical
parameters every round and the identical leader sequence, including
ragged/empty client shards and the plagiarist attack path.
"""

import numpy as np
import pytest

from repro.core.serialization import flatten_pytree
from repro.data.synthetic import make_mnist_like
from repro.fl.client import Client
from repro.fl.hfl_runtime import BHFLConfig, BHFLRuntime
from repro.fl.hierarchy import FELCluster, build_hierarchy
from repro.models.mlp import MLPConfig


def _global_flat(rt: BHFLRuntime) -> np.ndarray:
    if rt._global_flat is not None:
        return np.asarray(rt._global_flat)
    return np.asarray(flatten_pytree(rt.global_params))


def _run_both(make_runtime, rounds=3, **kw):
    ref = make_runtime("reference", **kw)
    bat = make_runtime("batched", **kw)
    assert ref.engine == "reference" and bat.engine == "batched"
    out = []
    for _ in range(rounds):
        m_ref = ref.run_round()
        m_bat = bat.run_round()
        out.append((m_ref, m_bat, _global_flat(ref), _global_flat(bat)))
    return out


# ---------------------------------------------------------------------------
# uniform IID shards (the bench configuration, scaled down)
# ---------------------------------------------------------------------------

def test_parity_uniform_iid():
    train, test = make_mnist_like(n_train=720, n_test=60)

    def make(engine):
        cfg = BHFLConfig(n_nodes=3, clients_per_node=2, fel_iterations=2,
                         engine=engine)
        return BHFLRuntime(build_hierarchy(train, 3, 2, "iid"), cfg, test)

    for r, (m_ref, m_bat, g_ref, g_bat) in enumerate(_run_both(make, rounds=3)):
        assert m_ref.leader_id == m_bat.leader_id, f"leader diverged @ round {r}"
        # uniform shards reduce in the identical order → bit-equal params
        np.testing.assert_allclose(g_ref, g_bat, rtol=1e-6, atol=1e-7)
        assert m_ref.test_accuracy == pytest.approx(m_bat.test_accuracy,
                                                    abs=1e-6)
        np.testing.assert_allclose(np.asarray(m_ref.consensus.similarities),
                                   np.asarray(m_bat.consensus.similarities),
                                   rtol=1e-6, atol=1e-7)


def test_parity_multi_epoch_and_multi_batch():
    """Several SGD steps per iteration (epochs × batches) keep the PRNG
    split sequence and lr-decay step counter aligned."""
    train, _ = make_mnist_like(n_train=600, n_test=10)

    def make(engine):
        cfg = BHFLConfig(n_nodes=2, clients_per_node=2, fel_iterations=2,
                         local_epochs=2, batch_size=32, engine=engine)
        return BHFLRuntime(build_hierarchy(train, 2, 2, "iid"), cfg, None)

    for r, (m_ref, m_bat, g_ref, g_bat) in enumerate(_run_both(make, rounds=3)):
        assert m_ref.leader_id == m_bat.leader_id
        np.testing.assert_allclose(g_ref, g_bat, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# ragged / empty shards
# ---------------------------------------------------------------------------

def _ragged_clusters(train, sizes):
    clusters, cid, off = [], 0, 0
    for nid, row in enumerate(sizes):
        clients = []
        for s in row:
            idx = np.arange(off, off + s)
            off += s
            clients.append(Client(cid, train.subset(idx)))
            cid += 1
        clusters.append(FELCluster(nid, clients))
    return clusters


def test_parity_ragged_and_empty_shards():
    """Ragged client sizes, an empty client, and a fully dataless cluster:
    the masked batched reduction must agree with the skip-empty reference
    semantics (the dataless cluster keeps the incoming global model)."""
    train, _ = make_mnist_like(n_train=400, n_test=10)
    sizes = [[70, 37, 0], [12, 90, 3], [0, 0, 0]]

    def make(engine):
        cfg = BHFLConfig(n_nodes=3, clients_per_node=3, fel_iterations=2,
                         engine=engine)
        return BHFLRuntime(_ragged_clusters(train, sizes), cfg, None)

    for r, (m_ref, m_bat, g_ref, g_bat) in enumerate(_run_both(make, rounds=3)):
        assert m_ref.leader_id == m_bat.leader_id
        # padded masked reductions reorder a handful of float adds
        np.testing.assert_allclose(g_ref, g_bat, rtol=1e-5, atol=1e-6)


def test_dataless_cluster_keeps_global_model():
    train, _ = make_mnist_like(n_train=200, n_test=10)
    sizes = [[50, 50], [0, 0]]

    def make(engine):
        cfg = BHFLConfig(n_nodes=2, clients_per_node=2, fel_iterations=2,
                         engine=engine)
        return BHFLRuntime(_ragged_clusters(train, sizes), cfg, None)

    bat = make("batched")
    start = np.asarray(bat._global_flat)
    W = bat._engine.run_round(bat._global_flat, round_seed=1)
    np.testing.assert_array_equal(np.asarray(W[1]), start)
    assert not np.array_equal(np.asarray(W[0]), start)


# ---------------------------------------------------------------------------
# plagiarist attack path
# ---------------------------------------------------------------------------

def test_parity_plagiarist_path():
    train, _ = make_mnist_like(n_train=600, n_test=10)

    def make(engine):
        cfg = BHFLConfig(n_nodes=3, clients_per_node=2, fel_iterations=1,
                         engine=engine)
        rt = BHFLRuntime(build_hierarchy(train, 3, 2, "iid"), cfg, None)
        rt.plagiarists = {1}
        return rt

    for r, (m_ref, m_bat, g_ref, g_bat) in enumerate(_run_both(make, rounds=3)):
        assert m_ref.leader_id == m_bat.leader_id
        np.testing.assert_allclose(g_ref, g_bat, rtol=1e-6, atol=1e-7)
        # HCDS flags the byte-identical copy identically on both paths
        assert m_ref.consensus.rejected == m_bat.consensus.rejected
        assert "plagiarized-model" in m_bat.consensus.rejected.values()


# ---------------------------------------------------------------------------
# engine selection / fallback
# ---------------------------------------------------------------------------

class _NoBatchAdapter:
    """Minimal adapter without batched_train_spec (protocol minimum)."""

    name = "no-batch"

    def __init__(self):
        from repro.fl.adapters import MLPAdapter
        self._inner = MLPAdapter(cfg=MLPConfig(hidden=8))

    def init(self, key):
        return self._inner.init(key)

    def local_train(self, params, client, *, seed=0):
        return self._inner.local_train(params, client, seed=seed)

    def evaluate(self, params, dataset):
        return self._inner.evaluate(params, dataset)

    def flatten(self, params):
        return self._inner.flatten(params)

    def unflatten(self, flat, template):
        return self._inner.unflatten(flat, template)


def test_engine_flag_validation_and_fallback():
    train, _ = make_mnist_like(n_train=200, n_test=10)
    clusters = build_hierarchy(train, 2, 2, "iid")
    cfg = BHFLConfig(n_nodes=2, clients_per_node=2, engine="nope")
    with pytest.raises(ValueError, match="unknown engine"):
        BHFLRuntime(clusters, cfg, None)

    cfg = BHFLConfig(n_nodes=2, clients_per_node=2,
                     mlp=MLPConfig(hidden=8), engine="batched")
    with pytest.raises(ValueError, match="batched_train_spec"):
        BHFLRuntime(build_hierarchy(train, 2, 2, "iid"), cfg, None,
                    adapter=_NoBatchAdapter())

    cfg = BHFLConfig(n_nodes=2, clients_per_node=2,
                     mlp=MLPConfig(hidden=8), engine="auto")
    rt = BHFLRuntime(build_hierarchy(train, 2, 2, "iid"), cfg, None,
                     adapter=_NoBatchAdapter())
    assert rt.engine == "reference"
    rt.run_round()     # fallback path still completes a round

    cfg = BHFLConfig(n_nodes=2, clients_per_node=2,
                     mlp=MLPConfig(hidden=8), engine="auto")
    rt = BHFLRuntime(build_hierarchy(train, 2, 2, "iid"), cfg, None)
    assert rt.engine == "batched"


def test_lm_adapter_batched_engine_runs():
    """LM adapters opt in to the batched engine; bf16 params mean the two
    engines only track loosely (the reference loop promotes to f32 after
    step 1, the engine trains in f32 throughout), so this is a smoke +
    shape test, not a strict parity pin."""
    from repro.data.tokens import make_token_dataset
    from repro.fl.adapters import transformer_adapter

    train, test = make_token_dataset(n_seqs=64, seq_len=8, vocab_size=32)
    cfg = BHFLConfig(n_nodes=2, clients_per_node=2, fel_iterations=1,
                     engine="batched")
    ad = transformer_adapter(vocab_size=32, d_model=16, n_layers=1)
    rt = BHFLRuntime(build_hierarchy(train, 2, 2, "iid"), cfg, test,
                     adapter=ad)
    m = rt.run_round()
    assert np.isfinite(m.test_loss)
    assert rt._global_flat.shape[0] == flatten_pytree(rt.global_params).shape[0]


# ---------------------------------------------------------------------------
# shape bucketing / compile-cache reuse
# ---------------------------------------------------------------------------

def test_shape_bucketing_reuses_compiled_round():
    """Runtimes rebuilt at nearby scales (3 vs 4 clients, 96 vs 128 samples
    per shard — same pow2 buckets) must reuse one compiled round program;
    a scale in a different bucket must trace fresh. Bucketed padding is
    masked, so the padded program's output is bit-identical to the exact
    one."""
    from repro.fl import batched_fel
    from repro.fl.adapters import MLPAdapter
    from repro.models.mlp import MLPConfig

    adapter = MLPAdapter(cfg=MLPConfig(hidden=8))

    def runtime(clients, per_client, bucketing=True):
        train, _ = make_mnist_like(n_train=2 * clients * per_client,
                                   n_test=10)
        cfg = BHFLConfig(n_nodes=2, clients_per_node=clients,
                         fel_iterations=1, mlp=MLPConfig(hidden=8),
                         engine="batched", shape_bucketing=bucketing)
        return BHFLRuntime(build_hierarchy(train, 2, clients, "iid"), cfg,
                           None, adapter=adapter)

    rt1 = runtime(3, 96)
    rt1.run_round()
    count = batched_fel.compile_count()
    assert rt1._engine.n_clients_padded == 4
    assert rt1._engine.n_max == 128

    rt2 = runtime(4, 128)               # same buckets: (4 clients, 128, ...)
    rt2.run_round()
    assert batched_fel.compile_count() == count     # cache hit, no re-trace
    assert rt2._engine._round_fn is rt1._engine._round_fn

    rt3 = runtime(5, 96)                # 5 clients -> pad 8: a new bucket
    rt3.run_round()
    assert batched_fel.compile_count() == count + 1

    # bucketed padding is bit-exact against the unbucketed program
    # (same starting global model through both engines)
    exact = runtime(3, 96, bucketing=False)
    start = exact._global_flat
    W_exact = np.asarray(exact._engine.run_round(start, 1))
    W_bucket = np.asarray(rt1._engine.run_round(start, 1))
    np.testing.assert_array_equal(W_exact, W_bucket)


def test_api_engine_kwarg():
    from repro import api
    run = api.run_bhfl(model="mlp", n_nodes=2, clients_per_node=2,
                       fel_iterations=1, rounds=2, engine="batched")
    assert run.runtime.engine == "batched"
    assert run.chain_valid and run.chain_height == 2


# ---------------------------------------------------------------------------
# layout: every client in flight (vmap) or one at a time (sequential)
# ---------------------------------------------------------------------------

def _ragged_hierarchy():
    train, test = make_mnist_like(n_train=600, n_test=40)
    rng = np.random.default_rng(5)
    order = rng.permutation(len(train))
    cuts = [[70, 130, 0], [96, 64, 50]]     # ragged, one empty shard
    clusters, off = [], 0
    for n, sizes in enumerate(cuts):
        clients = []
        for j, s in enumerate(sizes):
            clients.append(Client(n * 3 + j, train.subset(order[off:off + s])))
            off += s
        clusters.append(FELCluster(n, clients))
    return clusters, test


@pytest.mark.parametrize("hierarchy", ["uniform", "ragged"])
def test_sequential_layout_reproduces_vmapped(monkeypatch, hierarchy):
    """With a device budget too small for every client's training state,
    the engine runs clusters under lax.map and clients under lax.scan;
    W(k) and gw(k) match the vmapped program up to FedAvg's reduction
    order, for the same seeds, batch plan and masks."""
    from repro.fl import batched_fel
    if hierarchy == "uniform":
        train, test = make_mnist_like(n_train=720, n_test=60)
        clusters = lambda: build_hierarchy(train, 3, 2, "iid")
    else:
        clusters = lambda: _ragged_hierarchy()[0]
        test = _ragged_hierarchy()[1]
    n = len(clusters())
    cfg = BHFLConfig(n_nodes=n, clients_per_node=2, fel_iterations=2,
                     engine="batched")
    vm = BHFLRuntime(clusters(), cfg, test)
    monkeypatch.setattr(batched_fel, "device_bytes_limit", lambda: 1 << 20)
    sq = BHFLRuntime(clusters(), cfg, test)
    assert (vm._engine.layout, sq._engine.layout) == ("vmap", "sequential")
    assert sq._engine.clients_in_flight == 1
    assert vm._engine.clients_in_flight == vm._engine.n_clusters * \
        vm._engine.n_clients_padded
    from repro import obs
    rec = obs.TraceRecorder("t")
    for _ in range(2):
        W_v = np.asarray(vm._engine.run_round(vm._global_flat, 7))
        with obs.use_recorder(rec):
            W_s = np.asarray(sq._engine.run_round(sq._global_flat, 7))
        np.testing.assert_allclose(W_s, W_v, rtol=2e-6, atol=2e-7)
        m_v, m_s = vm.run_round(), sq.run_round()
        assert m_v.leader_id == m_s.leader_id
        np.testing.assert_allclose(_global_flat(sq), _global_flat(vm),
                                   rtol=2e-6, atol=2e-7)
    assert rec.metrics_snapshot()["counters"]["fel.sequential_dispatches"] == 2
    assert {(s.attrs["layout"], s.attrs["clients_in_flight"])
            for s in rec.spans if s.name == "fel.dispatch"} == {
        ("sequential", 1)}


def test_paper_mlp_keeps_the_vmapped_program(monkeypatch):
    """The paper's MLP fits a 16 GB chip many times over: the engine keeps
    the vmapped program, and says so on its dispatch span."""
    from repro import obs
    from repro.fl import batched_fel
    train, test = make_mnist_like(n_train=8 * 5 * 40, n_test=20)
    cfg = BHFLConfig(n_nodes=8, clients_per_node=5, fel_iterations=1,
                     engine="batched")
    assert BHFLRuntime(build_hierarchy(train, 8, 5, "iid"), cfg,
                       test)._engine.layout == "vmap"    # no limit reported
    monkeypatch.setattr(batched_fel, "device_bytes_limit",
                        lambda: 16 * 10**9)
    rt = BHFLRuntime(build_hierarchy(train, 8, 5, "iid"), cfg, test)
    rec = obs.TraceRecorder("t")
    with obs.use_recorder(rec):
        rt.run_round()
    dispatch = [s for s in rec.spans if s.name == "fel.dispatch"]
    assert [(s.attrs["layout"], s.attrs["clients_in_flight"])
            for s in dispatch] == [("vmap", 40)]
    counters = rec.metrics_snapshot()["counters"]
    assert counters["fel.dispatches"] == 1
    assert "fel.sequential_dispatches" not in counters


def test_engine_holds_shapes_and_history_keeps_the_newest_gw():
    """The engine keeps the parameters' shapes and dtypes, no values; the
    runtime's history keeps gw(k) for the newest round only, and every
    round's block keeps its digest."""
    import hashlib
    import jax
    train, test = make_mnist_like(n_train=240, n_test=20)
    cfg = BHFLConfig(n_nodes=2, clients_per_node=2, fel_iterations=1,
                     mlp=MLPConfig(hidden=8), engine="batched")
    rt = BHFLRuntime(build_hierarchy(train, 2, 2, "iid"), cfg, test)
    leaves = jax.tree.leaves(rt._engine._template)
    assert leaves and all(isinstance(a, jax.ShapeDtypeStruct) for a in leaves)
    gws = []
    for _ in range(3):
        gws.append(np.asarray(rt.run_round().consensus.global_model,
                              np.float32))
    assert [m.consensus.global_model is None for m in rt.history] == [
        True, True, False]
    np.testing.assert_array_equal(_global_flat(rt), gws[-1])
    blocks = rt.consensus.ledgers[0].blocks
    assert [b.global_model_digest for b in blocks] == [
        hashlib.sha256(gw.tobytes()).hexdigest() for gw in gws]
