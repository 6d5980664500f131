"""RWKV-6 "Finch" (``models/rwkv6``) against the plain reference of the
benchmark's configuration (``bench/configs/rwkv6-1.6b.py``), at tiny
widths on seeded weights.

In float32 activations the program and the reference are the same
equations, so forward, loss, gradients and a whole BHFL round agree to
float32 rounding. At the configuration's own precision (bfloat16
activations) they agree to bfloat16 rounding only: XLA rounds the two
programs' intermediates at different fusion points.
"""

import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.serialization import flatten_pytree
from repro.models import ssm_models

BENCH = Path(__file__).resolve().parents[1] / "bench"
TINY = dict(n_layers=2, d_model=64, head_size=32, d_ff=224, vocab_size=96,
            mix_lora=8, decay_lora=8)


def _config_module():
    spec = importlib.util.spec_from_file_location(
        "rwkv6_1_6b_config_under_test", BENCH / "configs" / "rwkv6-1.6b.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def finch():
    """(config module, tiny config dict)."""
    cfg = json.loads((BENCH / "configs" / "rwkv6-1.6b.json").read_text())
    cfg["model"] = dict(TINY)
    return _config_module(), cfg


@pytest.fixture
def float32(monkeypatch, finch):
    """Both sides in float32 activations."""
    monkeypatch.setattr(ssm_models, "COMPUTE_DTYPE", jnp.float32)
    monkeypatch.setattr(finch[0], "ACT", jnp.float32)
    return finch


def _reference_precision():
    sys.path.insert(0, str(BENCH))
    from reference import PRECISIONS
    return PRECISIONS["reference"]


def _losses(mod, cfg, rows):
    ad = mod.program_adapter(cfg)
    program = ad.batched_train_spec().per_example_loss
    ref = mod.make_per_example_loss(cfg)
    prec = _reference_precision()
    return (lambda p: jnp.mean(program(p, {"rows": rows}, None)),
            lambda p: jnp.mean(ref(p, {"rows": rows}, None, prec)), ad)


def _params(mod, cfg, seed=3):
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        mod.init(cfg, jax.random.key(seed)))


def _rows(vocab, n=2, length=41, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab,
                                                            (n, length)),
                       jnp.int32)


def _leaf_gaps(a, b):
    return {jax.tree_util.keystr(k): float(np.linalg.norm(
        np.asarray(x, np.float64) - np.asarray(y, np.float64))
        / max(np.linalg.norm(np.asarray(y, np.float64)), 1e-30))
        for (k, x), y in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                             jax.tree.leaves(b))}


def test_program_has_the_reference_structure(finch):
    mod, cfg = finch
    ad = mod.program_adapter(cfg)
    mine = jax.tree.map(lambda a: (a.shape, a.dtype),
                        mod.init(cfg, jax.random.key(0)))
    theirs = jax.tree.map(lambda a: (a.shape, a.dtype),
                          ad.init(jax.random.key(0)))
    assert mine == theirs
    assert theirs["embed"][1] == jnp.bfloat16
    assert theirs["layers"]["mix_b"][0] == (2, 5, 8, 64)


def test_forward_loss_and_gradients_match_plain_reference(float32):
    mod, cfg = float32
    params, rows = _params(mod, cfg), _rows(TINY["vocab_size"])
    program, ref, ad = _losses(mod, cfg, rows)
    batch = {"tokens": rows[:, :-1]}
    logits, _ = ad.model.forward(params, batch)
    ref_logits = mod._logits(params, rows[:, :-1], cfg, _reference_precision())
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=1e-5, atol=1e-5)
    (l_p, g_p), (l_r, g_r) = (jax.value_and_grad(program)(params),
                              jax.value_and_grad(ref)(params))
    assert float(l_p) == pytest.approx(float(l_r), rel=1e-6)
    gaps = _leaf_gaps(g_p, g_r)
    assert max(gaps.values()) < 1e-5, gaps


def test_bf16_gradients_agree_to_bf16_rounding(finch):
    """At the configuration's precision the two differ by where bfloat16
    rounding falls (measured up to ~2% of a leaf's gradient norm)."""
    mod, cfg = finch
    params, rows = _params(mod, cfg), _rows(TINY["vocab_size"], seed=1)
    program, ref, _ = _losses(mod, cfg, rows)
    (l_p, g_p), (l_r, g_r) = (jax.value_and_grad(program)(params),
                              jax.value_and_grad(ref)(params))
    assert float(l_p) == pytest.approx(float(l_r), rel=1e-3)
    gaps = _leaf_gaps(g_p, g_r)
    assert max(gaps.values()) < 0.05, gaps


def test_decode_steps_through_the_state_match_the_forward(float32):
    """Token by token through the recurrent cache (the serving path) gives
    the full forward's logits: the token shifts and WKV states carry."""
    mod, cfg = float32
    ad = mod.program_adapter(cfg)
    params = _params(mod, cfg, seed=5)
    rows = _rows(TINY["vocab_size"], n=2, length=12, seed=2)
    full, _ = ad.model.forward(params, {"tokens": rows})
    cache = ad.model.init_cache(2, 12)
    steps = []
    for t in range(12):
        logits, cache = ad.model.decode_step(params, cache, rows[:, t:t + 1],
                                             jnp.asarray(t, jnp.int32))
        steps.append(logits[:, 0])
    np.testing.assert_allclose(np.stack(steps, 1), np.asarray(full),
                               rtol=1e-4, atol=1e-4)


def test_one_bhfl_round_matches_the_reference(float32):
    """W(k) rows and gw(k) of one BHFL round through the batched engine
    against ``bench/reference.py`` (float32 activations on both sides)."""
    mod, cfg = float32
    sys.path.insert(0, str(BENCH))
    import harness
    from reference import model_evaluation
    w = json.loads((BENCH / "workloads" / "rwkv6-fel-n4.json").read_text())
    w["traffic"].update(seq_len=16, vocab=TINY["vocab_size"], n_train=16,
                        n_test=4)
    w["deployment"].update(n_nodes=2, clients_per_node=2, fel_iterations=2)
    cell = harness.Cell("rwkv6-fel-n4", workload=w, config=cfg, module=mod)
    seeds = harness.Seeds.of(2026)
    servers, test = cell.data(seeds)
    params = cell.weights(seeds)
    rt = cell.runtime(seeds, servers, test, params)
    assert rt.engine == "batched" and rt._engine.layout == "vmap"
    rows = []
    rt.consensus.add_phase_hook(
        "commit_reveal", lambda _, ctx: rows.extend(ctx.models), when="before")
    m = rt.run_round()
    ref = harness.make_reference(cell, seeds, servers, test)
    start = mod.round_start(params)
    want = [cell.layout.flatten(ref._fel(start, seeds.program + 1, clients,
                                         sizes))
            for clients, sizes in zip(ref.servers, ref.sizes)]
    for got, exp in zip(rows, want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   rtol=1e-4, atol=2e-6)
    gw, sims = model_evaluation(want, np.asarray([sum(s) for s in ref.sizes],
                                                 np.float64))
    np.testing.assert_allclose(np.asarray(m.consensus.global_model),
                               np.asarray(gw), rtol=1e-4, atol=2e-6)
    assert np.argmax(m.consensus.similarities) == np.argmax(sims)


def test_finch_preset_through_make_adapter_and_run_bhfl():
    from repro import api
    from repro.data.tokens import make_token_dataset
    full = api.make_adapter("rwkv6-1.6b", n_layers=4, vocab_size=8192)
    a = full.arch
    assert (a.d_model, a.n_layers, a.d_ff, a.vocab_size, a.rwkv_head_size,
            a.rwkv_mix_lora, a.rwkv_decay_lora) == (2048, 4, 7168, 8192, 64,
                                                    32, 64)
    assert full.model.n_params() == 255_467_520
    tiny = api.make_adapter("rwkv6-1.6b", d_model=64, n_heads=2,
                            n_kv_heads=2, d_ff=224, n_layers=1,
                            rwkv_head_size=32, rwkv_mix_lora=8,
                            rwkv_decay_lora=8, vocab_size=32, batch_size=4)
    data = make_token_dataset(n_seqs=32, seq_len=8, vocab_size=32)
    run = api.run_bhfl(model=tiny, data=data, rounds=1, n_nodes=2,
                       clients_per_node=2, fel_iterations=1,
                       engine="batched")
    assert run.runtime.engine == "batched"
    assert run.chain_valid and np.isfinite(run.history[-1].test_loss)


def test_lm_evaluate_compiles_once_per_shape(finch):
    """A second round's evaluate reuses the compiled program, and the
    test pass carries the same spans as the MLP's."""
    from repro import obs
    from repro.data.tokens import TokenDataset
    mod, cfg = finch
    cfg["model"]["vocab_size"] = 80          # a shape no other test compiles
    ad = mod.program_adapter(cfg)
    params = ad.init(jax.random.key(0))
    test = TokenDataset(np.asarray(_rows(80, n=3, length=9)), 80)
    compiles = []

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        ad.evaluate(params, test)
        first = len(compiles)
        rec = obs.TraceRecorder("t")
        with obs.use_recorder(rec):
            acc, loss = ad.evaluate(params, test)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert first >= 1 and len(compiles) == first
    assert 0.0 <= acc <= 1.0 and np.isfinite(loss)
    names = [(s.name, s.attrs.get("on")) for s in rec.spans]
    assert ("device.put", "test_set") in names
    assert ("device.wait", "eval") in names
    put = next(s for s in rec.spans if s.name == "device.put")
    assert put.attrs["h2d_bytes"] == test.tokens.nbytes


def test_flat_size_is_the_parameter_count(finch):
    mod, cfg = finch
    params = mod.init(cfg, jax.random.key(0))
    assert flatten_pytree(params).shape == (
        sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)),)
