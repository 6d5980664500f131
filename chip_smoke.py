"""Run one BHFL task end to end on one TPU chip and check what comes out.

    python chip_smoke.py

Phases, in order; any failure raises and the script exits nonzero:

1. device — JAX version and devices; anything but a TPU is refused
   (there is no CPU fallback).
2. bhfl — ``api.run_bhfl`` at the paper's setting (§7.1): the MNIST MLP
   784-128-10 (101,770 parameters), 8 edge servers × 5 clients, 3 FEL
   iterations, MNIST's 60k/10k split synthesized from seed 0, 3 rounds on
   the batched FEL engine. Per round: leader, accuracy, loss, wall time
   (ended by ``block_until_ready``) and the compile time inside it.
3. me_kernel — ``model_evaluation`` with the Pallas kernel against the
   jnp branch at HIGHEST precision, N ∈ {8, 12, 16, 64}, D = 101,770;
   and the default ME routing on the chip must be the kernel.
4. fel_engines — one round of the batched and the reference FEL engine
   from the same start; their gw(k) must agree.

The last line printed is ``{"ok": true, "device": {...}}``. The XLA
compile cache is ``repro.compile_cache``'s. Everything runs in this one
process: the chip belongs to whoever touched JAX first.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402

D_MLP = 101_770                 # 784·128 + 128 + 128·10 + 10
ME_NODES = (8, 12, 16, 64)      # 12: a partial row block in the kernel's grid
# Both ME paths are f32 (the kernel on the VPU, the reference at HIGHEST
# matmul precision) but sum the D = 101,770 products in different orders.
# The rounding of such a sum grows like sqrt(D)·2^-24 ≈ 2e-5 relative;
# cosines lie in [-1, 1], so 1e-4 absolute leaves a factor of five.
ME_SIM_ATOL = 1e-4
# Eq. 1 runs at the default matmul precision: an f32 weighted sum over
# N ≤ 64 rows is within N·2^-24 ≈ 4e-6 of a float64 reference. Operands
# rounded to bf16 (2^-9 ≈ 2e-3) would miss that by orders of magnitude.
ME_GW_RTOL = 1e-5
# The two FEL engines do the same f32 arithmetic in a different
# association (vmapped vs per-client dots, in-graph vs host FedAvg), so
# each SGD step can differ by O(2^-24) relative, compounded over
# 3 iterations × 46 steps of momentum SGD. The disagreement is measured
# against how far the round moved the model, ‖gw − gw0‖: 1e-5 of it is
# far above reassociation noise, and an ordering or weighting fault moves
# gw by O(1) of it.
FEL_REL_TOL = 1e-5


def check_device() -> dict:
    print(f"jax {jax.__version__}")
    devices = jax.devices()
    print(f"devices: {devices}")
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}
    print(f"device_kind: {info['kind']}  device_count: {info['count']}")
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (platform {info['platform']!r})"
                         " — refusing to run on another backend")
    return info


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, as reported by
    its own monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def run_bhfl_phase(data, *, n_nodes: int = 8, clients: int = 5,
                   fel_iterations: int = 3, rounds: int = 3) -> None:
    from repro import api
    from repro.core import crypto

    print(f"[bhfl] crypto backend: {crypto.get_backend()}")
    clock = CompileClock()
    marks = [(time.perf_counter(), 0.0)]

    def on_round(m) -> None:
        jax.block_until_ready(m.consensus.global_model)
        now, compiled = time.perf_counter(), clock.seconds
        wall = now - marks[-1][0]
        print(f"[bhfl] round {m.round}: leader={m.leader_id} "
              f"acc={m.test_accuracy:.4f} loss={m.test_loss:.4f} "
              f"wall={wall:.3f}s compile={compiled - marks[-1][1]:.3f}s")
        marks.append((now, compiled))

    try:
        run = api.run_bhfl(model="mlp", engine="batched", n_nodes=n_nodes,
                           clients_per_node=clients,
                           fel_iterations=fel_iterations, rounds=rounds,
                           data=data, on_round=on_round)
    finally:
        clock.close()
    print(f"[bhfl] total compile: {clock.seconds:.3f}s "
          f"(round 0 wall includes set-up)")
    if run.runtime.engine != "batched":
        raise AssertionError(f"FEL engine is {run.runtime.engine!r}")
    if not run.chain_valid or run.chain_height != rounds:
        raise AssertionError(f"chain valid={run.chain_valid} "
                             f"height={run.chain_height}, want {rounds}")
    losses = [m.test_loss for m in run.history]
    if len(losses) != rounds or not np.all(np.isfinite(losses)):
        raise AssertionError(f"losses {losses}")
    chance = 1.0 / data[1].n_classes
    if not run.history[-1].test_accuracy > chance:
        raise AssertionError(f"accuracy {run.history[-1].test_accuracy} "
                             f"is not above chance {chance}")


def me_kernel_phase(*, nodes=ME_NODES, d: int = D_MLP,
                    interpret: bool = False) -> None:
    from repro.core.model_eval import model_evaluation

    for n in nodes:
        kw, ks = jax.random.split(jax.random.key(n))
        base = jax.random.normal(jax.random.key(0), (d,), jnp.float32)
        # models near one another, each at its own distance: the vote
        # is the least-perturbed row, with clear margins
        scale = jnp.linspace(0.5, 2.0, n)[jnp.argsort(
            jax.random.uniform(ks, (n,)))]
        W = base + scale[:, None] * jax.random.normal(kw, (n, d))
        sizes = jax.random.randint(ks, (n,), 100, 1000).astype(jnp.float32)
        got = model_evaluation(W, sizes, use_kernel=True, interpret=interpret)
        with jax.default_matmul_precision("highest"):
            ref = model_evaluation(W, sizes, use_kernel=False)
        jax.block_until_ready((got, ref))
        sim_err = float(jnp.max(jnp.abs(got.similarities - ref.similarities)))
        W64 = np.asarray(W, np.float64)
        lam = np.asarray(sizes, np.float64) / float(np.sum(sizes))
        gw64 = lam @ W64
        gw_err = (np.linalg.norm(np.asarray(got.global_model) - gw64)
                  / np.linalg.norm(gw64))
        print(f"[me] N={n} D={d}: vote kernel={int(got.vote)} "
              f"ref={int(ref.vote)} max|Δsim|={sim_err:.3e} "
              f"gw rel err vs float64={gw_err:.3e}")
        if int(got.vote) != int(ref.vote):
            raise AssertionError(f"N={n}: votes differ")
        if not sim_err <= ME_SIM_ATOL:
            raise AssertionError(f"N={n}: similarities differ by {sim_err}")
        if not gw_err <= ME_GW_RTOL:
            raise AssertionError(f"N={n}: gw(k) off by {gw_err} relative")
    # the ME phase of a BHFL round calls model_evaluation with defaults:
    # on the chip that must be the compiled kernel
    if not interpret:
        W = jax.ShapeDtypeStruct((nodes[0], d), jnp.float32)
        s = jax.ShapeDtypeStruct((nodes[0],), jnp.float32)
        text = model_evaluation.lower(W, s).compile().as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError("default ME routing does not use the kernel")
        print("[me] default routing on this device: Pallas kernel")


def fel_engines_phase(data, *, n_nodes: int = 8, clients: int = 5,
                      fel_iterations: int = 3) -> None:
    from repro import api
    from repro.core.serialization import flatten_pytree

    train, test = data
    out = {}
    for engine in ("batched", "reference"):
        cfg = api.BHFLConfig(n_nodes=n_nodes, clients_per_node=clients,
                             fel_iterations=fel_iterations, engine=engine)
        rt = api.BHFLRuntime(api.build_hierarchy(train, n_nodes, clients,
                                                 "iid", seed=0), cfg, test)
        gw0 = np.asarray(flatten_pytree(rt.global_params))
        t0 = time.perf_counter()
        m = rt.run_round()
        gw = np.asarray(flatten_pytree(rt.global_params))
        print(f"[fel] {engine}: leader={m.leader_id} "
              f"acc={m.test_accuracy:.4f} wall={time.perf_counter() - t0:.3f}s")
        out[engine] = (gw0, gw)
    (gw0, gw_b), (gw0_r, gw_r) = out["batched"], out["reference"]
    if not np.array_equal(gw0, gw0_r):
        raise AssertionError("the engines started from different models")
    moved = float(np.linalg.norm(gw_r - gw0))
    rel = float(np.linalg.norm(gw_b - gw_r)) / moved
    print(f"[fel] ‖gw_batched − gw_reference‖ / ‖gw_reference − gw0‖ = "
          f"{rel:.3e} (‖gw − gw0‖ = {moved:.4f}; tolerance {FEL_REL_TOL})")
    if not rel <= FEL_REL_TOL:
        raise AssertionError(f"FEL engines disagree: {rel}")


def main() -> None:
    device = check_device()
    print(f"compile cache: {compile_cache.enable()}")
    from repro import api

    t0 = time.perf_counter()
    data = api.make_mnist_like(60000, 10000, seed=0)
    print(f"[data] 60000/10000 synthesized in {time.perf_counter() - t0:.1f}s")
    run_bhfl_phase(data)
    me_kernel_phase()
    fel_engines_phase(data)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
