"""One run of one cell: set-up, the measured window, the traced window and
the check against the plain reference.

Everything that belongs to one configuration, cell or metric lives in a
file of its own, found by name:

* ``bench/configs/<config>.json`` (sizes, optimiser, precision, cuts) and
  ``bench/configs/<config>.py`` (initial weights, plain reference model,
  the program's adapter and data set types, operation counts);
* ``bench/workloads/<cell>.json`` (traffic, deployment, consensus
  constants, checked rounds, limits of the check);
* ``bench/traffic/<kind>.py`` (a generator) and ``bench/partition/<kind>.py``
  (a split over clients), named by a workload's traffic and deployment;
* ``bench/e2e/<metric>.py`` and ``bench/metrics/<metric>.py``, each a
  ``read(ctx)`` that returns a number or ``None``.

``BENCHMARK.json`` gives each cell's configuration and chips, and says
which metrics each cell reports.
"""

from __future__ import annotations

import gc
import hashlib
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from functools import partial
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
import numpy as np

import compare
from layout import Layout
from reference import ReferenceBHFL, btsv_leaders

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    name = "bench_" + "".join(c if c.isalnum() else "_"
                              for c in str(path.relative_to(BENCH)))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class CompileClock:
    """Compilations and the seconds JAX spends tracing, lowering and
    compiling, as reported by its own monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration
            self.compiles += event == self.EVENTS[2]
            self.traces += event == self.EVENTS[0]

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


@dataclass(frozen=True)
class Seeds:
    """What one ``--seed`` fixes: the data and split (``data``), the
    initial weights (``key``) and the program's round seeds (``program``,
    small enough that its per-client seeds fit in int32)."""
    data: int
    key: int
    program: int

    @classmethod
    def of(cls, seed: int) -> "Seeds":
        a, b = np.random.SeedSequence(seed).generate_state(2)
        return cls(int(seed), int(a % 2**31), int(b % 1_000_000))


def device_info(chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found {info['platform']!r} "
                         f"({info['kind']}); this benchmark runs only on a TPU")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return info


def peak_memory() -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


WORKLOAD_KEYS = {"traffic", "deployment", "consensus", "check_rounds",
                 "trace_rounds", "limits", "test_size"}
DEPLOYMENT_KEYS = {"n_nodes", "clients_per_node", "fel_iterations",
                   "partition"}


def refuse_unknown(what: str, got: dict, known: set) -> None:
    """A key the harness does not act on would be ignored in silence and
    the cell measured as something else; refuse it instead."""
    extra = sorted(set(got) - known)
    if extra:
        raise SystemExit(f"{what} has keys the harness does not act on: "
                         f"{extra} (it knows {sorted(known)})")


def found(folder: str, name: str) -> Path:
    path = BENCH / folder / f"{name}.py"
    if not path.exists():
        raise SystemExit(f"no bench/{folder}/{name}.py for {name!r}")
    return path


class Cell:
    """A cell's files, its configuration module and the program's adapter,
    built once per process and reused by every seed run in it. Its
    configuration and chips are its entry in ``BENCHMARK.json``; the
    traffic's generator is ``bench/traffic/<kind>.py`` and its split over
    the clients ``bench/partition/<kind>.py``, each found by name."""

    def __init__(self, name: str, workload: Optional[dict] = None,
                 config: Optional[dict] = None, module: Any = None):
        self.name = name
        entry = [w for w in load_json(ROOT / "BENCHMARK.json")["workloads"]
                 if w["name"] == name]
        if not entry:
            raise SystemExit(f"{name!r} is not a workload of BENCHMARK.json")
        self.chips = entry[0]["chips"]
        self.w = workload or load_json(BENCH / "workloads" / f"{name}.json")
        refuse_unknown(f"bench/workloads/{name}.json", self.w, WORKLOAD_KEYS)
        refuse_unknown(f"{name}'s deployment", self.w["deployment"],
                       DEPLOYMENT_KEYS)
        cname = entry[0]["config"]
        self.cfg = config or load_json(BENCH / "configs" / f"{cname}.json")
        self.mod = module or load_module(BENCH / "configs" / f"{cname}.py")
        self.generator = load_module(found("traffic",
                                           self.w["traffic"]["kind"]))
        refuse_unknown(f"{name}'s traffic", self.w["traffic"],
                       self.generator.KEYS)
        part = dict(self.deployment["partition"])
        self.partition = load_module(found("partition", part.pop("kind")))
        self.partition_params = part
        self.adapter = self.mod.program_adapter(self.cfg)
        self.loss = self.mod.make_per_example_loss(self.cfg)
        self.evaluate = jax.jit(self.mod.make_evaluate(self.cfg),
                                static_argnums=2)
        self.init = jax.jit(partial(self.mod.init, self.cfg))
        self.layout: Optional[Layout] = None

    @property
    def deployment(self) -> dict:
        return self.w["deployment"]

    def weights(self, seeds: Seeds) -> Any:
        params = self.init(jax.random.key(seeds.key))
        if self.layout is None:
            self.layout = Layout(params)
        return params

    def data(self, seeds: Seeds):
        """(servers, test): per edge server its clients as (client_id,
        columns), and the test columns."""
        dep = self.deployment
        train, test = self.generator.make(self.w["traffic"], seeds.data)
        c = dep["clients_per_node"]
        shards = self.partition.split(train, dep["n_nodes"] * c,
                                      self.partition_params, seeds.data)
        servers = [[(n * c + j, {k: v[shards[n * c + j]]
                                 for k, v in train.items()})
                    for j in range(c)] for n in range(dep["n_nodes"])]
        return servers, test

    def runtime(self, seeds: Seeds, servers, test, params):
        from repro import api
        from repro.fl.client import Client
        from repro.fl.hierarchy import FELCluster
        dep, cons = self.deployment, self.w["consensus"]
        clusters = [FELCluster(n, [Client(cid, self.mod.program_dataset(
            self.cfg, cols)) for cid, cols in clients])
            for n, clients in enumerate(servers)]
        cfg = api.BHFLConfig(
            n_nodes=dep["n_nodes"], clients_per_node=dep["clients_per_node"],
            fel_iterations=dep["fel_iterations"], seed=seeds.program,
            engine="batched", g_max=cons["g_max"],
            btsv=api.BTSVConfig(**cons["btsv"]))
        rt = api.BHFLRuntime(clusters, cfg,
                             self.mod.program_dataset(self.cfg, test),
                             adapter=self.adapter)
        if rt.engine != "batched":
            raise RuntimeError(f"FEL engine is {rt.engine!r}, not 'batched'")
        mine = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        theirs = jax.tree.map(lambda a: (a.shape, a.dtype), rt.global_params)
        if mine != theirs:
            raise RuntimeError("the configuration's weights do not have the "
                               "program's structure, shapes and dtypes")
        rt.global_params = params
        return rt

    def flops_per_round(self, servers, test) -> dict:
        """Operations and bytes of one round, from the shapes: each client
        trains on whole batches only (drop-remainder)."""
        dep, bs = self.deployment, self.cfg["batch_size"]
        trained = 0
        for clients in servers:
            for _, cols in clients:
                n = len(next(iter(cols.values())))
                b = min(bs, n)
                trained += (n // b) * b * self.mod.row_units(cols)
        trained *= dep["fel_iterations"] * self.cfg["local_epochs"]
        n_test = len(next(iter(test.values())))
        f = self.mod.flops(self.cfg, trained,
                           n_test * self.mod.row_units(test))
        n, d = dep["n_nodes"], self.layout.size
        f["me"] = 4.0 * n * d
        f["me_bytes"] = 4.0 * n * d
        return f


class ProgramProbe:
    """Reads what the timed path produced in the checked rounds: the W(k)
    handed to the consensus, the similarities, the votes cast and the
    evaluated test loss."""

    def __init__(self, rt, layout: Layout, start_flat):
        self.layout, self.start = layout, start_flat
        self.prev = start_flat
        self.active = True
        self.traj = compare.Trajectory()
        self._rows = None
        rt.consensus.add_phase_hook("commit_reveal", self._grab_rows,
                                    when="before")

    def _grab_rows(self, phase: str, ctx) -> None:
        if self.active:
            self._rows = list(ctx.models)

    def after_round(self, m) -> None:
        ups = np.stack([np.asarray(self.layout.change_norms(r, self.prev))
                        for r in self._rows])
        self._rows = None
        self.traj.updates.append(ups)
        self.traj.add_round(m.consensus.similarities, m.test_loss,
                            votes=m.consensus.votes)
        self.prev = m.consensus.global_model

    def finish(self) -> None:
        self.traj.change = np.asarray(self.layout.change_norms(self.prev,
                                                               self.start))
        self.active = False
        self.prev = self.start = None


def chain_checks(rt, consensus: dict) -> Dict[str, float]:
    """The ledgers after every round the run made: linkage, signatures,
    one head everywhere, one block per round carrying its round's leader,
    the last block's digest of gw(k), and each leader against a BTSV
    tally of the votes the block records."""
    ledgers = rt.consensus.ledgers
    blocks = ledgers[0].blocks
    faults = 0
    faults += sum(not led.verify_chain() for led in ledgers)
    faults += not ledgers[0].verify_chain(rt.consensus.public_keys)
    faults += len({(led.height, led.head_hash) for led in ledgers}) != 1
    faults += len(blocks) != len(rt.history)
    faults += sum(b.round != m.round or b.leader_id != m.leader_id
                  for b, m in zip(blocks, rt.history))
    gw = np.asarray(rt.history[-1].consensus.global_model, np.float32)
    faults += (hashlib.sha256(gw.tobytes()).hexdigest()
               != blocks[-1].global_model_digest)
    n = rt.cfg.n_nodes
    votes = [[b.votes[i] for i in range(n)] for b in blocks]
    leaders = btsv_leaders(votes, consensus["g_max"], consensus["btsv"])
    mismatch = sum(l != b.leader_id for l, b in zip(leaders, blocks))
    return {"leader_mismatch": float(mismatch), "block_faults": float(faults)}


@dataclass
class Window:
    walls: List[float] = field(default_factory=list)
    seconds: float = 0.0
    compiles: int = 0
    traces: int = 0


def run_window(rt, seconds: float, annotate: bool = False,
               max_rounds: Optional[int] = None) -> Window:
    """Rounds back to back until ``seconds`` have passed; a round ends
    when gw(k) is on the device and its block on every ledger."""
    clock = CompileClock()
    win = Window()
    t_win = time.perf_counter()
    try:
        while True:
            t0 = time.perf_counter()
            if annotate:
                with jax.profiler.TraceAnnotation("bench.round"):
                    m = rt.run_round()
                    jax.block_until_ready(m.consensus.global_model)
            else:
                m = rt.run_round()
                jax.block_until_ready(m.consensus.global_model)
            t1 = time.perf_counter()
            win.walls.append(t1 - t0)
            if t1 - t_win >= seconds or (max_rounds is not None
                                         and len(win.walls) >= max_rounds):
                break
    finally:
        clock.close()
    win.seconds = t1 - t_win
    win.compiles, win.traces = clock.compiles, clock.traces
    return win


class PhaseAnnotations:
    """Profiler annotations around each consensus phase and ``evaluate``,
    so that the trace can say what the host was doing in an idle gap."""

    def __init__(self, rt, adapter):
        self._open: List[Any] = []
        rt.consensus.add_phase_hook("*", self._before, when="before")
        rt.consensus.add_phase_hook("*", self._after, when="after")
        evaluate = adapter.evaluate

        def annotated(params, dataset):
            with jax.profiler.TraceAnnotation("bench.evaluate"):
                return evaluate(params, dataset)
        adapter.evaluate = annotated

    def _before(self, phase: str, ctx) -> None:
        ann = jax.profiler.TraceAnnotation(f"bench.phase.{phase}")
        ann.__enter__()
        self._open.append(ann)

    def _after(self, phase: str, ctx) -> None:
        self._open.pop().__exit__(None, None, None)


def benchmark_metrics(cell: str, kind: str) -> List[dict]:
    """The cell's entries of ``BENCHMARK.json``'s ``end_to_end`` or
    ``per_layer`` list."""
    spec = load_json(ROOT / "BENCHMARK.json")
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    e2e_here = {n for n, m in e2e.items()
                if cell in m.get("workloads", [cell])}
    if kind == "end_to_end":
        return [e2e[n] for n in e2e if n in e2e_here]
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in e2e_here
                             else [])]


def read_metrics(entries: List[dict], folder: str, ctx) -> Dict[str, dict]:
    out = {}
    for m in entries:
        reader = load_module(BENCH / folder / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


@dataclass
class TraceContext:
    """What a per-layer reader sees of one traced window."""
    trace: Any                  # trace.Reduced
    spans: List[Any]            # repro.obs span records of the window
    rounds: int
    window: Window
    flops: dict                 # operations and bytes of one round
    peaks: dict                 # the chip's row of peaks.json
    cell: "Cell"


@dataclass
class E2EContext:
    window: Window
    setup_s: float


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table["devices"]:
        raise SystemExit(f"device kind {kind!r} has no row in "
                         f"bench/peaks.json; add its published peaks")
    return table["devices"][kind]


def traced_window(cell: Cell, rt, seconds: float, flops: dict,
                  device: dict) -> tuple:
    bench_trace = load_module(BENCH / "trace.py")
    from repro.obs import TraceRecorder, use_recorder
    out = OUT / "trace" / cell.name
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    rec = TraceRecorder("bench")
    jax.profiler.start_trace(str(out))
    try:
        with use_recorder(rec):
            win = run_window(rt, seconds, annotate=True,
                             max_rounds=cell.w["trace_rounds"])
    finally:
        jax.profiler.stop_trace()
    reduced = bench_trace.load(bench_trace.find_xplane(out))
    ctx = TraceContext(reduced, list(rec.spans), len(win.walls), win, flops,
                       peaks_for(device["kind"]) if device["platform"] == "tpu"
                       else {}, cell)
    return ctx, reduced


def check(cell: Cell, probe: ProgramProbe, seeds: Seeds, servers, test,
          chain: Dict[str, float]) -> Dict[str, dict]:
    """Runs the reference over the checked rounds and judges every number
    against the cell's limits."""
    params = cell.weights(seeds)
    ref = make_reference(cell, seeds, servers, test)
    traj = ref.run(params, len(probe.traj.losses), cell.layout)
    values = compare.numbers(probe.traj, traj)
    values.update(chain)
    return compare.judge(values, cell.w["limits"])


def make_reference(cell: Cell, seeds: Seeds, servers, test,
                   prec: Optional[str] = None) -> ReferenceBHFL:
    """The plain reference, at the configuration's own precision unless
    ``prec`` names another (``reference.PRECISIONS``)."""
    return ReferenceBHFL(cell.loss, cell.evaluate, cell.mod.round_start,
                         cell.cfg, servers, test,
                         cell.deployment["fel_iterations"], seeds.program,
                         prec=prec or cell.cfg["precision"]["reference"])


def setup(cell: Cell, seeds: Seeds, annotate: bool):
    """Data, weights and the runtime; then the checked rounds through the
    timed path. Returns (runtime, probe, servers, test)."""
    servers, test = cell.data(seeds)
    params = cell.weights(seeds)
    rt = cell.runtime(seeds, servers, test, params)
    probe = ProgramProbe(rt, cell.layout, cell.layout.flatten(params))
    del params
    if annotate:
        PhaseAnnotations(rt, cell.adapter)
    for _ in range(cell.w["check_rounds"]):
        m = rt.run_round()
        jax.block_until_ready(m.consensus.global_model)
        probe.after_round(m)
    probe.finish()
    return rt, probe, servers, test


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_tpu: bool = True) -> dict:
    """One run; returns the result line's object."""
    from repro import compile_cache
    device = device_info(cell.chips, require_tpu)
    compile_cache.enable()
    seeds = Seeds.of(seed)
    rt, probe, servers, test = setup(cell, seeds, annotate=trace)
    flops = cell.flops_per_round(servers, test)
    setup_s = time.perf_counter() - t_start
    breakdown = None
    if trace:
        ctx, reduced = traced_window(cell, rt, seconds, flops, device)
        win = ctx.window
        metrics = read_metrics(benchmark_metrics(cell.name, "per_layer"),
                               "metrics", ctx)
        device["busy_s"] = reduced.busy_seconds()
        device["window_s"] = reduced.window_seconds()
        breakdown = reduced.breakdown()
    else:
        win = run_window(rt, seconds)
        metrics = read_metrics(benchmark_metrics(cell.name, "end_to_end"),
                               "e2e", E2EContext(win, setup_s))
    print(f"window: {len(win.walls)} rounds in {win.seconds:.6f} s; "
          f"compilations in the window: {win.compiles} "
          f"(traces: {win.traces})", file=sys.stderr)
    device["memory_peak_bytes"] = peak_memory()
    chain = chain_checks(rt, cell.w["consensus"])
    del rt
    gc.collect()
    checks = check(cell, probe, seeds, servers, test, chain)
    result = {
        "correct": all(c["ok"] for c in checks.values()),
        "attempted": len(win.walls), "failed": 0,
        "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}"
              f"{'' if c['ok'] else '  FAILED'}", file=sys.stderr)
    return result
