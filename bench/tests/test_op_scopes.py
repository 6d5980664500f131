"""The reader of device time by JAX name scope (``bench/op_scopes.py``)
on hand-made name stacks and ops, and on a trace recorded here (the CPU
backend's ops stand in for device ops, as in ``bench/trace.py``).

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import op_scopes  # noqa: E402

trace = op_scopes.trace


@pytest.mark.parametrize("path, inside", [
    ("jit(round_fn)/while/body/rwkv.time_mix/wkv6/while/body/mul", True),
    ("jit(round_fn)/transpose(jvp(rwkv.time_mix))/wkv6/dot_general", True),
    ("jit(round_fn)/checkpoint/rematted_computation/transpose(jvp(wkv6))/add",
     True),
    ("jit(_lm_evaluate)/rwkv.time_mix/dot_general", False),
    ("jit(round_fn)/rwkv.channel_mix/wkv6x/add", False),
    ("jit(round_fn)/rwkv.time_mix/mywkv6/add", False),
])
def test_in_scope_reads_components_and_transform_wrappers(path, inside):
    assert op_scopes.in_scope(path, "wkv6") is inside


def test_instruction_of_a_tpu_and_a_cpu_op_event():
    assert op_scopes.instruction(
        "%fusion.73 = (f32[2]{0}) fusion(f32[2]{0} %p), kind=kLoop") == \
        "fusion.73"
    assert op_scopes.instruction("copy.10") == "copy.10"


def test_scope_intervals_union_per_device_averaged():
    ops = [trace.Op(0, 10, "%a = f32[] add()", "jit_f(1)", "/device:TPU:0"),
           trace.Op(5, 15, "%b = f32[] mul()", "jit_f(1)", "/device:TPU:0"),
           trace.Op(40, 45, "%c = f32[] mul()", "jit_f(1)", "/device:TPU:0"),
           trace.Op(0, 30, "%a = f32[] add()", "jit_f(1)", "/device:TPU:1"),
           trace.Op(0, 99, "%a = f32[] add()", "jit_g(2)", "/device:TPU:0")]
    table = {"jit_f(1)": {"a": "jit(f)/wkv6/while/body/add",
                          "b": "jit(f)/transpose(jvp(wkv6))/mul",
                          "c": "jit(f)/rwkv.channel_mix/mul"}}
    by_device = op_scopes.scope_intervals(ops, table, "wkv6")
    assert by_device == {"/device:TPU:0": [(0, 10), (5, 15)],
                         "/device:TPU:1": [(0, 30)]}
    assert op_scopes.scope_ns(by_device) == (15 + 30) // 2
    assert op_scopes.scope_ns(
        op_scopes.scope_intervals(ops, table, "rwkv.channel_mix")) == 5


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A traced window of a jitted scan under ``wkv6`` beside an op under
    ``rwkv.channel_mix``, written where the harness writes a cell's."""
    import jax
    import jax.numpy as jnp
    out = tmp_path_factory.mktemp("bench_out")

    @jax.jit
    def f(x):
        with jax.named_scope("wkv6"):
            s, ys = jax.lax.scan(lambda c, t: (0.9 * c + t, c),
                                 jnp.zeros(x.shape[1:]), x)
        with jax.named_scope("rwkv.channel_mix"):
            z = jnp.tanh(ys).sum()
        return z + s.sum()

    x = jnp.ones((16, 64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(out / "trace" / "cell"))
    with jax.profiler.TraceAnnotation("bench.round"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(out / "trace" / "cell")
    op_scopes.op_names.cache_clear()
    return out, SimpleNamespace(trace=trace.load(path), rounds=1,
                                cell=SimpleNamespace(name="cell"),
                                peaks={"bf16_flops": 1e12,
                                       "hbm_bytes_per_s": 1e9},
                                flops={"wkv_flops": 2.0, "wkv_bytes": 5.0})


def test_recorded_trace_keeps_the_name_stacks(recorded, monkeypatch):
    out, ctx = recorded
    monkeypatch.setattr(op_scopes, "OUT", out)
    table = op_scopes.op_names(
        trace.find_xplane(out / "trace" / "cell"))
    assert any("jit_f" in k for k in table)
    stacks = [s for names in table.values() for s in names.values()]
    assert any(op_scopes.in_scope(s, "wkv6") for s in stacks)
    wkv = op_scopes.per_round_ms(ctx, "wkv6")
    assert wkv is not None and wkv > 0
    assert op_scopes.per_round_ms(ctx, "no.such.scope") is None


def test_metric_readers_on_the_recorded_trace(recorded, monkeypatch):
    out, ctx = recorded
    monkeypatch.setattr(op_scopes, "OUT", out)
    ms = harness.load_module(BENCH / "metrics" / "wkv_device_ms.py").read(ctx)
    assert ms == pytest.approx(op_scopes.per_round_ms(ctx, "wkv6"))
    share = harness.load_module(BENCH / "metrics" / "wkv_roofline.py").read(ctx)
    # least time: max(2 / 1e12, 5 / 1e9) s over the scope's time
    assert share == pytest.approx(100 * 5e-9 / (ms * 1e-3))


def test_readers_are_silent_without_a_trace_or_counts(recorded, monkeypatch):
    out, ctx = recorded
    monkeypatch.setattr(op_scopes, "OUT", out)
    bare = SimpleNamespace(**{**vars(ctx), "flops": {}})
    assert harness.load_module(
        BENCH / "metrics" / "wkv_roofline.py").read(bare) is None
    monkeypatch.setattr(op_scopes, "OUT", Path("/nonexistent"))
    assert op_scopes.per_round_ms(ctx, "wkv6") is None
