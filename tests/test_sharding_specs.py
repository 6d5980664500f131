"""Parameter/caches PartitionSpec derivation + a miniature end-to-end
sharded lowering on 8 fake devices (subprocess — keeps the XLA device-count
flag out of this test process)."""

import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.models.model_api import Model
from repro.models.sharding import param_pspecs

LLM_ARCHS = [a for a in ARCH_IDS if a != "mnist-mlp"]
TP, FSDP = 16, 16


@pytest.mark.parametrize("arch", LLM_ARCHS)
def test_pspec_tree_matches_params(arch):
    model = Model(get_config(arch))
    abstract = model.abstract_params()
    specs = param_pspecs(abstract, TP, FSDP, model.cfg.family)
    assert (jax.tree_util.tree_structure(specs)
            == jax.tree_util.tree_structure(abstract))


@pytest.mark.parametrize("arch", LLM_ARCHS)
def test_pspec_dims_divide_evenly(arch):
    """Every sharded dim must divide exactly by the axis size (we never rely
    on uneven GSPMD padding)."""
    sizes = {"model": TP, "data": FSDP, "pod": 2}
    model = Model(get_config(arch))
    abstract = model.abstract_params()
    specs = param_pspecs(abstract, TP, FSDP, model.cfg.family)
    for (kp, leaf), (_, spec) in zip(
            jax.tree_util.tree_flatten_with_path(abstract)[0],
            jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))[0]):
        for dim, names in enumerate(spec):
            if names is None:
                continue
            names = names if isinstance(names, tuple) else (names,)
            total = 1
            for n in names:
                total *= sizes[n]
            assert leaf.shape[dim] % total == 0, (
                f"{arch}: {jax.tree_util.keystr(kp)} dim {dim} "
                f"({leaf.shape[dim]}) not divisible by {total}")


@pytest.mark.parametrize("arch", ["yi-6b", "phi3.5-moe-42b-a6.6b"])
def test_big_weights_are_sharded(arch):
    """No multi-hundred-MB leaf may stay fully replicated."""
    model = Model(get_config(arch))
    abstract = model.abstract_params()
    specs = param_pspecs(abstract, TP, FSDP, model.cfg.family)
    import math
    for (kp, leaf), (_, spec) in zip(
            jax.tree_util.tree_flatten_with_path(abstract)[0],
            jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))[0]):
        nbytes = math.prod(leaf.shape) * leaf.dtype.itemsize
        if nbytes > 256 * 2 ** 20:
            assert any(s is not None for s in spec), (
                f"{arch}: {jax.tree_util.keystr(kp)} ({nbytes/2**20:.0f} MiB) "
                "replicated")


MINI_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.fl import pofel_trainer as pt
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import build_train_setup
    from repro.configs.shapes import InputShape
    from repro.models.transformer import FwdOptions

    mesh = make_mesh((2, 4), ("data", "model"))
    shape = InputShape("mini_train", 64, 8, "train")
    profile = "{profile}"
    if profile == "zero3":
        tcfg = pt.PoFELTrainConfig(n_clusters=2, cluster_axis="data")
        opts = FwdOptions(remat=False, seq_shard_axis="model", dp_axes=(),
                          parallel_q=True, gather_kv=True,
                          weight_gather=True, expert_axis="model")
    else:
        tcfg = pt.PoFELTrainConfig(n_clusters=4)
        opts = FwdOptions(remat=False)
    # monkeypatch the full config to the reduced one for an 8-device lowering
    import repro.configs as C
    real_get = C.get_config
    import repro.launch.specs as S
    S.get_config = lambda a: real_get(a).reduced()
    setup = build_train_setup("{arch}", mesh, shape, tcfg, opts,
                              profile=profile)
    with mesh:
        compiled = setup.jitted.lower(*setup.abstract_args).compile()
    print("MINI_OK", compiled.cost_analysis() is not None)
""")


@pytest.mark.parametrize("arch,profile", [
    ("yi-6b", "baseline"), ("deepseek-moe-16b", "baseline"),
    ("rwkv6-1.6b", "baseline"), ("zamba2-7b", "baseline"),
    ("musicgen-medium", "baseline"),
    # optimized §Perf profiles
    ("yi-6b", "zero3"), ("deepseek-moe-16b", "zero3"),
])
def test_mini_sharded_lowering(arch, profile):
    """Reduced config, 2×4 fake-device mesh: the full train-step (PoFEL
    round) lowers and compiles with the production sharding rules."""
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", MINI_DRYRUN.format(arch=arch, profile=profile)],
        capture_output=True, text=True, timeout=600, env=env)
    assert "MINI_OK" in res.stdout, res.stderr[-2000:]
