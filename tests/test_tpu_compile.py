"""The main path's device programs, the ME kernel and the batched FEL
round, compile for a TPU v5e that is described, not attached (the TPU
compiler ships with jax; nothing runs).

Interpret mode accepts block shapes that Mosaic refuses, so the CPU
kernel tests cannot see a kernel that would crash the ME phase on the
chip. These compiles can. The topology is described inside a fixture,
never at import, so every test worker collects the same tests and only
the one running this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.model_eval import model_evaluation

D_MLP = 101_770     # the paper's MLP, 784-128-10 (§7.1)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("n", [8, 16, 64])
def test_me_kernel_compiles_for_v5e(one_chip, n):
    """N=64 was refused while the kernel had rank-1 (bn,) output blocks."""
    W = jax.ShapeDtypeStruct((n, D_MLP), jnp.float32, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    compiled = model_evaluation.lower(W, sizes, use_kernel=True,
                                      interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_batched_fel_round_compiles_for_v5e(one_chip):
    """The batched FEL round program of the paper's setting (MLP, 8 edge
    servers × 5 clients, 3 FEL iterations), on fewer samples per client."""
    from repro.data.synthetic import make_mnist_like
    from repro.fl.hfl_runtime import BHFLConfig, BHFLRuntime
    from repro.fl.hierarchy import build_hierarchy

    train, _ = make_mnist_like(n_train=8 * 5 * 64, n_test=10)
    cfg = BHFLConfig(n_nodes=8, clients_per_node=5, fel_iterations=3,
                     engine="batched")
    rt = BHFLRuntime(build_hierarchy(train, 8, 5, "iid"), cfg, None)
    eng = rt._engine
    idx, seeds = eng._batch_plan(round_seed=1)
    args = (rt._global_flat, idx, seeds.astype("int32"), eng._data,
            eng._sizes_f, eng._bs_dev, eng._stepmask)
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    assert shapes[0].shape == (D_MLP,)
    eng._round_fn.lower(*shapes).compile()
