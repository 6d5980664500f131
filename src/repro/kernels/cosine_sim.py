"""Fused batched cosine-similarity partials — the ME hot spot (paper §7.3).

One HBM pass over the stacked FEL models W (N, D) and the global model
gw (D,) produces all three reduction partials of Eq. 2:

    dot_n = Σ_d W[n,d]·gw[d],   wsq_n = Σ_d W[n,d]²,   gsq = Σ_d gw[d]²

Arithmetic intensity: 6 FLOP per 2(+ε) loaded values vs three separate
passes at 2 FLOP each — the kernel is HBM-bound either way, so fusing the
three reductions cuts HBM traffic ~3× (the hillclimb log §Perf quantifies
this on the compiled dry-run).

Tiling: grid = (⌈N/bn⌉, ⌈D/bd⌉), W tiles (bn, bd) in VMEM, gw tile
(1, bd) re-fetched per row-block (Pallas pipelines it), fp32 accumulators
live in the output refs (revisited across the D grid dimension). The
outputs are 2-D — (N, 1) row partials in (bn, 1) blocks and a (1, 1)
‖gw‖² — because Mosaic accepts a rank-1 block only when it spans the
whole array or a multiple of 128 lanes, which (bn,) blocks do not.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _cosine_partials_kernel(w_ref, g_ref, dot_ref, wsq_ref, gsq_ref, *,
                            d: int, bd: int):
    j = pl.program_id(1)
    i = pl.program_id(0)

    @pl.when(j == 0)
    def _init_row():
        dot_ref[...] = jnp.zeros_like(dot_ref)
        wsq_ref[...] = jnp.zeros_like(wsq_ref)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init_g():
        gsq_ref[...] = jnp.zeros_like(gsq_ref)

    w = w_ref[...].astype(jnp.float32)          # (bn, bd)
    g = g_ref[...].astype(jnp.float32)          # (1, bd)
    if d % bd:
        # the last D block runs past the array: its tail holds unspecified
        # values, so select (not multiply) them away
        col = j * bd + jax.lax.broadcasted_iota(jnp.int32, (1, bd), 1)
        w = jnp.where(col < d, w, 0.0)
        g = jnp.where(col < d, g, 0.0)
    dot_ref[...] += jnp.sum(w * g, axis=1, keepdims=True)
    wsq_ref[...] += jnp.sum(w * w, axis=1, keepdims=True)

    @pl.when(i == 0)
    def _acc_g():
        gsq_ref[...] += jnp.sum(g * g, axis=1, keepdims=True)


def interpret_default() -> bool:
    """Compiled (Mosaic) on TPU, interpret mode everywhere else.

    These kernels accumulate into output refs revisited across the grid
    (``dot_ref[...] +=`` over the D dimension), which is only well-defined
    where the grid executes sequentially — i.e. on TPU. A Triton (GPU)
    lowering would race on the accumulators (and the sibling wkv6/flash
    kernels use TPU-only ``pltpu`` scratch), so GPU stays on interpret
    unless a caller overrides ``interpret=`` explicitly.
    """
    return jax.default_backend() != "tpu"


def cosine_partials(W: jax.Array, gw: jax.Array, *, block_n: int = 8,
                    block_d: int = 512, interpret: bool | None = None):
    """(N, D), (D,) → (dot (N,), wsq (N,), gsq ()) in one fused pass.

    ``interpret=None`` (the default) resolves per backend via
    :func:`interpret_default`; pass an explicit bool to override.
    """
    if interpret is None:
        interpret = interpret_default()
    return _cosine_partials(W, gw, block_n=block_n, block_d=block_d,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_n", "block_d", "interpret"))
def _cosine_partials(W: jax.Array, gw: jax.Array, *, block_n: int = 8,
                     block_d: int = 512, interpret: bool = True):
    N, D = W.shape
    bn = min(block_n, N)
    bd = min(block_d, D)
    # edge blocks are partial: out-of-range columns are masked in the
    # kernel and out-of-range rows are never written back, so W is read
    # once, in place (no padded copy)
    grid = (pl.cdiv(N, bn), pl.cdiv(D, bd))
    rows = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))

    dot, wsq, gsq = pl.pallas_call(
        functools.partial(_cosine_partials_kernel, d=D, bd=bd),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
            pl.BlockSpec((1, bd), lambda i, j: (0, j)),
        ],
        out_specs=[rows, rows, pl.BlockSpec((1, 1), lambda i, j: (0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(W, gw.reshape(1, D))
    return dot[:, 0], wsq[:, 0], gsq[0, 0]
