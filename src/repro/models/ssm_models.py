"""Full-model definitions for the attention-free / hybrid families:

* rwkv6 — RWKV-6 "Finch" (config.rwkv=True): embedding, ``ln0``, Finch
  blocks, ``ln_out``, head; O(1)-state decode.
* zamba2 hybrid — Mamba2 blocks with a single SHARED attention+MLP block
  applied every ``attn_every`` layers (Zamba2's parameter-sharing trick):
  81 layers = 13 groups × (5 mamba + shared attn) + 3 trailing mamba.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.layers import (COMPUTE_DTYPE, apply_rope, blockwise_attention,
                                 decode_attention, dense_init, embed_init,
                                 rms_norm, swiglu_mlp)
from repro.models.mamba2 import (Mamba2Config, Mamba2State, mamba2_apply,
                                 mamba2_init, mamba2_init_state)
from repro.models.rwkv6 import (RWKVBlockState, RWKVConfig, layer_norm,
                                rwkv_block_apply, rwkv_block_init,
                                rwkv_init_state)

PARAM_DTYPE = jnp.bfloat16


def _stack(trees: list) -> Any:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


# ---------------------------------------------------------------------------
# RWKV6 LM
# ---------------------------------------------------------------------------

def rwkv_cfg_of(cfg: ArchConfig) -> RWKVConfig:
    return RWKVConfig(cfg.d_model, head_size=cfg.rwkv_head_size, d_ff=cfg.d_ff,
                      mix_lora=cfg.rwkv_mix_lora,
                      decay_lora=cfg.rwkv_decay_lora)


def rwkv_init_params(cfg: ArchConfig, key: jax.Array) -> dict:
    """Embedding and head stored in bf16 (``PARAM_DTYPE``), every other
    leaf in float32."""
    rcfg = rwkv_cfg_of(cfg)
    ks = jax.random.split(key, cfg.n_layers + 2)
    D = cfg.d_model
    return {
        "embed": embed_init(ks[0], cfg.vocab_size, D, PARAM_DTYPE),
        "ln0_w": jnp.ones((D,), jnp.float32),
        "ln0_b": jnp.zeros((D,), jnp.float32),
        "ln_out_w": jnp.ones((D,), jnp.float32),
        "ln_out_b": jnp.zeros((D,), jnp.float32),
        "lm_head": dense_init(ks[1], D, cfg.vocab_size, PARAM_DTYPE),
        "layers": _stack([rwkv_block_init(rcfg, k, i, cfg.n_layers)
                          for i, k in enumerate(ks[2:])]),
    }


def rwkv_forward(params: dict, tokens: jax.Array, cfg: ArchConfig,
                 remat: bool = True) -> jax.Array:
    """Finch: embedding → ``ln0`` → blocks → ``ln_out`` → head, activations
    in ``COMPUTE_DTYPE``; each block is rematerialised in the backward
    pass when ``remat``."""
    rcfg = rwkv_cfg_of(cfg)
    x = params["embed"].astype(COMPUTE_DTYPE)[tokens]
    x = layer_norm(x, params["ln0_w"], params["ln0_b"])

    def body(x, layer):
        fn = rwkv_block_apply
        if remat:
            fn = jax.checkpoint(fn, static_argnums=(2,))
        x, _ = fn(layer, x, rcfg)
        return x, None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = layer_norm(x, params["ln_out_w"], params["ln_out_b"])
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(x.dtype))


def rwkv_init_caches(cfg: ArchConfig, batch: int) -> RWKVBlockState:
    rcfg = rwkv_cfg_of(cfg)
    one = rwkv_init_state(rcfg, batch)
    return jax.tree.map(lambda t: jnp.broadcast_to(t, (cfg.n_layers,) + t.shape),
                        one)


def rwkv_decode_step(params: dict, cache: RWKVBlockState, tokens: jax.Array,
                     pos: jax.Array, cfg: ArchConfig
                     ) -> tuple[jax.Array, RWKVBlockState]:
    """tokens (B, 1); the recurrent state is position-independent."""
    del pos
    rcfg = rwkv_cfg_of(cfg)
    x = params["embed"].astype(COMPUTE_DTYPE)[tokens]
    x = layer_norm(x, params["ln0_w"], params["ln0_b"])

    def body(x, scanned):
        layer, st = scanned
        x, st = rwkv_block_apply(layer, x, rcfg, state=st)
        return x, st

    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
    x = layer_norm(x, params["ln_out_w"], params["ln_out_b"])
    logits = jnp.einsum("btd,dv->btv", x, params["lm_head"].astype(x.dtype))
    return logits, new_cache


# ---------------------------------------------------------------------------
# Zamba2 hybrid
# ---------------------------------------------------------------------------

def mamba_cfg_of(cfg: ArchConfig) -> Mamba2Config:
    return Mamba2Config(cfg.d_model, d_state=cfg.ssm_state,
                        expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim)


def hybrid_group_shape(cfg: ArchConfig) -> tuple[int, int, int]:
    """(n_groups, mamba_per_group, n_tail) — groups of (mamba×k, shared attn)."""
    per = cfg.attn_every
    mamba_per_group = per - 1
    n_groups = cfg.n_layers // per
    n_tail = cfg.n_layers - n_groups * per
    return n_groups, mamba_per_group, n_tail


def _shared_attn_init(cfg: ArchConfig, key: jax.Array) -> dict:
    ks = jax.random.split(key, 5)
    D = cfg.d_model
    return {
        "ln1": jnp.ones((D,), jnp.float32),
        "ln2": jnp.ones((D,), jnp.float32),
        "wq": dense_init(ks[0], D, cfg.q_dim, PARAM_DTYPE),
        "wk": dense_init(ks[1], D, cfg.kv_dim, PARAM_DTYPE),
        "wv": dense_init(ks[2], D, cfg.kv_dim, PARAM_DTYPE),
        "wo": dense_init(ks[3], cfg.q_dim, D, PARAM_DTYPE),
        "mlp": {
            "w_gate": dense_init(jax.random.fold_in(ks[4], 0), D, cfg.d_ff, PARAM_DTYPE),
            "w_up": dense_init(jax.random.fold_in(ks[4], 1), D, cfg.d_ff, PARAM_DTYPE),
            "w_down": dense_init(jax.random.fold_in(ks[4], 2), cfg.d_ff, D, PARAM_DTYPE),
        },
    }


def hybrid_init_params(cfg: ArchConfig, key: jax.Array) -> dict:
    mcfg = mamba_cfg_of(cfg)
    n_groups, mpg, n_tail = hybrid_group_shape(cfg)
    ks = jax.random.split(key, 4)
    grp_keys = jax.random.split(ks[2], n_groups * mpg)
    grouped = _stack([mamba2_init(mcfg, k) for k in grp_keys])
    grouped = jax.tree.map(
        lambda x: x.reshape((n_groups, mpg) + x.shape[1:]), grouped)
    params = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, PARAM_DTYPE),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        "lm_head": dense_init(ks[1], cfg.d_model, cfg.vocab_size, PARAM_DTYPE),
        "mamba_groups": grouped,
        "shared_attn": _shared_attn_init(cfg, ks[3]),
    }
    if n_tail:
        tail_keys = jax.random.split(jax.random.fold_in(ks[2], 999), n_tail)
        params["mamba_tail"] = _stack([mamba2_init(mcfg, k) for k in tail_keys])
    return params


def _shared_attn_apply(sa: dict, x: jax.Array, cfg: ArchConfig,
                       positions: jax.Array) -> jax.Array:
    B, S, D = x.shape
    h = rms_norm(x, sa["ln1"], cfg.norm_eps)
    q = jnp.einsum("bsd,dh->bsh", h, sa["wq"].astype(h.dtype)
                   ).reshape(B, S, cfg.n_heads, cfg.hd)
    k = jnp.einsum("bsd,dh->bsh", h, sa["wk"].astype(h.dtype)
                   ).reshape(B, S, cfg.n_kv_heads, cfg.hd)
    v = jnp.einsum("bsd,dh->bsh", h, sa["wv"].astype(h.dtype)
                   ).reshape(B, S, cfg.n_kv_heads, cfg.hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    o = blockwise_attention(q, k, v, causal=True, window=cfg.sliding_window)
    x = x + jnp.einsum("bsh,hd->bsd", o.reshape(B, S, cfg.q_dim),
                       sa["wo"].astype(x.dtype))
    h = rms_norm(x, sa["ln2"], cfg.norm_eps)
    m = sa["mlp"]
    return x + swiglu_mlp(h, m["w_gate"], m["w_up"], m["w_down"])


def hybrid_forward(params: dict, tokens: jax.Array, cfg: ArchConfig,
                   remat: bool = True, sharded: bool = False) -> jax.Array:
    mcfg = mamba_cfg_of(cfg)
    B, S = tokens.shape
    x = params["embed"].astype(COMPUTE_DTYPE)[tokens]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def mamba_body(x, layer):
        fn = mamba2_apply
        if remat:
            fn = jax.checkpoint(fn, static_argnums=(2, 4))
        out, _ = fn(layer, x, mcfg, None, sharded)
        return x + out, None

    def group_body(x, group):
        x, _ = jax.lax.scan(mamba_body, x, group)
        x = _shared_attn_apply(params["shared_attn"], x, cfg, positions)
        return x, None

    x, _ = jax.lax.scan(group_body, x, params["mamba_groups"])
    if "mamba_tail" in params:
        x, _ = jax.lax.scan(mamba_body, x, params["mamba_tail"])
    x = rms_norm(x, params["final_norm"])
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(x.dtype))


class HybridCache(NamedTuple):
    mamba_groups: Mamba2State     # leaves lead with (n_groups, mpg, ...)
    mamba_tail: Optional[Mamba2State]
    attn_k: jax.Array             # (n_groups, B, S, Hk, hd)
    attn_v: jax.Array


def hybrid_init_cache(cfg: ArchConfig, batch: int, seq_len: int) -> HybridCache:
    mcfg = mamba_cfg_of(cfg)
    n_groups, mpg, n_tail = hybrid_group_shape(cfg)
    one = mamba2_init_state(mcfg, batch)
    grouped = jax.tree.map(
        lambda t: jnp.broadcast_to(t, (n_groups, mpg) + t.shape), one)
    tail = (jax.tree.map(lambda t: jnp.broadcast_to(t, (n_tail,) + t.shape), one)
            if n_tail else None)
    k = jnp.zeros((n_groups, batch, seq_len, cfg.n_kv_heads, cfg.hd),
                  COMPUTE_DTYPE)
    return HybridCache(grouped, tail, k, jnp.zeros_like(k))


def hybrid_decode_step(params: dict, cache: HybridCache, tokens: jax.Array,
                       pos: jax.Array, cfg: ArchConfig
                       ) -> tuple[jax.Array, HybridCache]:
    mcfg = mamba_cfg_of(cfg)
    B = tokens.shape[0]
    x = params["embed"].astype(COMPUTE_DTYPE)[tokens]
    sa = params["shared_attn"]

    def mamba_body(x, scanned):
        layer, st = scanned
        out, st = mamba2_apply(layer, x, mcfg, state=st)
        return x + out, st

    def group_body(x, scanned):
        group, states, kc, vc = scanned
        x, states = jax.lax.scan(mamba_body, x, (group, states))
        # shared attention with this group's KV cache
        h = rms_norm(x, sa["ln1"], cfg.norm_eps)
        q = jnp.einsum("btd,dh->bth", h, sa["wq"].astype(h.dtype)
                       ).reshape(B, 1, cfg.n_heads, cfg.hd)
        k = jnp.einsum("btd,dh->bth", h, sa["wk"].astype(h.dtype)
                       ).reshape(B, 1, cfg.n_kv_heads, cfg.hd)
        v = jnp.einsum("btd,dh->bth", h, sa["wv"].astype(h.dtype)
                       ).reshape(B, 1, cfg.n_kv_heads, cfg.hd)
        pvec = jnp.broadcast_to(pos[None], (B, 1)).astype(jnp.int32)
        q = apply_rope(q, pvec, cfg.rope_theta)
        k = apply_rope(k, pvec, cfg.rope_theta)
        kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype), (0, pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype), (0, pos, 0, 0))
        o = decode_attention(q, kc, vc, pos, window=cfg.sliding_window)
        x = x + jnp.einsum("bth,hd->btd", o.reshape(B, 1, cfg.q_dim),
                           sa["wo"].astype(x.dtype))
        h = rms_norm(x, sa["ln2"], cfg.norm_eps)
        m = sa["mlp"]
        x = x + swiglu_mlp(h, m["w_gate"], m["w_up"], m["w_down"])
        return x, (states, kc, vc)

    x, (g_states, kcs, vcs) = jax.lax.scan(
        group_body, x, (params["mamba_groups"], cache.mamba_groups,
                        cache.attn_k, cache.attn_v))
    tail_states = cache.mamba_tail
    if "mamba_tail" in params:
        x, tail_states = jax.lax.scan(
            mamba_body, x, (params["mamba_tail"], cache.mamba_tail))
    x = rms_norm(x, params["final_norm"])
    logits = jnp.einsum("btd,dv->btv", x, params["lm_head"].astype(x.dtype))
    return logits, HybridCache(g_states, tail_states, kcs, vcs)
