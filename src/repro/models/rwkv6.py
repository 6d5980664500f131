"""RWKV-6 "Finch" block (Peng et al., *Eagle and Finch*, arXiv:2404.05892
§4; reference code ``RWKV_Tmix_x060`` / ``RWKV_CMix_x060`` in
github.com/BlinkDL/RWKV-LM ``RWKV-v5/src/model.py``).

Time mixing, on ``x = LN1(h)`` (``sx = x_{t-1} - x_t``, the token shift):

  xxx = x + sx ⊙ μ_x
  [m_w, m_k, m_v, m_r, m_g] = tanh(xxx · A_mix) · B_mix      (ddlerp LoRA)
  x_□ = x + sx ⊙ (μ_□ + m_□)
  r, k, v = x_r W_r, x_k W_k, x_v W_v;   g = SiLU(x_g W_g)
  w_t = exp(−exp(d_0 + tanh(x_w A_w) B_w))                   (decay LoRA)
  per head:  o_t = r_t (diag(u) k_tᵀ v_t + S_{t−1}),
             S_t = diag(w_t) S_{t−1} + k_tᵀ v_t
  out = W_o (GroupNorm_H(o) ⊙ g)       (ln_x: weight and bias, eps 64e-5)

Channel mixing, on ``x = LN2(h)``:

  x_k = x + sx ⊙ μ_k,   x_r = x + sx ⊙ μ_r
  out = σ(x_r W_r) ⊙ (relu(x_k W_k)² W_v)

Block: ``h += TimeMix(LN1(h)); h += ChannelMix(LN2(h))``; every LayerNorm
has a weight and a bias (eps 1e-5). The model's ``ln0`` after the
embedding lives in ``models.ssm_models``.

Departures from the reference code:

* initialisation: matrices by ``layers.dense_init`` (the LoRA factors
  scaled by 0.1 so that both train from the first step), ``μ`` 0.5,
  norms 1 and 0 — not the published zero/orthogonal inits and ``μ``
  schedules; ``d_0`` and ``u`` follow the published per-channel,
  depth-dependent schedules;
* precision: the matrix products take the activations' dtype (bf16 in
  ``models.ssm_models``); the decay, the WKV state and the group norm
  are float32;
* no dropout, no ``head_size_divisor`` scaling of ``r`` or ``k``.

The recurrence runs as ``lax.scan`` over time (O(S) work, O(1) state);
``use_pallas`` runs the same recurrence through ``repro.kernels.wkv6``.
The time mixing, the recurrence and the channel mixing are named scopes
(``rwkv.time_mix``, ``wkv6``, ``rwkv.channel_mix``), so device-trace ops
carry them.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init

LN_EPS = 1e-5
LN_X_EPS = 64e-5        # 1e-5 · head_size_divisor², divisor 8
WKV_CHUNK = 32          # tokens between the states the backward keeps


class RWKVConfig(NamedTuple):
    d_model: int
    head_size: int = 64
    d_ff: int = 0            # channel-mix hidden; 3.5x d_model if 0
    mix_lora: int = 32       # D_MIX_LORA: the token shift's ddlerp
    decay_lora: int = 64     # D_DECAY_LORA

    @property
    def n_heads(self) -> int:
        return self.d_model // self.head_size

    @property
    def ffn_dim(self) -> int:
        return self.d_ff or int(3.5 * self.d_model)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array,
               eps: float = LN_EPS) -> jax.Array:
    """LayerNorm over the last axis in float32, back in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def rwkv_block_init(cfg: RWKVConfig, key: jax.Array, layer: int = 0,
                    n_layers: int = 1) -> dict:
    """Layer ``layer`` of ``n_layers``: the decay ``d_0`` and the bonus ``u``
    follow the published per-channel schedules (``time_decay``,
    ``time_faaaa``), which depend on the layer's depth."""
    D, H, K = cfg.d_model, cfg.n_heads, cfg.head_size
    L, Ld = cfg.mix_lora, cfg.decay_lora
    ks = jax.random.split(key, 13)
    ones = jnp.ones((D,), jnp.float32)
    zeros = jnp.zeros((D,), jnp.float32)
    depth = layer / max(n_layers - 1, 1)
    chan = jnp.arange(D, dtype=jnp.float32) / (D - 1)
    zigzag = ((jnp.arange(D) + 1) % 3 - 1).astype(jnp.float32) * 0.1
    return {
        "ln1_w": ones, "ln1_b": zeros, "ln2_w": ones, "ln2_b": zeros,
        # time mixing: token shift (ddlerp) and decay
        "mu_x": 0.5 * ones,
        "mu": 0.5 * jnp.ones((5, D), jnp.float32),     # w, k, v, r, g
        "mix_a": dense_init(ks[0], D, 5 * L) * 0.1,
        "mix_b": jnp.stack([dense_init(k, L, D) for k in
                            jax.random.split(ks[1], 5)]) * 0.1,
        "w0": -6.0 + 5.0 * chan ** (0.7 + 1.3 * depth),
        "w_lora_a": dense_init(ks[2], D, Ld) * 0.1,
        "w_lora_b": dense_init(ks[3], Ld, D) * 0.1,
        "u": (depth * (1.0 - chan) + zigzag).reshape(H, K),   # bonus
        "wr": dense_init(ks[4], D, D),
        "wk": dense_init(ks[5], D, D),
        "wv": dense_init(ks[6], D, D),
        "wg": dense_init(ks[7], D, D),
        "wo": dense_init(ks[8], D, D),
        "ln_x_w": ones, "ln_x_b": zeros,               # per-head group norm
        # channel mixing
        "mu_ffn": 0.5 * jnp.ones((2, D), jnp.float32),  # k, r
        "wk_ffn": dense_init(ks[9], D, cfg.ffn_dim),
        "wv_ffn": dense_init(ks[10], cfg.ffn_dim, D),
        "wr_ffn": dense_init(ks[11], D, D),
    }


def _group_norm(x: jax.Array, w: jax.Array, b: jax.Array, n_heads: int,
                eps: float = LN_X_EPS) -> jax.Array:
    """Per-head LayerNorm over the head's channels (RWKV's ``ln_x``), in
    float32."""
    B, S, D = x.shape
    xh = x.reshape(B, S, n_heads, D // n_heads).astype(jnp.float32)
    mean = jnp.mean(xh, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xh - mean), axis=-1, keepdims=True)
    xh = (xh - mean) * jax.lax.rsqrt(var + eps)
    return xh.reshape(B, S, D) * w.astype(jnp.float32) + b.astype(jnp.float32)


def _token_shift(x: jax.Array, x_prev_last: jax.Array | None = None) -> jax.Array:
    """(B, S, D) → previous-token tensor; x_prev_last seeds position 0."""
    shifted = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    if x_prev_last is not None:
        shifted = shifted.at[:, 0].set(x_prev_last.astype(x.dtype))
    return shifted


def _dot(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.einsum("bsd,de->bse", x, w.astype(x.dtype))


def _time_mix_inputs(params: dict, x: jax.Array, shifted: jax.Array,
                     cfg: RWKVConfig):
    """The token shift's ddlerp and the projections: r, k, v, SiLU(g) in
    ``x``'s dtype, the decay w in float32."""
    B, S, D = x.shape
    sx = shifted - x
    xxx = x + sx * params["mu_x"].astype(x.dtype)
    m = jnp.tanh(_dot(xxx, params["mix_a"]))                 # (B, S, 5L)
    m = jnp.einsum("bsfl,fld->fbsd", m.reshape(B, S, 5, cfg.mix_lora),
                   params["mix_b"].astype(x.dtype))          # (5, B, S, D)
    mu = params["mu"].astype(x.dtype)[:, None, None, :]
    xw, xk, xv, xr, xg = x[None] + sx[None] * (mu + m)
    r = _dot(xr, params["wr"])
    k = _dot(xk, params["wk"])
    v = _dot(xv, params["wv"])
    g = jax.nn.silu(_dot(xg, params["wg"]))
    dd = jnp.tanh(_dot(xw, params["w_lora_a"]))
    dd = jnp.einsum("bsl,ld->bsd", dd, params["w_lora_b"].astype(dd.dtype))
    w = jnp.exp(-jnp.exp(params["w0"].astype(jnp.float32)
                         + dd.astype(jnp.float32)))          # (B,S,D) in (0,1)
    return r, k, v, g, w


def wkv_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
             u: jax.Array, state: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The WKV recurrence over (B, S, H, K) float32 inputs, one token a
    step: returns ((B, S, H, K) outputs, (B, H, K, K) final state)."""
    def step(S_prev, inputs):
        r_t, k_t, v_t, w_t = inputs                      # (B,H,K) each
        kv = jnp.einsum("bhk,bhv->bhkv", k_t, v_t)
        o_t = jnp.einsum("bhk,bhkv->bhv", r_t, S_prev + u[None, :, :, None] * kv)
        return w_t[..., None] * S_prev + kv, o_t

    S = r.shape[1]
    chunk = WKV_CHUNK if S % WKV_CHUNK == 0 else S
    xs = tuple(t.transpose(1, 0, 2, 3).reshape((S // chunk, chunk) + t.shape[:1]
                                               + t.shape[2:])
               for t in (r, k, v, w))

    @jax.checkpoint
    def run_chunk(S_prev, chunk_xs):
        return jax.lax.scan(jax.checkpoint(step), S_prev, chunk_xs)

    # the backward keeps the state entering each chunk, then, one chunk at
    # a time, the state entering each of its steps: (S/chunk + chunk)
    # (B, H, K, K) states, not several a token
    new_state, outs = jax.lax.scan(run_chunk, state, xs)
    return outs.reshape((S,) + outs.shape[2:]).transpose(1, 0, 2, 3), new_state


def rwkv_time_mix(params: dict, x: jax.Array, cfg: RWKVConfig,
                  state: jax.Array | None = None,
                  shift_state: jax.Array | None = None,
                  use_pallas: bool = False,
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Finch time mixing over (B, S, D), ``x`` already LayerNorm'd.

    state: (B, H, K, V) carry; shift_state: (B, D) last token of prev chunk.
    use_pallas: run the VMEM-resident kernel (repro.kernels.wkv6) instead of
    the lax.scan recurrence — identical numerics (tests/test_kernels_wkv6).
    Returns (out, new_state, new_shift_state).
    """
    B, S, D = x.shape
    H, K = cfg.n_heads, cfg.head_size
    with jax.named_scope("rwkv.time_mix"):
        shifted = _token_shift(x, shift_state)
        r, k, v, g, w = _time_mix_inputs(params, x, shifted, cfg)
        rh, kh, vh = (t.reshape(B, S, H, K).astype(jnp.float32)
                      for t in (r, k, v))
        wh = w.reshape(B, S, H, K)
        u = params["u"].astype(jnp.float32)              # (H, K)
        if state is None:
            state = jnp.zeros((B, H, K, K), jnp.float32)
        with jax.named_scope("wkv6"):
            if use_pallas:
                from repro.kernels.ops import wkv6_recurrence
                o, new_state = wkv6_recurrence(rh, kh, vh, wh, u, state)
            else:
                o, new_state = wkv_scan(rh, kh, vh, wh, u, state)
        o = _group_norm(o.reshape(B, S, D), params["ln_x_w"],
                        params["ln_x_b"], H)
        o = (o * g.astype(jnp.float32)).astype(x.dtype)
        out = _dot(o, params["wo"])
    return out, new_state, x[:, -1]


def rwkv_channel_mix(params: dict, x: jax.Array, cfg: RWKVConfig,
                     shift_state: jax.Array | None = None,
                     ) -> tuple[jax.Array, jax.Array]:
    """Finch channel mixing over (B, S, D), ``x`` already LayerNorm'd."""
    with jax.named_scope("rwkv.channel_mix"):
        sx = _token_shift(x, shift_state) - x
        mu = params["mu_ffn"].astype(x.dtype)
        xk = x + sx * mu[0]
        xr = x + sx * mu[1]
        kk = jnp.square(jax.nn.relu(_dot(xk, params["wk_ffn"])))
        vv = jnp.einsum("bsf,fd->bsd", kk, params["wv_ffn"].astype(x.dtype))
        rr = jax.nn.sigmoid(_dot(xr, params["wr_ffn"]))
    return rr * vv, x[:, -1]


class RWKVBlockState(NamedTuple):
    wkv: jax.Array          # (B, H, K, K)
    shift_tm: jax.Array     # (B, D)
    shift_cm: jax.Array     # (B, D)


def rwkv_block_apply(params: dict, x: jax.Array, cfg: RWKVConfig,
                     state: RWKVBlockState | None = None,
                     ) -> tuple[jax.Array, RWKVBlockState]:
    h = layer_norm(x, params["ln1_w"], params["ln1_b"])
    tm, wkv, sh_tm = rwkv_time_mix(
        params, h, cfg,
        state=None if state is None else state.wkv,
        shift_state=None if state is None else state.shift_tm)
    x = x + tm
    h = layer_norm(x, params["ln2_w"], params["ln2_b"])
    cm, sh_cm = rwkv_channel_mix(
        params, h, cfg,
        shift_state=None if state is None else state.shift_cm)
    x = x + cm
    return x, RWKVBlockState(wkv, sh_tm, sh_cm)


def rwkv_init_state(cfg: RWKVConfig, batch: int) -> RWKVBlockState:
    return RWKVBlockState(
        jnp.zeros((batch, cfg.n_heads, cfg.head_size, cfg.head_size), jnp.float32),
        jnp.zeros((batch, cfg.d_model), jnp.float32),
        jnp.zeros((batch, cfg.d_model), jnp.float32))
