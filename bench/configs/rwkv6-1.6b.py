"""rwkv6-1.6b: RWKV-6 "Finch" 1.6B at its published widths, cut to 4 of
its 24 layers and 8,192 of its 65,536 vocabulary ids; its plain
reference, how the program's adapter and data sets are built, and the
operations a round needs.

Reference forward (Peng et al., arXiv:2404.05892 §4; ``RWKV_Tmix_x060``
and ``RWKV_CMix_x060`` of github.com/BlinkDL/RWKV-LM), written here from
the equations and importing nothing of ``repro.models``:

    x = ln0(embed[tokens])
    per layer:  x += TimeMix(ln1(x));  x += ChannelMix(ln2(x))
    logits = ln_out(x) · head;   per-row mean next-token cross entropy

    TimeMix(x):  sx = x_{t-1} - x_t;   xxx = x + sx·μ_x
                 m_w,m_k,m_v,m_r,m_g = tanh(xxx·A_mix)·B_mix
                 x_□ = x + sx·(μ_□ + m_□)
                 r, k, v = x_r·W_r, x_k·W_k, x_v·W_v;  g = SiLU(x_g·W_g)
                 w = exp(-exp(d0 + tanh(x_w·A_w)·B_w))
                 per head and token:  o = r·(diag(u)·kᵀv + S);
                                      S = diag(w)·S + kᵀv
                 out = (GroupNorm_H(o)·g)·W_o       (eps 64e-5)
    ChannelMix(x): k = relu((x + sx·μ_k)·W_k)²;  r = σ((x + sx·μ_r)·W_r)
                   out = r · (k·W_v)

Every LayerNorm has a weight and a bias (eps 1e-5). Precision, as the
program computes: activations and matrix products in bfloat16 (f32
accumulation), LayerNorms, the group norm, the decay and the WKV state in
float32, parameters and momentum in ``prec.param`` (float32 for the
configuration, bfloat16 for the control). The WKV runs one token a step
(``lax.scan``).

Computed in blocks so that it fits the chip: the backward recomputes each
layer, and within the WKV keeps only the state entering every 32nd token
and then each token's state one block at a time. Without that, one
client's step needs 27.35 GB of a v5e's 15.75 (compiled for a described
v5e). Recomputation repeats the same operations, so the values are
those of the plain scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ACT = jnp.bfloat16          # activation dtype at every precision
STORED_BF16 = ("embed", "lm_head")


def _dims(cfg: dict):
    m = cfg["model"]
    D, K = m["d_model"], m["head_size"]
    return m, D, D // K, K


def init(cfg: dict, key: jax.Array) -> dict:
    """Truncated-normal matrices (std 1/sqrt(fan-in); the LoRA factors a
    tenth of that; the embedding 0.02), μ 0.5, norms 1 and 0; the decay d0
    and the bonus u by the published per-channel schedules of a layer at
    relative depth ``depth``: d0 = −6 + 5·c^(0.7 + 1.3·depth) and
    u = depth·(1 − c) + 0.1·zigzag, c = channel / (D − 1). Embedding and
    head are stored in bfloat16, every other leaf in float32."""
    m, D, H, K = _dims(cfg)
    L, Ld, F, V, n = (m["mix_lora"], m["decay_lora"], m["d_ff"],
                      m["vocab_size"], m["n_layers"])

    def mat(k, shape, fan_in, scale=1.0):
        return (jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
                * scale / jnp.sqrt(jnp.float32(fan_in)))

    chan = jnp.arange(D, dtype=jnp.float32) / (D - 1)

    def layer(k, depth):
        ks = jax.random.split(k, 12)
        one, zero = jnp.ones((D,), jnp.float32), jnp.zeros((D,), jnp.float32)
        zigzag = ((jnp.arange(D) + 1) % 3 - 1).astype(jnp.float32) * 0.1
        return {
            "ln1_w": one, "ln1_b": zero, "ln2_w": one, "ln2_b": zero,
            "mu_x": 0.5 * one, "mu": 0.5 * jnp.ones((5, D), jnp.float32),
            "mix_a": mat(ks[0], (D, 5 * L), D, 0.1),
            "mix_b": mat(ks[1], (5, L, D), L, 0.1),
            "w0": -6.0 + 5.0 * chan ** (0.7 + 1.3 * depth),
            "w_lora_a": mat(ks[2], (D, Ld), D, 0.1),
            "w_lora_b": mat(ks[3], (Ld, D), Ld, 0.1),
            "u": (depth * (1.0 - chan) + zigzag).reshape(H, K),
            "wr": mat(ks[4], (D, D), D), "wk": mat(ks[5], (D, D), D),
            "wv": mat(ks[6], (D, D), D), "wg": mat(ks[7], (D, D), D),
            "wo": mat(ks[8], (D, D), D),
            "ln_x_w": one, "ln_x_b": zero,
            "mu_ffn": 0.5 * jnp.ones((2, D), jnp.float32),
            "wk_ffn": mat(ks[9], (D, F), D), "wv_ffn": mat(ks[10], (F, D), F),
            "wr_ffn": mat(ks[11], (D, D), D),
        }

    ks = jax.random.split(key, n + 2)
    layers = [layer(k, i / max(n - 1, 1)) for i, k in enumerate(ks[2:])]
    return {
        "embed": (jax.random.truncated_normal(ks[0], -2.0, 2.0, (V, D),
                                              jnp.float32) * 0.02
                  ).astype(jnp.bfloat16),
        "ln0_w": jnp.ones((D,), jnp.float32),
        "ln0_b": jnp.zeros((D,), jnp.float32),
        "ln_out_w": jnp.ones((D,), jnp.float32),
        "ln_out_b": jnp.zeros((D,), jnp.float32),
        "lm_head": mat(ks[1], (D, V), D).astype(jnp.bfloat16),
        "layers": jax.tree.map(lambda *xs: jnp.stack(xs), *layers),
    }


def round_start(params: dict) -> dict:
    """The stored dtypes between rounds: embedding and head in bfloat16,
    the rest float32."""
    return {k: (v.astype(jnp.bfloat16) if k in STORED_BF16 else v)
            for k, v in params.items()}


# -- the reference model ------------------------------------------------------

def _ln(x, w, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) / jnp.sqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(ACT)


def _mm(prec, eq, a, b):
    return jnp.einsum(eq, a.astype(ACT), b.astype(ACT), precision=prec.matmul)


def _shift(x):
    """x_{t-1}, zeros before the first token."""
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def _wkv(r, k, v, w, u, chunk=32):
    """(B, S, H, K) float32 → outputs, one token a step from a zero state.
    The backward keeps the state entering every ``chunk`` tokens and
    recomputes the steps between (see the module's docstring)."""
    B, S, H, K = r.shape
    chunk = chunk if S % chunk == 0 else S

    def step(state, t):
        r_t, k_t, v_t, w_t = t
        kv = k_t[..., :, None] * v_t[..., None, :]            # (B, H, K, K)
        out = jnp.sum(r_t[..., :, None] * (u[None, :, :, None] * kv + state),
                      axis=-2)
        return w_t[..., :, None] * state + kv, out

    @jax.checkpoint
    def block(state, ts):
        return jax.lax.scan(jax.checkpoint(step), state, ts)

    seq = tuple(jnp.moveaxis(t, 1, 0).reshape((S // chunk, chunk, B, H, K))
                for t in (r, k, v, w))
    _, outs = jax.lax.scan(block, jnp.zeros((B, H, K, K), jnp.float32), seq)
    return jnp.moveaxis(outs.reshape(S, B, H, K), 0, 1)


def _time_mix(p, x, cfg, prec):
    m, D, H, K = _dims(cfg)
    B, S, _ = x.shape
    L = m["mix_lora"]
    sx = _shift(x) - x
    xxx = x + sx * p["mu_x"].astype(ACT)
    mix = jnp.tanh(_mm(prec, "bsd,dl->bsl", xxx, p["mix_a"]))
    mix = _mm(prec, "bsfl,fld->fbsd", mix.reshape(B, S, 5, L), p["mix_b"])
    xw, xk, xv, xr, xg = (x + sx * (p["mu"][i].astype(ACT) + mix[i])
                          for i in range(5))
    r = _mm(prec, "bsd,de->bse", xr, p["wr"])
    k = _mm(prec, "bsd,de->bse", xk, p["wk"])
    v = _mm(prec, "bsd,de->bse", xv, p["wv"])
    g = jax.nn.silu(_mm(prec, "bsd,de->bse", xg, p["wg"]))
    dd = _mm(prec, "bsl,ld->bsd",
             jnp.tanh(_mm(prec, "bsd,dl->bsl", xw, p["w_lora_a"])),
             p["w_lora_b"])
    w = jnp.exp(-jnp.exp(p["w0"].astype(jnp.float32)
                         + dd.astype(jnp.float32)))
    heads = lambda t: t.reshape(B, S, H, K).astype(jnp.float32)
    o = _wkv(heads(r), heads(k), heads(v), heads(w),
             p["u"].astype(jnp.float32))
    mean = jnp.mean(o, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(o - mean), axis=-1, keepdims=True)
    o = ((o - mean) / jnp.sqrt(var + 64e-5)).reshape(B, S, D)
    o = o * p["ln_x_w"].astype(jnp.float32) + p["ln_x_b"].astype(jnp.float32)
    return _mm(prec, "bsd,de->bse", (o * g.astype(jnp.float32)).astype(ACT),
               p["wo"])


def _channel_mix(p, x, prec):
    sx = _shift(x) - x
    xk = x + sx * p["mu_ffn"][0].astype(ACT)
    xr = x + sx * p["mu_ffn"][1].astype(ACT)
    kk = jnp.square(jax.nn.relu(_mm(prec, "bsd,df->bsf", xk, p["wk_ffn"])))
    rr = jax.nn.sigmoid(_mm(prec, "bsd,de->bse", xr, p["wr_ffn"]))
    return rr * _mm(prec, "bsf,fd->bsd", kk, p["wv_ffn"])


def _logits(params, tokens, cfg, prec):
    x = params["embed"].astype(ACT)[tokens]
    x = _ln(x, params["ln0_w"], params["ln0_b"])
    @jax.checkpoint
    def layer(x, p):
        x = x + _time_mix(p, _ln(x, p["ln1_w"], p["ln1_b"]), cfg, prec)
        return x + _channel_mix(p, _ln(x, p["ln2_w"], p["ln2_b"]), prec)

    for i in range(cfg["model"]["n_layers"]):
        x = layer(x, jax.tree.map(lambda a: a[i], params["layers"]))
    x = _ln(x, params["ln_out_w"], params["ln_out_b"])
    return _mm(prec, "bsd,dv->bsv", x, params["lm_head"]).astype(jnp.float32)


def _token_ce(logits, labels):
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def make_per_example_loss(cfg: dict):
    def per_example_loss(params, batch, key, prec):
        rows = batch["rows"]
        ce = _token_ce(_logits(params, rows[:, :-1], cfg, prec), rows[:, 1:])
        return jnp.mean(ce, axis=-1)
    return per_example_loss


def make_evaluate(cfg: dict):
    def evaluate(params, test, prec):
        rows = test["rows"]
        logits = _logits(params, rows[:, :-1], cfg, prec)
        acc = jnp.mean((jnp.argmax(logits, -1) == rows[:, 1:])
                       .astype(jnp.float32))
        return acc, jnp.mean(_token_ce(logits, rows[:, 1:]))
    return evaluate


# -- the program's side ---------------------------------------------------------

def program_adapter(cfg: dict):
    from repro.fl.adapters import finch_adapter
    m, o = cfg["model"], cfg["optimizer"]
    heads = m["d_model"] // m["head_size"]
    return finch_adapter(
        n_layers=m["n_layers"], d_model=m["d_model"], n_heads=heads,
        n_kv_heads=heads, d_ff=m["d_ff"], vocab_size=m["vocab_size"],
        rwkv_head_size=m["head_size"], rwkv_mix_lora=m["mix_lora"],
        rwkv_decay_lora=m["decay_lora"],
        local_epochs=cfg["local_epochs"], batch_size=cfg["batch_size"],
        lr=o["lr"], momentum=o["momentum"], decay=o["decay"])


def program_dataset(cfg: dict, columns: dict):
    from repro.data.tokens import TokenDataset
    return TokenDataset(columns["rows"], cfg["model"]["vocab_size"])


# -- operations and bytes ---------------------------------------------------------

def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product with each token."""
    m, D, _, _ = _dims(cfg)
    per_layer = (6 * D * D + 2 * D * m["d_ff"] + 10 * D * m["mix_lora"]
                 + 2 * D * m["decay_lora"])
    return m["n_layers"] * per_layer + D * m["vocab_size"]


def wkv_counts(cfg: dict, train_tokens: int, test_tokens: int) -> dict:
    """The least work of the WKV recurrence in a round: forward and
    backward over the trained tokens, forward over the test tokens, none
    recomputed. Per token, layer and head the forward needs the state
    update and the output read, 2 multiply-adds over the K×K state
    (4·K² operations); the backward twice that. Bytes: r, k, v and w read
    and o written in float32 by the forward; the backward reads r, k, v, w
    and dO and writes dr, dk, dv and dw; plus each sequence's K×K states
    in and out. The true kernel moves more (the state for the backward),
    so the share cannot pass 100%."""
    m, D, H, K = _dims(cfg)
    n = m["n_layers"]
    tokens_fb = train_tokens * n
    tokens_f = test_tokens * n
    flops = (4 + 8) * H * K * K * tokens_fb + 4 * H * K * K * tokens_f
    per_token = 4 * D
    bytes_ = (5 + 9) * per_token * tokens_fb + 5 * per_token * tokens_f
    return {"wkv_flops": float(flops), "wkv_bytes": float(bytes_)}


def flops(cfg: dict, train_tokens: int, test_tokens: int) -> dict:
    """Operations of one round's training (6 per weight of a matrix
    product and token, forward and backward, plus the WKV's) and of its
    evaluation (2 per weight and token, plus the WKV forward), and the
    WKV's least work (:func:`wkv_counts`). Norms, token shifts and the
    loss are elementwise and left out."""
    p = matmul_params(cfg)
    wkv = wkv_counts(cfg, train_tokens, test_tokens)
    m, _, H, K = _dims(cfg)
    wkv_eval = 4.0 * H * K * K * test_tokens * m["n_layers"]
    return {"train": 6.0 * p * train_tokens + wkv["wkv_flops"] - wkv_eval,
            "eval": 2.0 * p * test_tokens + wkv_eval, **wkv}


def row_units(columns: dict) -> int:
    """Trained tokens per row: the row's length less the one it starts
    with."""
    return int(columns["rows"].shape[1]) - 1
