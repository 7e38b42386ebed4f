"""Plain reference: Solar-Open2's forward pass, next-token loss and router
bias update.

Straightforward `jax.numpy` in float32; the caller sets
`jax.default_matmul_precision("highest")`. After upstage/Solar-Open2-250B's
`config.json`, whose keys `cfg` holds under their own names: a decoder of
`num_hidden_layers` blocks, a block at a position of `gqa_layers` grouped-
query attention and every other one Kimi delta attention
(`linear_attn_config`), every block's feed-forward a mixture of experts
(`first_k_dense_replace` 0); `RMSNorm(x) = x * rsqrt(mean(x^2) +
rms_norm_eps) * scale`; no bias unless stated; `x` is `(B, T,
hidden_size)`.

- block (pre-norm, the Glm4Moe / Kimi family's order; the config does not
  state it): `h = x + Mixer(RMSNorm(x))`, `y = h + MoE(RMSNorm(h))`; a
  final `RMSNorm`, then the head; embedding and head untied.
- GQA: `q = W_q x` (`num_attention_heads` of `head_dim`), `k, v = W_k x,
  W_v x` (`num_key_value_heads`); query head `h` reads KV head `h //
  (num_attention_heads / num_key_value_heads)`; no rotary embedding
  (`use_rope` false: `partial_rotary_factor` and `rope_theta` are inert),
  no q/k norm (the config names none); causal `softmax(q k^T /
  sqrt(head_dim)) v`, a row at a time; `o <- o * sigmoid(W_gate x)` over
  each head's `head_dim` (`use_gqa_gate`, read as the query-dependent gate
  after attention, arXiv:2505.06708); `W_o`.
- KDA (Kimi Linear's Kimi Delta Attention, arXiv:2510.26692, as FLA's
  `KimiDeltaAttention`): per head (`linear_attn_config.num_heads` of
  `head_dim` d, keys and values alike), `q, k, v = silu(conv(W x))`, the
  convolution causal, depthwise, of `short_conv_kernel_size` taps; `q <- q
  / |q| d^-1/2`, `k <- k / |k|` (eps 1e-6 inside the root); `b_t = 2
  sigmoid(W_b x)` (`kda_allow_neg_eigval`: the 2); the decay per key
  channel `g_t = -exp(A_log_h) softplus(W_f2 W_f1 x + dt_bias)`, low rank
  through `d` (`kda_use_full_proj` false), `A_log` per head, `dt_bias` per
  channel; the state `S` (d x d), `S_0 = 0`:

      S_t = (I - b_t k_t k_t^T) Diag(e^{g_t}) S_{t-1} + b_t k_t v_t^T,
      o_t = S_t^T q_t

  **token by token**, as written; the output `W_o(RMSNorm_head(o_t) *
  sigmoid(W_g2 W_g1 x + b_g))`, `W_g2` with a bias (FLA's `g_proj`), the
  norm over each head's `d` with one scale of `d`.
- MoE: `s = sigmoid(x W_r)` over all `router_experts` (the published
  count; float32); the `num_experts_per_tok` experts of the largest `s +
  b`, `b` the correction bias in `batch_stats` (zero at the start); `w_e =
  s_e / sum_chosen s` (`norm_topk_prob`) times `routed_scaling_factor`;
  `MoE(x) = sum_{e chosen and held} w_e FFN_e(x) + FFN_shared(x)`, `FFN(x)
  = W_down(silu(W_gate x) * W_up x)` at `moe_intermediate_size`. The held
  experts are `held_offset .. + n_routed_experts`: **every held expert runs
  over every token, times its weight or 0**; what the absent experts would
  add is left out, as in the program. After the step each layer's `b_e <-
  b_e + 1e-3 sign(mean load - load_e)`, loads over all `router_experts`
  (DeepSeek-V3's auxiliary-loss-free rule; the speed is assumed). The key
  set is Glm4Moe's, whose router this sigmoid-with-bias one is.
- the loss: the mean over rows and positions `0..T-2` of the next token's
  cross-entropy, over the vocabulary held (`vocab_size`: a slice).

It fits beside the program's 12.4 GB state by recomputation alone, which
changes no mathematics: each block, each row's scores and logits, each
expert, and the recurrence in spans of 64 tokens are recomputed in the
backward pass (`jax.checkpoint`). `cfg["reference_remat"]: false` turns
that off and `"reference_unroll": true` writes the recurrence as a Python
loop, so that a jaxpr holds every product once.

Imports nothing of the program; the variable tree carries the program's
leaf names. `q` rounds each matmul operand (identity for the reference).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BATCH_COUPLED = False  # every row's loss stands alone
_SPAN = 64  # tokens of the recurrence recomputed together
BIAS_RATE = 1e-3


def _kinds(cfg):
    """'gqa' or 'kda' for each layer built."""
    return ["gqa" if i in cfg["gqa_layers"] else "kda"
            for i in range(cfg["num_hidden_layers"])]


def _kda_dims(cfg):
    """(heads, d) of a KDA layer: keys and values of one head size."""
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"]


def init(cfg, key):
    """Seeded variables: kernels, embedding and head N(0, 0.02), unit norm
    scales, the convolutions' taps U(-K^-1/2, K^-1/2) (torch's `Conv1d`
    default), `A = exp(A_log)` uniform in (1, 16) and `softplus(dt_bias)`
    log-uniform in (1e-3, 0.1), as FLA's `KimiDeltaAttention` makes them,
    `b_g` zero; the routers' correction biases zero."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, dh = cfg["num_attention_heads"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"]
    lh, ld = _kda_dims(cfg)
    taps = cfg["linear_attn_config"]["short_conv_kernel_size"]
    n, e = cfg["n_routed_experts"], cfg["router_experts"]
    f = cfg["moe_intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    keys = iter(jax.random.split(key, 24 * cfg["num_hidden_layers"] + 2))
    normal = lambda *shape: 0.02 * jax.random.normal(next(keys), shape,
                                                     jnp.float32)
    dense = lambda *shape: {"kernel": normal(*shape)}
    ones = lambda m: {"scale": jnp.ones((m,), jnp.float32)}
    conv = lambda m: jax.random.uniform(next(keys), (taps, m), jnp.float32,
                                        -taps ** -0.5, taps ** -0.5)
    params = {"embed": {"embedding": normal(v, d)}, "final_norm": ones(d),
              "head": normal(d, v)}
    stats = {}
    for i, kind in enumerate(_kinds(cfg)):
        if kind == "gqa":
            mixer = {"q": dense(d, heads * dh), "k": dense(d, kv * dh),
                     "v": dense(d, kv * dh), "o": dense(heads * dh, d)}
            if cfg["use_gqa_gate"]:
                mixer["gate"] = dense(d, heads * dh)
        else:
            dt = jnp.exp(jax.random.uniform(next(keys), (lh * ld,),
                                            jnp.float32)
                         * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
            mixer = {
                "q": dense(d, lh * ld), "k": dense(d, lh * ld),
                "v": dense(d, lh * ld), "o": dense(lh * ld, d),
                "q_conv": conv(lh * ld), "k_conv": conv(lh * ld),
                "v_conv": conv(lh * ld), "b": dense(d, lh),
                "f_a": dense(d, ld), "f_b": dense(ld, lh * ld),
                "g_a": dense(d, ld),
                "g_b": {"kernel": normal(ld, lh * ld),
                        "bias": jnp.zeros((lh * ld,), jnp.float32)},
                "o_norm": ones(ld),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (lh,), jnp.float32, 1.0, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt))}
        moe = {"router": normal(d, e), "gate": normal(n, d, f),
               "up": normal(n, d, f), "down": normal(n, f, d)}
        if fs:
            moe.update(shared_gate=normal(d, fs), shared_up=normal(d, fs),
                       shared_down=normal(fs, d))
        params[f"block_{i}"] = {"mixer": mixer, "moe": moe,
                                "mixer_norm": ones(d), "moe_norm": ones(d)}
        stats[f"block_{i}"] = {"moe": {
            "router_bias": jnp.zeros((e,), jnp.float32)}}
    return {"params": params, "batch_stats": stats}


def _rms(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * p["scale"]


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _remat(cfg, fn):
    return jax.checkpoint(fn) if cfg.get("reference_remat", True) else fn


def _rows(cfg, fn, xs):
    """`fn` over the leading axis, a row at a time and recomputed (or, for
    a jaxpr that holds every product, all rows at once)."""
    if cfg.get("reference_unroll"):
        return jax.vmap(fn)(xs)
    return lax.map(_remat(cfg, fn), xs)


def _gqa(cfg, q, x, p):
    b, t, _ = x.shape
    heads, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    proj = lambda name, h: (q(x) @ q(p[name]["kernel"])).reshape(b, t, h, dh)
    qq = proj("q", heads)
    kk, vv = (jnp.repeat(proj(name, kv), heads // kv, axis=2)
              for name in ("k", "v"))

    def row(qkv):  # one row's scores at a time: (H, T, T)
        qq, kk, vv = qkv
        s = jnp.einsum("thk,shk->hts", q(qq), q(kk)) * dh ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.einsum("hts,shk->thk", q(jax.nn.softmax(s, axis=-1)),
                          q(vv))

    o = _rows(cfg, row, (qq, kk, vv))
    if cfg["use_gqa_gate"]:
        o = o * jax.nn.sigmoid(proj("gate", heads))
    return q(o.reshape(b, t, heads * dh)) @ q(p["o"]["kernel"])


def _short_conv(x, kernel):
    """y_t = sum_i kernel[i] x_{t - K + 1 + i}, zeros before the start."""
    taps, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, i:i + t] * kernel[i] for i in range(taps))


def _kda_step(q, s, x):
    """One token of the rule. s: (B, H, dk, dv)."""
    qq, kk, vv, g, beta = x
    s = jnp.exp(g)[..., None] * s  # Diag(e^g) S
    written = beta[..., None] * (vv - jnp.einsum("bhkv,bhk->bhv", q(s),
                                                 q(kk)))
    s = s + jnp.einsum("bhk,bhv->bhkv", q(kk), q(written))
    return s, jnp.einsum("bhkv,bhk->bhv", q(s), q(qq))


def _kda_rule(cfg, q, qq, kk, vv, g, beta):
    """The recurrence over T tokens. Arguments (B, T, H, ...) -> (B, T, H,
    d)."""
    b, t, h, dk = qq.shape
    xs = tuple(jnp.moveaxis(y, 1, 0) for y in (qq, kk, vv, g, beta))
    s = jnp.zeros((b, h, dk, vv.shape[-1]), jnp.float32)
    if cfg.get("reference_unroll"):
        out = []
        for i in range(t):
            s, o = _kda_step(q, s, tuple(y[i] for y in xs))
            out.append(o)
        return jnp.stack(out, axis=1)
    span = _SPAN if t % _SPAN == 0 else t
    spans = tuple(y.reshape(t // span, span, *y.shape[1:]) for y in xs)
    tokens = lambda s, x: lax.scan(lambda s, x: _kda_step(q, s, x), s, x)
    _, o = lax.scan(_remat(cfg, tokens), s, spans)
    return jnp.moveaxis(o.reshape(t, b, h, -1), 0, 1)


def _kda(cfg, q, x, p):
    b, t, _ = x.shape
    h, d = _kda_dims(cfg)
    mm = lambda y, name: q(y) @ q(p[name]["kernel"])

    def mixed(name):
        y = _short_conv(mm(x, name), p[name + "_conv"])
        return _silu(y).reshape(b, t, h, d)

    unit = lambda y: y * lax.rsqrt(
        jnp.sum(jnp.square(y), axis=-1, keepdims=True) + 1e-6)
    qq = unit(mixed("q")) * d ** -0.5
    kk = unit(mixed("k"))
    vv = mixed("v")
    beta = jax.nn.sigmoid(mm(x, "b"))
    if cfg["kda_allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        (mm(mm(x, "f_a"), "f_b") + p["dt_bias"]).reshape(b, t, h, d))
    o = _kda_rule(cfg, q, qq, kk, vv, g, beta)
    gate = (mm(mm(x, "g_a"), "g_b") + p["g_b"]["bias"]).reshape(b, t, h, d)
    o = _rms(o, p["o_norm"], cfg["rms_norm_eps"]) * jax.nn.sigmoid(gate)
    return q(o.reshape(b, t, h * d)) @ q(p["o"]["kernel"])


def _ffn(q, x, gate, up, down):
    return q(_silu(q(x) @ q(gate)) * (q(x) @ q(up))) @ q(down)


def route(cfg, q, x, router, bias):
    """x (T, D) -> (the weight of every expert for every token, zero where
    not chosen: (T, router_experts); each expert's load, float32)."""
    e, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(q(x) @ q(router))
    _, choice = lax.top_k(scores + lax.stop_gradient(bias), k)
    chosen = jax.nn.one_hot(choice, e, dtype=jnp.float32).sum(axis=1)
    weights = scores * chosen
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights * cfg["routed_scaling_factor"], jnp.sum(chosen, axis=0)


def bias_update(bias, load):
    return bias + BIAS_RATE * jnp.sign(jnp.mean(load) - load)


def _moe(cfg, q, x, p, bias):
    """x (B, T, D) -> (MoE(x), the layer's new correction bias)."""
    b, t, d = x.shape
    x = x.reshape(b * t, d)
    weights, load = route(cfg, q, x, p["router"], bias)
    held = lax.dynamic_slice_in_dim(weights, cfg["held_offset"],
                                    cfg["n_routed_experts"], axis=1)
    expert = _remat(cfg, lambda x, w, gate, up, down: w[:, None] * _ffn(
        q, x, gate, up, down))
    out = sum(expert(x, held[:, i], p["gate"][i], p["up"][i], p["down"][i])
              for i in range(cfg["n_routed_experts"]))
    if cfg["n_shared_experts"]:
        out = out + _ffn(q, x, p["shared_gate"], p["shared_up"],
                         p["shared_down"])
    return out.reshape(b, t, d), bias_update(bias, load)


def _block(cfg, q, kind, x, p, bias):
    eps = cfg["rms_norm_eps"]
    mixer = _gqa if kind == "gqa" else _kda
    h = x + mixer(cfg, q, _rms(x, p["mixer_norm"], eps), p["mixer"])
    y, bias = _moe(cfg, q, _rms(h, p["moe_norm"], eps), p["moe"], bias)
    return h + y, bias


def hidden_states(cfg, params, batch_stats, tokens, q=lambda x: x):
    """tokens int (B, T) -> (the final norm's output (B, T, hidden_size),
    the new batch_stats)."""
    x = params["embed"]["embedding"][tokens]
    stats = {}
    for i, kind in enumerate(_kinds(cfg)):
        name = f"block_{i}"
        block = _remat(cfg, lambda x, p, b, kind=kind: _block(
            cfg, q, kind, x, p, b))
        x, bias = block(x, params[name],
                        batch_stats[name]["moe"]["router_bias"])
        stats[name] = {"moe": {"router_bias": bias}}
    return _rms(x, params["final_norm"], cfg["rms_norm_eps"]), stats


def forward(cfg, variables, tokens, q=lambda x: x):
    """tokens int (B, T) -> (logits (B, T, vocab_size), new batch_stats)."""
    params = variables["params"]
    x, stats = hidden_states(cfg, params, variables["batch_stats"], tokens,
                             q)
    return q(x) @ q(params["head"]), stats


def loss_fn(cfg, params, batch_stats, batch, q=lambda x: x):
    """Mean next-token cross-entropy over positions 0..T-2 -> (loss, the
    batch_stats after the step's bias update)."""
    tokens = batch["tokens"]
    x, stats = hidden_states(cfg, params, batch_stats, tokens, q)

    def row(xs):  # one row's logits at a time: (T - 1, vocab_size)
        x, targets = xs
        logp = jax.nn.log_softmax(q(x) @ q(params["head"]))
        return -jnp.take_along_axis(logp, targets[:, None], axis=1)[:, 0]

    return jnp.mean(_rows(cfg, row, (x[:, :-1], tokens[:, 1:]))), stats


# -- the written counts ------------------------------------------------------

def _matmul_parameters(cfg, tokens_per_layer: float):
    """Parameters that multiply every token, head aside, over the layers
    built, the held experts' by the share of tokens they are expected to
    see (`n_routed_experts x num_experts_per_tok / router_experts` of them;
    the convolutions' taps, norms, decays and biases are no matmuls; the
    embedding is a lookup). -> parameter-tokens of one row of
    `tokens_per_layer`."""
    d, e = cfg["hidden_size"], cfg["router_experts"]
    heads, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    lh, ld = _kda_dims(cfg)
    f, n, k = (cfg["moe_intermediate_size"], cfg["n_routed_experts"],
               cfg["num_experts_per_tok"])
    per_kind = {
        "gqa": 2 * d * heads * dh + 2 * d * kv * dh
        + (d * heads * dh if cfg["use_gqa_gate"] else 0),
        "kda": 4 * d * lh * ld + d * lh + 2 * (d * ld + ld * lh * ld)}
    moe = d * e + 3 * d * f * cfg["n_shared_experts"]
    routed = 3 * d * f * n * k / e  # a token's expected share
    return sum((per_kind[kind] + moe + routed) * tokens_per_layer
               for kind in _kinds(cfg))


def delta_rule_flops(cfg, rows: int, tokens: int) -> float:
    """FLOPs of the KDA recurrence's products in one training step, the
    mathematics as written: a token of a head multiplies `(D S)^T k`, the
    rank-one `k written^T` and `S^T q` forward (3 x 2 d d) and each
    product's two gradients backward (6 x 2 d d): 18 d^2. The decay is
    elementwise. A chunked form or a kernel executes other products; the
    same work is read whatever implements it."""
    h, d = _kda_dims(cfg)
    return float(_kinds(cfg).count("kda") * rows * tokens * h * 18 * d * d)


def delta_rule_bytes(cfg, rows: int, tokens: int, itemsize: int) -> float:
    """The least HBM traffic of the same: q, k, the decay g (d each), v, o
    (d each) and b, and their gradients, each once in the io dtype; no
    state leaves near memory."""
    h, d = _kda_dims(cfg)
    return float(_kinds(cfg).count("kda") * rows * tokens * h
                 * 2 * (5 * d + 1) * itemsize)


def expert_flops(cfg, pairs: float) -> float:
    """FLOPs of the held experts' grouped products in one training step
    for `pairs` (token, expert) pairs over all layers: a pair multiplies
    gate, up and down forward (3 x 2 D F) and each product's two gradients
    backward: 18 D F."""
    return float(18 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
                 * pairs)


def expert_bytes(cfg, pairs: float, itemsize: int) -> float:
    """The least HBM traffic of the same: every held expert's three
    matrices read forward, read backward and their gradient written (9 D F
    values an expert a layer), and a pair's rows (x and y of D, gate, up and
    their product of F) once forward and twice backward, in the io dtype."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = 9 * d * f * cfg["n_routed_experts"] * cfg["num_hidden_layers"]
    return float((weights + 3 * pairs * (2 * d + 3 * f)) * itemsize)


def step_flops(cfg, batch_spec, scores: str = "causal") -> float:
    """FLOPs of one training step's mathematics, forward and backward,
    written down: a plain form multiplies every held expert by every token
    and the zeros under a causal mask, and a jaxpr counts a `scan`'s body
    once.

    - every kernel's three products (forward, and the gradient to its
      input and to itself): 6 x parameters x tokens, a held expert's over
      the tokens it is expected to see (`T k n / E` a layer: routing is
      uniform in expectation); the head over the `T - 1` positions that
      have a next token;
    - the scores: a head of a row multiplies `q k^T` and `p v` forward and
      four products backward over the `T (T + 1) / 2` pairs the mask
      leaves (`scores="causal"`): 12 x pairs x head size; `"whole"` counts
      all `T^2`, as a jaxpr of the plain form does;
    - the recurrence: `delta_rule_flops`."""
    rows, tokens = batch_spec["tokens"].shape
    d = cfg["hidden_size"]
    heads, dh = cfg["num_attention_heads"], cfg["head_dim"]
    pairs = {"causal": tokens * (tokens + 1) // 2,
             "whole": tokens * tokens}[scores]
    return float(
        6 * _matmul_parameters(cfg, rows * tokens)
        + 6 * d * cfg["vocab_size"] * rows * (tokens - 1)
        + _kinds(cfg).count("gqa") * rows * heads * 12 * pairs * dh
    ) + delta_rule_flops(cfg, rows, tokens)
