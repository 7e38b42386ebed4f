"""Plain reference: Olmo-Hybrid's forward pass and next-token loss.

Straightforward `jax.numpy` in float32; the caller sets
`jax.default_matmul_precision("highest")`. After allenai/Olmo-Hybrid-7B's
`config.json`, whose keys `cfg` holds under their own names: a decoder of
`num_hidden_layers` blocks, the first of `layer_types`, each a sequence
mixer and a SwiGLU MLP, no bias anywhere, `RMSNorm(x) = x *
rsqrt(mean(x^2) + rms_norm_eps) * scale`; `x` is `(B, T, hidden_size)`.

- block (the OLMo 2/3 family's norm after the sublayer; the config does not
  state it): `h = x + RMSNorm(Mixer(x))`, `y = h + RMSNorm(MLP(h))`,
  `MLP(h) = W_down(silu(W_gate h) * W_up h)`; a final `RMSNorm`, then the
  head; embedding and head untied.
- `full_attention`: `q, k, v = W_q x, W_k x, W_v x`; `q, k <- RMSNorm(q),
  RMSNorm(k)` over the whole width (the family's); heads of `hidden_size /
  num_attention_heads`; causal `softmax(q k^T / sqrt(head)) v`; `W_o`. No
  rotary embedding: the config's `rope_theta` is null.
- `linear_attention` (the gated delta rule, FLA's `GatedDeltaNet`
  convention for the `linear_*` keys): `q = W_q x`, `k = W_k x` (heads of
  `linear_key_head_dim`), `v = W_v x` (heads of `linear_value_head_dim`),
  each through a causal depthwise convolution of `linear_conv_kernel_dim`
  taps, then `silu`; per head `q <- q / |q| * dk^-1/2`, `k <- k / |k|`;
  `b_t = 2 sigmoid(W_b x)` (`linear_allow_neg_eigval`: the 2); `g_t =
  -exp(A_log) softplus(W_a x + dt_bias)`, `a_t = exp(g_t)`; per head a
  state `S` of `dv x dk`, `S_0 = 0`:

      S_t = a_t S_{t-1} (I - b_t k_t k_t^T) + b_t v_t k_t^T,   o_t = S_t q_t

  **token by token**, as written: the program computes it in chunks, and
  this does not. The output is `W_o(RMSNorm_head(o_t) * silu(W_g x))`, the
  norm over each head's `dv` with one scale of `dv`.
- the loss: the mean over rows and positions `0..T-2` of the next token's
  cross-entropy, over the vocabulary held (`vocab_size`: a slice, where the
  vocabulary is divided over chips).

It fits beside an 11 GB training state by recomputation alone, which
changes no mathematics: each block, each row's scores, and the recurrence
in spans of 64 tokens are recomputed in the backward pass (`jax.checkpoint`;
storing every `S_t` would be 9 GB a layer at 2 x 2048 tokens).
`cfg["reference_remat"]: false` turns that off and `"reference_unroll":
true` writes the recurrence as a Python loop, so that a jaxpr holds every
product once (`tests/benchmark/test_olmo_hybrid_cell.py` counts them so).

Imports nothing of the program; the variable tree carries the program's
leaf names. `q` rounds each matmul operand (identity for the reference).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BATCH_COUPLED = False  # every row stands alone
LINEAR, FULL = "linear_attention", "full_attention"
_SPAN = 64  # tokens of the recurrence recomputed together


def _layers(cfg):
    return list(cfg["layer_types"])[:cfg["num_hidden_layers"]]


def _linear_dims(cfg):
    """(heads, dk, dv) of a linear-attention layer."""
    h = cfg["linear_num_value_heads"]
    assert h == cfg["linear_num_key_heads"], "key and value heads differ"
    return h, cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]


def init(cfg, key):
    """Seeded variables {"params", "batch_stats": {}}: kernels and the
    embedding N(0, 0.02), unit norm scales, the convolutions' taps
    U(-1/2, 1/2) (torch's `Conv1d` default at 4 taps), `A_log` the
    logarithm of U(0, 16) and `dt_bias` the inverse softplus of a step
    log-uniform in (1e-3, 0.1), as FLA makes them."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, dk, dv = _linear_dims(cfg)
    taps = cfg["linear_conv_kernel_dim"]
    keys = iter(jax.random.split(key, 16 * len(_layers(cfg)) + 2))
    normal = lambda *shape: 0.02 * jax.random.normal(next(keys), shape,
                                                     jnp.float32)
    dense = lambda *shape: {"kernel": normal(*shape)}
    ones = lambda n: {"scale": jnp.ones((n,), jnp.float32)}
    conv = lambda n: jax.random.uniform(next(keys), (taps, n), jnp.float32,
                                        -taps ** -0.5, taps ** -0.5)
    params = {"embed": {"embedding": normal(v, d)}, "final_norm": ones(d),
              "head": normal(d, v)}
    for i, kind in enumerate(_layers(cfg)):
        if kind == FULL:
            mixer = {"q": dense(d, d), "k": dense(d, d), "v": dense(d, d),
                     "o": dense(d, d), "q_norm": ones(d), "k_norm": ones(d)}
        else:
            dt = jnp.exp(jax.random.uniform(next(keys), (h,), jnp.float32)
                         * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
            mixer = {
                "q": dense(d, h * dk), "k": dense(d, h * dk),
                "v": dense(d, h * dv), "g": dense(d, h * dv),
                "a": dense(d, h), "b": dense(d, h), "o": dense(h * dv, d),
                "q_conv": conv(h * dk), "k_conv": conv(h * dk),
                "v_conv": conv(h * dv), "o_norm": ones(dv),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (h,), jnp.float32, 1e-3, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt))}
        params[f"block_{i}"] = {
            "mixer": mixer, "mixer_norm": ones(d), "mlp_norm": ones(d),
            "mlp": {"gate": dense(d, f), "up": dense(d, f),
                    "down": dense(f, d)}}
    return {"params": params, "batch_stats": {}}


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


def _full_attention(cfg, q, x, p):
    b, t, d = x.shape
    h = cfg["num_attention_heads"]
    eps = cfg["rms_norm_eps"]
    heads = lambda y: y.reshape(b, t, h, d // h)
    qq = heads(_rms(q(x) @ q(p["q"]["kernel"]), p["q_norm"], eps))
    kk = heads(_rms(q(x) @ q(p["k"]["kernel"]), p["k_norm"], eps))
    vv = heads(q(x) @ q(p["v"]["kernel"]))

    def row(qkv):  # one row's scores at a time: (H, T, T)
        qq, kk, vv = qkv
        s = jnp.einsum("thk,shk->hts", q(qq), q(kk)) * (d // h) ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.einsum("hts,shk->thk", q(jax.nn.softmax(s, axis=-1)),
                          q(vv))

    o = _rows(cfg, row, (qq, kk, vv))
    return q(o.reshape(b, t, d)) @ q(p["o"]["kernel"])


def _short_conv(x, kernel):
    """y_t = sum_i kernel[i] x_{t - K + 1 + i}, zeros before the start."""
    taps, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, i:i + t] * kernel[i] for i in range(taps))


def _delta_step(q, s, x):
    """One token of the gated delta rule. s: (B, H, dv, dk)."""
    qq, kk, vv, g, beta = x
    s = jnp.exp(g)[..., None, None] * s
    written = beta[..., None] * (vv - jnp.einsum("bhvk,bhk->bhv", q(s), q(kk)))
    s = s + jnp.einsum("bhv,bhk->bhvk", q(written), q(kk))
    return s, jnp.einsum("bhvk,bhk->bhv", q(s), q(qq))


def _delta_rule(cfg, q, qq, kk, vv, g, beta):
    """The recurrence over T tokens. Arguments (B, T, H, ...) -> (B, T, H,
    dv)."""
    b, t, h, dk = qq.shape
    xs = tuple(jnp.moveaxis(y, 1, 0) for y in (qq, kk, vv, g, beta))
    s = jnp.zeros((b, h, vv.shape[-1], dk), jnp.float32)
    if cfg.get("reference_unroll"):
        out = []
        for i in range(t):
            s, o = _delta_step(q, s, tuple(y[i] for y in xs))
            out.append(o)
        return jnp.stack(out, axis=1)
    span = _SPAN if t % _SPAN == 0 else t
    spans = tuple(y.reshape(t // span, span, *y.shape[1:]) for y in xs)
    tokens = lambda s, x: lax.scan(lambda s, x: _delta_step(q, s, x), s, x)
    _, o = lax.scan(_remat(cfg, tokens), s, spans)
    return jnp.moveaxis(o.reshape(t, b, h, -1), 0, 1)


def _linear_attention(cfg, q, x, p):
    b, t, _ = x.shape
    h, dk, dv = _linear_dims(cfg)

    def mixed(name, width):
        y = _short_conv(q(x) @ q(p[name]["kernel"]), p[name + "_conv"])
        return _silu(y).reshape(b, t, h, width)

    unit = lambda y: y * lax.rsqrt(
        jnp.sum(jnp.square(y), axis=-1, keepdims=True) + 1e-6)
    qq = unit(mixed("q", dk)) * dk ** -0.5
    kk = unit(mixed("k", dk))
    vv = mixed("v", dv)
    beta = jax.nn.sigmoid(q(x) @ q(p["b"]["kernel"]))
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
        q(x) @ q(p["a"]["kernel"]) + p["dt_bias"])
    o = _delta_rule(cfg, q, qq, kk, vv, g, beta)
    gate = (q(x) @ q(p["g"]["kernel"])).reshape(b, t, h, dv)
    o = _rms(o, p["o_norm"], cfg["rms_norm_eps"]) * _silu(gate)
    return q(o.reshape(b, t, h * dv)) @ q(p["o"]["kernel"])


def _block(cfg, q, kind, x, p):
    eps = cfg["rms_norm_eps"]
    mixer = _full_attention if kind == FULL else _linear_attention
    h = x + _rms(mixer(cfg, q, x, p["mixer"]), p["mixer_norm"], eps)
    m = p["mlp"]
    y = _silu(q(h) @ q(m["gate"]["kernel"])) * (q(h) @ q(m["up"]["kernel"]))
    return h + _rms(q(y) @ q(m["down"]["kernel"]), p["mlp_norm"], eps)


def hidden_states(cfg, params, tokens, q=lambda x: x):
    """tokens int (B, T) -> the final norm's output (B, T, hidden_size)."""
    x = params["embed"]["embedding"][tokens]
    for i, kind in enumerate(_layers(cfg)):
        if kind not in (LINEAR, FULL):
            raise ValueError(f"layer type {kind!r}")
        block = _remat(cfg, lambda x, p, kind=kind: _block(cfg, q, kind, x, p))
        x = block(x, params[f"block_{i}"])
    return _rms(x, params["final_norm"], cfg["rms_norm_eps"])


def forward(cfg, variables, tokens, q=lambda x: x):
    """tokens int (B, T) -> (logits (B, T, vocab_size), {})."""
    params = variables["params"]
    return q(hidden_states(cfg, params, tokens, q)) @ q(params["head"]), {}


def loss_fn(cfg, params, batch_stats, batch, q=lambda x: x):
    """Mean next-token cross-entropy over positions 0..T-2 -> (loss, {})."""
    tokens = batch["tokens"]
    x = hidden_states(cfg, params, tokens, q)[:, :-1]

    def row(xs):  # one row's logits at a time: (T - 1, vocab_size)
        x, targets = xs
        logp = jax.nn.log_softmax(q(x) @ q(params["head"]))
        return -jnp.take_along_axis(logp, targets[:, None], axis=1)[:, 0]

    return jnp.mean(_rows(cfg, row, (x, tokens[:, 1:]))), batch_stats


# -- the written counts ------------------------------------------------------

def _matmul_parameters(cfg):
    """Parameters that multiply every token, head aside: the layers'
    kernels (the convolutions' taps, norms and decays are no matmuls; the
    embedding is a lookup)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, dk, dv = _linear_dims(cfg)
    per_kind = {FULL: 4 * d * d,
                LINEAR: d * (2 * h * dk + 2 * h * dv + 2 * h) + h * dv * d}
    return sum(3 * d * f + per_kind[kind] for kind in _layers(cfg))


def delta_rule_flops(cfg, rows: int, tokens: int) -> float:
    """FLOPs of the recurrence's products in one training step, the
    mathematics as written: a token of a head multiplies `S k`, the
    rank-one `written k^T` and `S q` forward (3 x 2 dv dk) and each
    product's two gradients backward (6 x 2 dv dk): 18 dv dk. A chunked
    form or a kernel executes other products; the same work is read
    whatever implements it."""
    h, dk, dv = _linear_dims(cfg)
    return float(_layers(cfg).count(LINEAR) * rows * tokens * h
                 * 18 * dv * dk)


def delta_rule_bytes(cfg, rows: int, tokens: int, itemsize: int) -> float:
    """The least HBM traffic of the same: q, k, v, g, b and o and their
    gradients, each once in the io dtype; no state leaves near memory."""
    h, dk, dv = _linear_dims(cfg)
    return float(_layers(cfg).count(LINEAR) * rows * tokens * h
                 * 2 * (2 * dk + 2 * dv + 2) * itemsize)


def step_flops(cfg, batch_spec, scores: str = "causal") -> float:
    """FLOPs of one training step's mathematics, forward and backward,
    written down: `flops.jaxpr_flops` would count a `scan`'s body once and
    the zeros under a causal mask.

    - every kernel's three products (forward, and the gradient to its
      input and to itself; the first block's input gradient goes on to
      the embedding): 6 x parameters x tokens, the head over the `T - 1`
      positions that have a next token;
    - the scores: a head of a row multiplies `q k^T` and `p v` forward and
      four products backward over the `T (T + 1) / 2` pairs the mask
      leaves (`scores="causal"`): 12 x pairs x head size; `"whole"` counts
      all `T^2`, as a jaxpr of the plain form does;
    - the recurrence: `delta_rule_flops`."""
    rows, tokens = batch_spec["tokens"].shape
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    pairs = {"causal": tokens * (tokens + 1) // 2,
             "whole": tokens * tokens}[scores]
    return float(
        6 * _matmul_parameters(cfg) * rows * tokens
        + 6 * d * cfg["vocab_size"] * rows * (tokens - 1)
        + _layers(cfg).count(FULL) * rows * heads * 12 * pairs * (d // heads)
    ) + delta_rule_flops(cfg, rows, tokens)
