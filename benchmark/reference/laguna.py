"""Plain reference: Laguna's forward pass and next-token loss.

Straightforward `jax.numpy` in float32; the caller sets
`jax.default_matmul_precision("highest")`. After poolside/Laguna-XS.2's
`config.json`, whose keys `cfg` holds under their own names: a decoder of
`num_hidden_layers` blocks, block `l` attending as `layer_types[l]` says
("full_attention" or "sliding_attention") with
`num_attention_heads_per_layer[l]` query heads, its feed-forward as
`mlp_layer_types[l]` says ("dense" or "sparse"); `RMSNorm(x) = x *
rsqrt(mean(x^2) + rms_norm_eps) * scale`; no bias anywhere; `x` is `(B, T,
hidden_size)`.

- block (pre-norm; the config does not state the order): `h = x +
  Attn(RMSNorm(x))`, `y = h + FFN(RMSNorm(h))`; a final `RMSNorm`, then
  the head; embedding and head untied.
- Attn: `q = W_q x` (H heads of `head_dim` d), `k, v = W_k x, W_v x`
  (`num_key_value_heads`); query head `h` reads KV head `h // (H /
  num_key_value_heads)`; no q/k norm (the config names none). q and k
  rotated at positions `0..T-1` by `rope_parameters[layer_types[l]]`,
  written out here from the formulas (NeoX pairing, dim `i` with dim `i +
  r/2` of the `r = d partial_rotary_factor` rotated dims; the others pass
  through): the default form `inv_freq_i = theta^(-2i/r)`; YaRN's (the
  full layers) the same divided by `factor` past a linear ramp between
  `low = floor(r ln(L / (2 pi beta_fast)) / (2 ln theta))` and `high =
  ceil(r ln(L / (2 pi beta_slow)) / (2 ln theta))`, `L` the
  `original_max_position_embeddings`, cos and sin times
  `attention_factor`. Scores `q k^T / sqrt(d)`, causal, and on a sliding
  layer only the `sliding_window` keys `t - W + 1 .. t`; a dense masked
  softmax over every key, a block of `_QUERIES` queries at a time; each
  head's output times `sigmoid(W_g x)_h`, one gate a head (`gating`, read
  as its sibling Laguna-S-2.1's "per-head"); `W_o`.
- FFN "dense": `W_down(silu(W_gate x) * W_up x)` at `intermediate_size`.
  "sparse": `p = softmax(x W_r)` over all `router_experts` (the published
  256; float32); the `num_experts_per_tok` experts of the largest `p`,
  `w_e = p_e / sum_chosen p` (renormalised, as Laguna-S-2.1's
  `norm_topk_prob`) times `moe_routed_scaling_factor`, applied to each
  expert's output (`moe_apply_router_weight_on_input` false); no bias, no
  auxiliary loss; `sum_{e chosen and held} w_e FFN_e(x) + FFN_shared(x)`,
  experts at `moe_intermediate_size`, the shared one at
  `shared_expert_intermediate_size`, ungated. The held experts are
  `held_offset .. + num_experts`: **every held expert runs over every
  token, times its weight or 0**; what the absent experts would add is
  left out, as in the program, or with `stand_in` each absent expert `e`
  has the matrices of held expert `(e - held_offset) mod num_experts`, so
  a held expert's weight is the sum of the weights routed to the experts
  it stands for.
- the loss: the mean over rows and positions `0..T-2` of the next token's
  cross-entropy, over the vocabulary held (`vocab_size`: a slice).

Each block, each query block's scores, each expert and each row's logits
are recomputed in the backward pass (`jax.checkpoint`), which changes no
mathematics; the experts, the rows and the query blocks are loops, so that
the step compiles one body of each. `cfg["reference_remat"]: false` turns
the recomputation off and `"reference_unroll": true` maps with `vmap`, so
that a jaxpr holds every product once.

Imports nothing of the program; the variable tree carries the program's
leaf names. `q` rounds each matmul operand (identity for the reference).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BATCH_COUPLED = False  # every row's loss stands alone
_QUERIES = 256  # queries whose scores are made together


def _layers(cfg):
    """(attention kind, heads, ffn kind) of each layer built."""
    n = cfg["num_hidden_layers"]
    return list(zip(cfg["layer_types"][:n],
                    cfg["num_attention_heads_per_layer"][:n],
                    cfg["mlp_layer_types"][:n]))


def init(cfg, key):
    """Seeded variables: kernels, experts, router, embedding and head N(0,
    0.02), unit norm scales; no `batch_stats`."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    kv, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    n, e = cfg["num_experts"], cfg["router_experts"]
    f, fs, fd = (cfg["moe_intermediate_size"],
                 cfg["shared_expert_intermediate_size"],
                 cfg["intermediate_size"])
    keys = iter(jax.random.split(key, 16 * cfg["num_hidden_layers"] + 2))
    normal = lambda *shape: 0.02 * jax.random.normal(next(keys), shape,
                                                     jnp.float32)
    dense = lambda *shape: {"kernel": normal(*shape)}
    ones = lambda m: {"scale": jnp.ones((m,), jnp.float32)}
    params = {"embed": {"embedding": normal(v, d)}, "final_norm": ones(d),
              "head": normal(d, v)}
    for i, (_, heads, ffn) in enumerate(_layers(cfg)):
        attn = {"q": dense(d, heads * dh), "k": dense(d, kv * dh),
                "v": dense(d, kv * dh), "o": dense(heads * dh, d),
                "gate": dense(d, heads)}
        if ffn == "dense":
            mlp = {"gate": dense(d, fd), "up": dense(d, fd),
                   "down": dense(fd, d)}
        else:
            mlp = {"router": normal(d, e), "gate": normal(n, d, f),
                   "up": normal(n, d, f), "down": normal(n, f, d),
                   "shared_gate": normal(d, fs), "shared_up": normal(d, fs),
                   "shared_down": normal(fs, d)}
        params[f"block_{i}"] = {"attn": attn, "mlp": mlp,
                                "attn_norm": ones(d), "mlp_norm": ones(d)}
    return {"params": params, "batch_stats": {}}


def _rms(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * p["scale"]


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _remat(cfg, fn):
    return jax.checkpoint(fn) if cfg.get("reference_remat", True) else fn


def _map(cfg, fn, xs):
    """`fn` over the leading axis, one at a time and recomputed (or, for a
    jaxpr that holds every product, all at once)."""
    if cfg.get("reference_unroll"):
        return jax.vmap(fn)(xs)
    return lax.map(_remat(cfg, fn), xs)


def inverse_frequencies(rope, head_dim):
    """(inv_freq (r/2,) float64, the factor on cos and sin) of one entry
    of `rope_parameters`."""
    r = int(head_dim * rope.get("partial_rotary_factor", 1))
    theta = rope["rope_theta"]
    inv = 1.0 / theta ** (np.arange(0, r, 2) / r)
    if rope["rope_type"] == "default":
        return inv, 1.0
    assert rope["rope_type"] == "yarn", rope
    length = rope["original_max_position_embeddings"]
    at = lambda turns: r * math.log(length / (2 * math.pi * turns)) / (
        2 * math.log(theta))
    low = max(math.floor(at(rope["beta_fast"])), 0)
    high = min(math.ceil(at(rope["beta_slow"])), r - 1)
    ramp = np.clip((np.arange(r // 2) - low) / (high - low), 0, 1)
    inv = inv / rope["factor"] * ramp + inv * (1 - ramp)
    return inv, rope["attention_factor"]


def _rotate(x, rope):
    """x (B, T, H, d) rotated at positions 0..T-1."""
    t, d = x.shape[1], x.shape[-1]
    inv, factor = inverse_frequencies(rope, d)
    r = 2 * len(inv)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)
    cos = jnp.cos(angle)[:, None, :] * factor
    sin = jnp.sin(angle)[:, None, :] * factor
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., r:]], axis=-1)


def _attn(cfg, q, kind, heads, x, p):
    b, t, _ = x.shape
    kv, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    rope = cfg["rope_parameters"][kind]
    proj = lambda name, h: (q(x) @ q(p[name]["kernel"])).reshape(b, t, h, dh)
    qq = _rotate(proj("q", heads), rope)
    kk = jnp.repeat(_rotate(proj("k", kv), rope), heads // kv, axis=2)
    vv = jnp.repeat(proj("v", kv), heads // kv, axis=2)
    window = cfg["sliding_window"] if kind == "sliding_attention" else t
    blocks = t // _QUERIES if t % _QUERIES == 0 else 1
    size = t // blocks
    heads_first = lambda y: y.transpose(0, 2, 1, 3)  # (B, H, T, d)
    kk, vv = heads_first(kk), heads_first(vv)

    def queries(xs):  # a block of one row's queries against all its keys
        row, start, qb = xs
        s = jnp.einsum("htk,hsk->hts", q(qb), q(kk[row])) * dh ** -0.5
        at = start + jnp.arange(size)[:, None]
        key = jnp.arange(t)[None, :]
        s = jnp.where((key <= at) & (key > at - window), s, -jnp.inf)
        return jnp.einsum("hts,hsk->htk", q(jax.nn.softmax(s, axis=-1)),
                          q(vv[row]))

    rows = jnp.repeat(jnp.arange(b), blocks)
    starts = jnp.tile(jnp.arange(blocks) * size, b)
    qb = heads_first(qq).reshape(b, heads, blocks, size, dh).transpose(
        0, 2, 1, 3, 4).reshape(b * blocks, heads, size, dh)
    o = _map(cfg, queries, (rows, starts, qb)).reshape(
        b, blocks, heads, size, dh).transpose(0, 1, 3, 2, 4).reshape(
        b, t, heads, dh)
    o = o * jax.nn.sigmoid(q(x) @ q(p["gate"]["kernel"]))[..., None]
    return q(o.reshape(b, t, heads * dh)) @ q(p["o"]["kernel"])


def _ffn(q, x, gate, up, down):
    return q(_silu(q(x) @ q(gate)) * (q(x) @ q(up))) @ q(down)


def route(cfg, q, x, router):
    """x (T, D) -> the weight of every expert for every token, zero where
    not chosen: (T, router_experts)."""
    e, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(q(x) @ q(router), axis=-1)
    _, choice = lax.top_k(probs, k)
    weights = probs * jax.nn.one_hot(choice, e, dtype=jnp.float32).sum(1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights * cfg["moe_routed_scaling_factor"]


def _moe(cfg, q, x, p):
    b, t, d = x.shape
    x = x.reshape(b * t, d)
    n, weights = cfg["num_experts"], route(cfg, q, x, p["router"])
    if cfg.get("stand_in"):  # column j: the experts e = offset + j (mod n)
        held = jnp.roll(weights, -cfg["held_offset"], axis=1).reshape(
            b * t, -1, n).sum(axis=1)
    else:
        held = lax.dynamic_slice_in_dim(weights, cfg["held_offset"], n,
                                        axis=1)
    experts = (held.T, p["gate"], p["up"], p["down"])

    def expert(args):  # one held expert over every token
        w, gate, up, down = args
        return w[:, None] * _ffn(q, x, gate, up, down)

    if cfg.get("reference_unroll"):
        out = jnp.sum(jax.vmap(expert)(experts), axis=0)
    else:  # one expert at a time, summed as it goes
        out, _ = lax.scan(lambda acc, e: (acc + _remat(cfg, expert)(e), None),
                          jnp.zeros_like(x), experts)
    out = out + _ffn(q, x, p["shared_gate"], p["shared_up"],
                     p["shared_down"])
    return out.reshape(b, t, d)


def _block(cfg, q, layer, x, p):
    kind, heads, ffn = layer
    eps = cfg["rms_norm_eps"]
    h = x + _attn(cfg, q, kind, heads, _rms(x, p["attn_norm"], eps),
                  p["attn"])
    y = _rms(h, p["mlp_norm"], eps)
    if ffn == "dense":
        m = p["mlp"]
        return h + _ffn(q, y, m["gate"]["kernel"], m["up"]["kernel"],
                        m["down"]["kernel"])
    return h + _moe(cfg, q, y, p["mlp"])


def hidden_states(cfg, params, tokens, q=lambda x: x):
    """tokens int (B, T) -> the final norm's output (B, T, hidden_size)."""
    x = params["embed"]["embedding"][tokens]
    for i, layer in enumerate(_layers(cfg)):
        block = _remat(cfg, lambda x, p, layer=layer: _block(
            cfg, q, layer, x, p))
        x = block(x, params[f"block_{i}"])
    return _rms(x, params["final_norm"], cfg["rms_norm_eps"])


def forward(cfg, variables, tokens, q=lambda x: x):
    """tokens int (B, T) -> logits (B, T, vocab_size)."""
    params = variables["params"]
    return q(hidden_states(cfg, params, tokens, q)) @ q(params["head"])


def loss_fn(cfg, params, batch_stats, batch, q=lambda x: x):
    """Mean next-token cross-entropy over positions 0..T-2 -> (loss,
    batch_stats unchanged)."""
    tokens = batch["tokens"]
    x = hidden_states(cfg, params, tokens, q)

    def row(xs):  # one row's logits at a time: (T - 1, vocab_size)
        x, targets = xs
        logp = jax.nn.log_softmax(q(x) @ q(params["head"]))
        return -jnp.take_along_axis(logp, targets[:, None], axis=1)[:, 0]

    return jnp.mean(_map(cfg, row, (x[:, :-1], tokens[:, 1:]))), batch_stats


# -- the written counts ------------------------------------------------------

def window_pairs(tokens: int, window: int) -> int:
    """(query, key) pairs a sliding window leaves in a row."""
    return sum(min(t + 1, window) for t in range(tokens))


def _scored_pairs(cfg, kind, tokens, scores):
    if scores == "whole":
        return tokens * tokens
    if kind == "sliding_attention":
        return window_pairs(tokens, cfg["sliding_window"])
    return tokens * (tokens + 1) // 2


def window_attention_flops(cfg, rows: int, tokens: int) -> float:
    """FLOPs of the sliding layers' attention products in one training
    step, the mathematics: a head multiplies `q k^T` and `p v` forward and
    four products backward over the pairs its window leaves, 12 x pairs x
    head size (the scores' recomputation in a kernel's backward is not
    counted)."""
    pairs = window_pairs(tokens, cfg["sliding_window"])
    return float(sum(rows * heads * 12 * pairs * cfg["head_dim"]
                     for kind, heads, _ in _layers(cfg)
                     if kind == "sliding_attention"))


def window_attention_bytes(cfg, rows: int, tokens: int,
                           itemsize: int) -> float:
    """The least HBM traffic of the same: q and o at the query heads, k and
    v at the KV heads, and their four gradients, each once in the io
    dtype; no score leaves near memory."""
    kv = cfg["num_key_value_heads"]
    return float(sum(rows * tokens * 4 * (heads + kv) * cfg["head_dim"]
                     * itemsize for kind, heads, _ in _layers(cfg)
                     if kind == "sliding_attention"))


def _sparse_layers(cfg) -> int:
    return sum(ffn == "sparse" for _, _, ffn in _layers(cfg))


def expert_flops(cfg, pairs: float) -> float:
    """FLOPs of the held experts' grouped products in one training step
    for `pairs` (token, expert) pairs over all layers: gate, up and down
    forward (3 x 2 D F) and each product's two gradients: 18 D F."""
    return float(18 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
                 * pairs)


def expert_bytes(cfg, pairs: float, itemsize: int) -> float:
    """The least HBM traffic of the same: every held expert's three
    matrices read forward, read backward and their gradient written (9 D F
    values an expert a layer), and a pair's rows (x and y of D, gate, up and
    their product of F) once forward and twice backward, in the io dtype."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = 9 * d * f * cfg["num_experts"] * _sparse_layers(cfg)
    return float((weights + 3 * pairs * (2 * d + 3 * f)) * itemsize)


def step_flops(cfg, batch_spec, scores: str = "masked") -> float:
    """FLOPs of one training step's mathematics, forward and backward,
    written down: the plain form multiplies every held expert by every
    token and every masked score.

    - every kernel's three products (forward, and the gradient to its
      input and to itself): 6 x parameters x tokens; a held expert's over
      the tokens it is expected to see (`T k n / E` a layer; with
      `stand_in` every pair, `T k`, is computed here); the head over
      the `T - 1` positions that have a next token;
    - the scores: 12 x pairs x head size a head of a row, over the pairs
      the mask leaves (`scores="masked"`: a full layer's causal `T (T + 1)
      / 2`, a sliding layer's `window_pairs`); `"whole"` counts all `T^2`,
      as a jaxpr of the plain form does."""
    rows, tokens = batch_spec["tokens"].shape
    d, dh, kv = (cfg["hidden_size"], cfg["head_dim"],
                 cfg["num_key_value_heads"])
    e, n, k = (cfg["router_experts"], cfg["num_experts"],
               cfg["num_experts_per_tok"])
    f, fs, fd = (cfg["moe_intermediate_size"],
                 cfg["shared_expert_intermediate_size"],
                 cfg["intermediate_size"])
    pairs = k if cfg.get("stand_in") else n * k / e  # a token, a layer
    per_token, scored = 0.0, 0.0
    for kind, heads, ffn in _layers(cfg):
        per_token += 2 * d * heads * dh + 2 * d * kv * dh + d * heads
        per_token += 3 * d * fd if ffn == "dense" else (
            d * e + 3 * d * fs + 3 * d * f * pairs)
        scored += heads * 12 * _scored_pairs(cfg, kind, tokens, scores) * dh
    return float(6 * per_token * rows * tokens
                 + 6 * d * cfg["vocab_size"] * rows * (tokens - 1)
                 + rows * scored)
