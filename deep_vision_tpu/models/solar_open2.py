"""Solar-Open2: a decoder of Kimi delta attention beside gated grouped-query
attention without positions, every layer's feed-forward a sigmoid-routed
mixture of experts with a shared one, after upstage/Solar-Open2-250B's
published config (`gqa_layers` 0, 4, ..., 44: one GQA layer, then three
KDA layers, twelve times over; 320 experts, 8 a token, 1 shared).

The equations, `x` of `(B, T, D)`, `RMSNorm` with a learned scale and `eps`
(`nn/layers.RMSNorm`), no bias unless stated:

- block, pre-norm (the Glm4Moe / Kimi family's order): `h = x +
  Mixer(RMSNorm(x))`, `y = h + MoE(RMSNorm(h))`; after the last block a
  final `RMSNorm`, then the head; embedding and head untied.
- GQA (`gqa_layers`): `q = W_q x` (H heads of `head_dim`), `k, v = W_k x,
  W_v x` (H_kv heads); query head `h` reads KV head `h // (H / H_kv)`
  (K and V repeated to the query heads by index); no rotary embedding
  (`use_rope` false), no q/k norm; causal `softmax(q k^T / sqrt(d)) v`,
  gated by `sigmoid(W_gate x)` over each head's `d` (`use_gqa_gate`: the
  query-dependent gate after attention, arXiv:2505.06708); then `W_o`.
- KDA, the other layers (Kimi Linear's Kimi Delta Attention,
  arXiv:2510.26692, as FLA's `KimiDeltaAttention`): `q, k, v` each `W x`
  (H heads of `head_dim`) through a causal depthwise convolution of 4 taps,
  then `silu`; per head `q <- q / |q| d^-1/2`, `k <- k / |k|`; `b_t = 2
  sigmoid(W_b x)` (`kda_allow_neg_eigval`: the 2); a decay per key channel
  `g_t = -exp(A_log_h) softplus(W_f2 W_f1 x + dt_bias)` in R^{d}, the
  projection low-rank through the head size (`kda_use_full_proj` false),
  `A_log` per head, `dt_bias` per channel; the state `S` (d x d), `S_0 =
  0`: `S_t = (I - b_t k_t k_t^T) Diag(e^{g_t}) S_{t-1} + b_t k_t v_t^T`,
  `o_t = S_t^T q_t` (`ops/gated_delta.py`, scope `kda`); the output
  `W_o(RMSNorm_head(o_t) * sigmoid(W_g2 W_g1 x + b_g))`, `W_g2` with a
  bias, the norm over each head's `d` with one scale of `d`. The rule works
  chunk-major, q, k, v crossing as their convolutions leave them; the
  decay's rank-128 input crosses, and its `W_f2` and the float32 row math
  stand on the chunk side.
- MoE, every layer (`parallel/moe.held_experts`): `s = sigmoid(x W_r)`
  over all `router_experts`, float32; the `num_experts_per_tok` experts of
  the largest `s + b`, `b` a correction bias that is not trained (in
  `batch_stats`, zero at the start, moved after each step by 1e-3 sign(mean
  load - load): DeepSeek-V3's rule); the weights the chosen scores over
  their sum times `routed_scaling_factor`; `sum_{e chosen, held} w_e
  FFN_e(x) + FFN_shared(x)`, `FFN(x) = W_down(silu(W_gate x) * W_up x)`.

Every width, the layer count, `gqa_layers`, the heads held, the experts
held (`n_routed_experts`, from `held_offset`) and the router's count are
arguments; the registered `solar_open2_250b` holds the published ones. A
chip's share holds fewer heads (each KV head still serving its group of
query heads), fewer experts (what the others would add is left out) and a
slice of the vocabulary (ids and logits over the slice).

The model returns `{"hidden", "head", "report"}`: the final norm's output
and the head's kernel for `losses/causal_lm.py`, and the step's report of
its experts, the (token, choice) pairs the held experts computed over all
layers and the largest held expert's load in any layer, named as counts
(`obs.registry.COUNT_PREFIX`: `Trainer` folds them into
`moe_routed_pairs_total`, `moe_held_load_max_total`). Each block
is recomputed in the backward pass but for the products with a kernel and
the delta rule's inverse triangles (`models/decoder.KEPT`).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from deep_vision_tpu.models import register_model
from deep_vision_tpu.models.decoder import (
    INIT,
    KEPT,
    a_log_init,
    causal_attention,
    conv_init,
    count_mixer_site,
    dense,
    dt_bias_init,
    l2_unit,
)
from deep_vision_tpu.nn.layers import RMSNorm
from deep_vision_tpu.obs.registry import COUNT_PREFIX
from deep_vision_tpu.ops.gated_delta import (
    CHUNK,
    from_chunks,
    gated_delta_chunks,
    short_conv,
    to_chunks,
)
from deep_vision_tpu.parallel.moe import bias_update, held_experts

GQA, KDA = "gqa", "kda"


class GroupedQueryAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    gate: bool = True
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        h, kv, dh = self.num_heads, self.num_kv_heads, self.head_dim
        assert h % kv == 0, f"{h} query heads over {kv} KV heads"
        count_mixer_site("full")
        q = dense(h * dh, self.dtype, "q")(x).reshape(b, t, h, dh)
        k, v = (dense(kv * dh, self.dtype, name)(x).reshape(b, t, kv, dh)
                for name in ("k", "v"))
        # query head i reads KV head i // (h / kv)
        k, v = (jnp.repeat(y, h // kv, axis=2) for y in (k, v))
        o = causal_attention(q, k, v)
        if self.gate:
            o = o * jax.nn.sigmoid(
                dense(h * dh, self.dtype, "gate")(x).reshape(b, t, h, dh))
        return dense(d, self.dtype, "o")(o.reshape(b, t, h * dh))


class KimiDeltaAttention(nn.Module):
    num_heads: int
    head_dim: int
    conv: int = 4
    allow_neg_eigval: bool = True
    eps: float = 1e-5
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        h, d = self.num_heads, self.head_dim
        count_mixer_site(KDA)

        def mixed(name):
            y = dense(h * d, self.dtype, name)(x)
            kernel = self.param(name + "_conv", conv_init, (self.conv, h * d),
                                jnp.float32)
            return nn.silu(short_conv(y, kernel)).reshape(b, t, h, d)

        # a length that chunks do not divide (a tiny test) is one chunk
        chunked = functools.partial(to_chunks,
                                    chunk=CHUNK if t % CHUNK == 0 else t)
        q = l2_unit(chunked(mixed("q")).astype(jnp.float32)) * d ** -0.5
        k = l2_unit(chunked(mixed("k")).astype(jnp.float32))
        v = chunked(mixed("v"))
        f32 = functools.partial(dense, dtype=jnp.float32)
        beta = jax.nn.sigmoid(f32(h, name="b")(x))
        if self.allow_neg_eigval:
            beta = 2.0 * beta
        # the decay per channel: its rank-d input crosses to the chunk side,
        # where the widening product and the float32 row math stand
        low = chunked(f32(d, name="f_a")(x)[:, :, None, :])[:, :, 0]
        pre = f32(h * d, name="f_b")(low)  # (N, B, C, H d)
        pre = jnp.moveaxis(pre.reshape(*pre.shape[:-1], h, d), -2, 2)
        a_log = self.param("A_log", functools.partial(a_log_init, lo=1.0),
                           (h,), jnp.float32)
        dt_bias = self.param("dt_bias", dt_bias_init, (h * d,), jnp.float32)
        g = -jnp.exp(a_log)[:, None, None] * jax.nn.softplus(
            pre + dt_bias.reshape(h, 1, d))  # (N, B, H, C, d)
        o = gated_delta_chunks(q, k, v, g, chunked(beta),
                               mm_dtype=self.dtype or x.dtype)
        gate = dense(h * d, self.dtype, "g_b", use_bias=True)(
            dense(d, self.dtype, "g_a")(x)).reshape(b, t, h, d)
        o = from_chunks(RMSNorm(self.eps, name="o_norm")(o).astype(
            gate.dtype)) * jax.nn.sigmoid(gate)
        return dense(x.shape[-1], self.dtype, "o")(o.reshape(b, t, h * d))


class SparseMoe(nn.Module):
    """The held experts' part of a sigmoid-routed layer and the shared
    expert: `parallel/moe.held_experts` over this module's parameters, the
    router's correction bias in `batch_stats`."""

    router_experts: int
    held: int
    held_offset: int
    top_k: int
    width: int
    shared_width: int
    scaling: float = 1.0
    train: bool = True
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        n, f = self.held, self.width
        param = lambda name, *shape: self.param(name, INIT, shape,
                                                jnp.float32)
        router = param("router", d, self.router_experts)
        experts = {"gate": param("gate", n, d, f), "up": param("up", n, d, f),
                   "down": param("down", n, f, d)}
        shared = None
        if self.shared_width:
            shared = {"gate": param("shared_gate", d, self.shared_width),
                      "up": param("shared_up", d, self.shared_width),
                      "down": param("shared_down", self.shared_width, d)}
        bias = self.variable("batch_stats", "router_bias", jnp.zeros,
                             (self.router_experts,), jnp.float32)
        y, stats = held_experts(
            x.reshape(b * t, d).astype(self.dtype or x.dtype), router,
            bias.value, experts, shared, top_k=self.top_k,
            held_offset=self.held_offset, scaling=self.scaling)
        if self.train and not self.is_initializing():
            bias.value = bias_update(bias.value, stats["load"])
        return y.reshape(b, t, d), stats


class SolarBlock(nn.Module):
    kind: str
    num_heads: int
    num_kv_heads: int
    head_dim: int
    gqa_gate: bool
    linear_heads: int
    linear_head_dim: int
    conv: int
    allow_neg_eigval: bool
    router_experts: int
    held: int
    held_offset: int
    top_k: int
    width: int
    shared_width: int
    scaling: float
    eps: float
    train: bool = True
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        if self.kind == GQA:
            mixer = GroupedQueryAttention(
                self.num_heads, self.num_kv_heads, self.head_dim,
                self.gqa_gate, self.dtype, name="mixer")
        elif self.kind == KDA:
            mixer = KimiDeltaAttention(
                self.linear_heads, self.linear_head_dim, self.conv,
                self.allow_neg_eigval, self.eps, self.dtype, name="mixer")
        else:
            raise ValueError(f"layer kind {self.kind!r}: have {GQA!r} and "
                             f"{KDA!r}")
        h = x + mixer(RMSNorm(self.eps, name="mixer_norm")(x))
        moe = SparseMoe(self.router_experts, self.held, self.held_offset,
                        self.top_k, self.width, self.shared_width,
                        self.scaling, self.train, self.dtype, name="moe")
        y, stats = moe(RMSNorm(self.eps, name="moe_norm")(h))
        return h + y, (stats["pairs"], stats["load_max"])


class SolarOpen2(nn.Module):
    """tokens int32 (B, T) -> {"hidden": (B, T, D), "head": (D, V),
    "report": {"count/moe_routed_pairs", "count/moe_held_load_max"}}."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    gqa_layers: Sequence[int]
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    linear_num_heads: int
    linear_head_dim: int
    moe_intermediate_size: int
    n_routed_experts: int
    router_experts: int
    num_experts_per_tok: int
    n_shared_experts: int = 1
    held_offset: int = 0
    routed_scaling_factor: float = 1.0
    short_conv_kernel_size: int = 4
    kda_allow_neg_eigval: bool = True
    use_gqa_gate: bool = True
    rms_norm_eps: float = 1e-5
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        dt = self.dtype or jnp.float32
        x = nn.Embed(self.vocab_size, self.hidden_size, embedding_init=INIT,
                     name="embed")(tokens).astype(dt)
        block_cls = nn.remat(SolarBlock, policy=KEPT)
        pairs, load_max = jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)
        for i in range(self.num_hidden_layers):
            x, (p, m) = block_cls(
                GQA if i in self.gqa_layers else KDA,
                self.num_attention_heads, self.num_key_value_heads,
                self.head_dim, self.use_gqa_gate, self.linear_num_heads,
                self.linear_head_dim, self.short_conv_kernel_size,
                self.kda_allow_neg_eigval, self.router_experts,
                self.n_routed_experts, self.held_offset,
                self.num_experts_per_tok, self.moe_intermediate_size,
                self.n_shared_experts * self.moe_intermediate_size,
                self.routed_scaling_factor, self.rms_norm_eps, train,
                self.dtype, name=f"block_{i}")(x)
            pairs, load_max = pairs + p, jnp.maximum(load_max, m)
        head = self.param("head", INIT, (self.hidden_size, self.vocab_size),
                          jnp.float32)
        return {"hidden": RMSNorm(self.rms_norm_eps, name="final_norm")(x),
                "head": head,
                "report": {COUNT_PREFIX + "moe_routed_pairs": pairs,
                           COUNT_PREFIX + "moe_held_load_max": load_max}}


@register_model("solar_open2_250b")
def solar_open2_250b(dtype=None, vocab_size: int = 196608,
                     num_hidden_layers: int = 48, gqa_layers=None,
                     hidden_size: int = 4096, num_attention_heads: int = 64,
                     num_key_value_heads: int = 8, head_dim: int = 128,
                     linear_num_heads: int = 64, linear_head_dim: int = 128,
                     moe_intermediate_size: int = 1280,
                     n_routed_experts: int = 320, router_experts: int = 320,
                     num_experts_per_tok: int = 8, n_shared_experts: int = 1,
                     held_offset: int = 0, **_):
    """The published widths and counts. A chip's share passes the heads
    it holds, the experts it holds (`n_routed_experts` from
    `held_offset`; the router keeps `router_experts`), the first
    `num_hidden_layers` and the slice of the vocabulary held here."""
    return SolarOpen2(
        vocab_size=vocab_size, hidden_size=hidden_size,
        num_hidden_layers=num_hidden_layers,
        gqa_layers=tuple(range(0, 48, 4) if gqa_layers is None
                         else gqa_layers),
        num_attention_heads=num_attention_heads,
        num_key_value_heads=num_key_value_heads, head_dim=head_dim,
        linear_num_heads=linear_num_heads, linear_head_dim=linear_head_dim,
        moe_intermediate_size=moe_intermediate_size,
        n_routed_experts=n_routed_experts, router_experts=router_experts,
        num_experts_per_tok=num_experts_per_tok,
        n_shared_experts=n_shared_experts, held_offset=held_offset,
        dtype=dtype)
