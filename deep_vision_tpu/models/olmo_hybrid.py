"""Olmo-Hybrid: a decoder whose sequence mixer is chosen per layer by
`layer_types`, a gated-delta-rule layer ("linear_attention") or causal
softmax attention ("full_attention"), after allenai/Olmo-Hybrid-7B's
published config (three linear layers, then one full, eight times over).

The equations, `x` of `(B, T, D)`, no bias anywhere, `RMSNorm` with a
learned scale and `eps` (`nn/layers.RMSNorm`):

- block, both kinds, the OLMo 2/3 family's norm after the sublayer:
  `h = x + RMSNorm(Mixer(x))`, `y = h + RMSNorm(MLP(h))`,
  `MLP(h) = W_down(silu(W_gate h) * W_up h)`; after the last block a final
  `RMSNorm`, then the head; embedding and head untied.
- full attention: `q, k, v = W_q x, W_k x, W_v x`; `q, k <- RMSNorm(q),
  RMSNorm(k)` over the whole width; heads of `D / H`; causal
  `softmax(q k^T / sqrt(D / H)) v`; `W_o`. No rotary embedding (the
  config's `rope_theta` is null): the order of tokens reaches this layer
  through the linear layers before it and the causal mask.
- linear attention (FLA's `GatedDeltaNet` convention): `q = W_q x`
  (`H x dk`), `k = W_k x` (`H x dk`), `v = W_v x` (`H x dv`), each through
  a causal depthwise convolution of `conv` taps, then `silu`; per head
  `q <- q / |q| * dk^-1/2`, `k <- k / |k|`; `b_t = 2 sigmoid(W_b x)` per
  head (`allow_neg_eigval`: the 2); `g_t = -exp(A_log) * softplus(W_a x +
  dt_bias)` per head; the gated delta rule (`ops/gated_delta.py`); the
  output `W_o(RMSNorm_head(o_t) * silu(W_g x))`, the norm over each head's
  `dv`. The rule works chunk-major: q, k, v cross over (`to_chunks`) as
  their convolutions leave them, `o` crosses back as the gate takes it,
  and the two normalisations, one (token, head) row each, are taken in
  float32 on the chunk side: the same numbers, one narrow pass a tensor.

Every width, the vocabulary and `layer_types` are arguments; the registered
`olmo_hybrid_7b` holds the published ones. A vocabulary below the published
one is a slice of it: the embedding's and the head's rows that one of
several chips holds, ids and logits over the slice.

The model returns `{"hidden", "head"}`, the final norm's output and the
head's kernel, and not logits: `losses/causal_lm.py` multiplies them a block
of tokens at a time, so a step never holds `(B, T, V)` float32 logits and
their gradient (`causal_lm.logits` makes them where a caller wants them).
Each block is recomputed in the backward pass (`remat`) but for what is
dear to make and small to keep (`_KEPT`): the projections' outputs, 0.4 GB
a block at 2 x 2048 tokens in bfloat16, and a delta-rule layer's inverse
triangles `T = (I + A)^-1`, kept by the name `ops/gated_delta.py` gives
them (31.5 MB a layer there, float32: the backward pass reads them and the
second forward does not invert again). So the second forward is the
elementwise ops, the delta rule's products and scan and the attention
kernel, and no matmul over the width.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from deep_vision_tpu.models import register_model
from deep_vision_tpu.models.decoder import (
    INIT as _INIT,
    KEPT as _KEPT,
    a_log_init,
    causal_attention,
    conv_init,
    count_mixer_site,
    dense as _dense,
    dt_bias_init,
    l2_unit,
)
from deep_vision_tpu.nn.layers import RMSNorm, SwiGLU
from deep_vision_tpu.ops.gated_delta import (
    CHUNK,
    from_chunks,
    gated_delta_chunks,
    short_conv,
    to_chunks,
)

LINEAR, FULL = "linear_attention", "full_attention"


class FullAttention(nn.Module):
    num_heads: int
    eps: float = 1e-6
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        h = self.num_heads
        assert d % h == 0, f"dim {d} not divisible by {h} heads"
        count_mixer_site("full")
        q = RMSNorm(self.eps, name="q_norm")(_dense(d, self.dtype, "q")(x))
        k = RMSNorm(self.eps, name="k_norm")(_dense(d, self.dtype, "k")(x))
        v = _dense(d, self.dtype, "v")(x)
        o = causal_attention(*(y.reshape(b, t, h, d // h) for y in (q, k, v)))
        return _dense(d, self.dtype, "o")(o.reshape(b, t, d))


class GatedDeltaNet(nn.Module):
    num_heads: int
    key_dim: int
    value_dim: int
    conv: int = 4
    allow_neg_eigval: bool = True
    eps: float = 1e-6
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        h, dk, dv = self.num_heads, self.key_dim, self.value_dim
        count_mixer_site("linear")

        def mixed(name, width):
            y = _dense(h * width, self.dtype, name)(x)
            kernel = self.param(name + "_conv", conv_init,
                                (self.conv, h * width), jnp.float32)
            return nn.silu(short_conv(y, kernel)).reshape(b, t, h, width)

        # a length that chunks do not divide (a tiny test) is one chunk
        chunked = functools.partial(to_chunks,
                                    chunk=CHUNK if t % CHUNK == 0 else t)
        # each tensor crosses to chunk-major once, in the dtype it is
        # stored in; the float32 row math stands on the chunk side
        q = l2_unit(chunked(mixed("q", dk)).astype(jnp.float32)) * dk ** -0.5
        k = l2_unit(chunked(mixed("k", dk)).astype(jnp.float32))
        v = chunked(mixed("v", dv))
        f32 = functools.partial(_dense, dtype=jnp.float32)
        beta = jax.nn.sigmoid(f32(h, name="b")(x))
        if self.allow_neg_eigval:
            beta = 2.0 * beta
        a_log = self.param("A_log", a_log_init, (h,), jnp.float32)
        dt_bias = self.param("dt_bias", dt_bias_init, (h,), jnp.float32)
        g = -jnp.exp(a_log) * jax.nn.softplus(f32(h, name="a")(x) + dt_bias)
        o = gated_delta_chunks(q, k, v, chunked(g), chunked(beta),
                               mm_dtype=self.dtype or x.dtype)
        gate = _dense(h * dv, self.dtype, "g")(x).reshape(b, t, h, dv)
        # and back once, rounded: the norm is over each (token, head) row
        o = from_chunks(RMSNorm(self.eps, name="o_norm")(o).astype(
            gate.dtype)) * nn.silu(gate)
        return _dense(x.shape[-1], self.dtype, "o")(o.reshape(b, t, h * dv))


class HybridBlock(nn.Module):
    kind: str
    num_heads: int
    intermediate: int
    linear_heads: int
    linear_key_dim: int
    linear_value_dim: int
    conv: int
    allow_neg_eigval: bool
    eps: float
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        if self.kind == FULL:
            mixer = FullAttention(self.num_heads, self.eps, self.dtype,
                                  name="mixer")
        elif self.kind == LINEAR:
            mixer = GatedDeltaNet(
                self.linear_heads, self.linear_key_dim, self.linear_value_dim,
                self.conv, self.allow_neg_eigval, self.eps, self.dtype,
                name="mixer")
        else:
            raise ValueError(f"layer type {self.kind!r}: have {LINEAR!r} "
                             f"and {FULL!r}")
        h = x + RMSNorm(self.eps, name="mixer_norm")(mixer(x))
        mlp = SwiGLU(self.intermediate, self.dtype, _INIT, name="mlp")
        return h + RMSNorm(self.eps, name="mlp_norm")(mlp(h))


class OlmoHybrid(nn.Module):
    """tokens int32 (B, T) -> {"hidden": (B, T, D), "head": (D, V)}."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    layer_types: Sequence[str]
    linear_num_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        dt = self.dtype or jnp.float32
        x = nn.Embed(self.vocab_size, self.hidden_size, embedding_init=_INIT,
                     name="embed")(tokens).astype(dt)
        block_cls = nn.remat(HybridBlock, policy=_KEPT)
        for i, kind in enumerate(self.layer_types):
            x = block_cls(
                kind, self.num_attention_heads, self.intermediate_size,
                self.linear_num_heads, self.linear_key_head_dim,
                self.linear_value_head_dim, self.linear_conv_kernel_dim,
                self.linear_allow_neg_eigval, self.rms_norm_eps, self.dtype,
                name=f"block_{i}")(x)
        head = self.param("head", _INIT, (self.hidden_size, self.vocab_size),
                          jnp.float32)
        return {"hidden": RMSNorm(self.rms_norm_eps, name="final_norm")(x),
                "head": head}


# allenai/Olmo-Hybrid-7B, config.json
_PERIOD = (LINEAR, LINEAR, LINEAR, FULL)


@register_model("olmo_hybrid_7b")
def olmo_hybrid_7b(dtype=None, vocab_size: int = 100352,
                   num_hidden_layers: int = 32, layer_types=None,
                   hidden_size: int = 3840, intermediate_size: int = 11008,
                   num_attention_heads: int = 30, linear_num_heads: int = 30,
                   linear_key_head_dim: int = 96,
                   linear_value_head_dim: int = 192, **_):
    """The published widths; `num_hidden_layers` keeps the first layers of
    the pattern, `vocab_size` the slice of the vocabulary held here."""
    layer_types = tuple(layer_types or _PERIOD * 8)[:num_hidden_layers]
    return OlmoHybrid(
        vocab_size=vocab_size, hidden_size=hidden_size,
        intermediate_size=intermediate_size,
        num_attention_heads=num_attention_heads, layer_types=layer_types,
        linear_num_heads=linear_num_heads,
        linear_key_head_dim=linear_key_head_dim,
        linear_value_head_dim=linear_value_head_dim, dtype=dtype)
