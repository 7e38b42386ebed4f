"""Laguna: a decoder of sliding-window attention beside full attention, each
layer's feed-forward a softmax-routed mixture of experts with a shared one
after a leading dense layer, after poolside/Laguna-XS.2's published config
(`layer_types`: one full-attention layer, then three sliding ones, ten
times over; `mlp_layer_types`: dense, then sparse; 256 experts, 8 a token,
1 shared).

The equations, `x` of `(B, T, D)`, `RMSNorm` with a learned scale and
`eps` (`nn/layers.RMSNorm`), no bias anywhere:

- block, pre-norm: `h = x + Attn(RMSNorm(x))`, `y = h + FFN(RMSNorm(h))`;
  after the last block a final `RMSNorm`, then the head; embedding and
  head untied.
- Attn: `q = W_q x` (`num_attention_heads_per_layer[l]` heads of
  `head_dim`), `k, v = W_k x, W_v x` (`num_key_value_heads`); query head
  `h` reads KV head `h // (H / H_kv)` (K and V repeated to the query heads
  by index); no q/k norm. q and k rotated (`ops/rotary.py`, scope `rope`)
  at positions 0..T-1 by the layer type's entry of `rope_parameters`: the
  sliding layers' default form over all of a head, the full layers' YaRN
  over its first `partial_rotary_factor` part, the rest passed through.
  `softmax(q k^T / sqrt(d)) v`, causal, and on a sliding layer over the
  `sliding_window` keys `t - W + 1 .. t` (`decoder.causal_attention`,
  scopes `attention/window` and `attention/full`); each head's output
  times `sigmoid(W_g x)_h`, one gate a head (`gating`; scope
  `attention/gate`); then `W_o`.
- FFN: a "dense" layer `W_down(silu(W_gate x) * W_up x)` at
  `intermediate_size`; a "sparse" one (`parallel/moe.held_experts`, the
  softmax route): `p = softmax(x W_r)` over all `router_experts`, float32;
  the `num_experts_per_tok` experts of the largest `p`, their weights the
  chosen probabilities over their sum times `moe_routed_scaling_factor`,
  applied to each expert's output; `sum_{e chosen, held} w_e FFN_e(x) +
  FFN_shared(x)`, experts at `moe_intermediate_size`, the shared one at
  `shared_expert_intermediate_size`, ungated.

Every width, the layer count, `rope_parameters`, the routed scaling and
the experts held (`num_experts` from `held_offset`; the router keeps
`router_experts`) are arguments; the registered `laguna_xs2` holds the
published ones. A chip's share holds fewer experts (what the others would
add is left out, or with `stand_in` computed by the held expert in each
one's place, `parallel/moe.held_experts`) and a slice of the vocabulary
(ids and logits over the slice).

The model returns `{"hidden", "head", "report"}` as `models/solar_open2.py`
does: the report counts the pairs the held experts computed, the largest
held expert's load and the expert layers' buffer tiles (`Trainer` folds
them into `moe_*_total`). Each block is recomputed in the backward pass but
for the products with a kernel (`models/decoder.KEPT`).
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
    SparseMoe,
    causal_attention,
    dense,
)
from deep_vision_tpu.nn.layers import RMSNorm, SwiGLU
from deep_vision_tpu.obs.registry import COUNT_PREFIX, get_registry
from deep_vision_tpu.ops.rotary import apply_rotary, rotary, tables

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"

# the published `rope_parameters`, by layer type (`ops/rotary.rotary`)
ROPE_PARAMETERS = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
           "original_max_position_embeddings": 4096, "beta_slow": 1,
           "beta_fast": 64, "attention_factor": 1.4158883083359672,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000,
              "partial_rotary_factor": 1}}


class LagunaAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    kind: str
    window: int
    rope: dict
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        h, kv, dh = self.num_heads, self.num_kv_heads, self.head_dim
        assert h % kv == 0, f"{h} query heads over {kv} KV heads"
        q = dense(h * dh, self.dtype, "q")(x).reshape(b, t, h, dh)
        k, v = (dense(kv * dh, self.dtype, name)(x).reshape(b, t, kv, dh)
                for name in ("k", "v"))
        get_registry().counter(
            "rotary_sites_total", "Rotary embeddings traced, by form",
            labels={"kind": self.rope["rope_type"]}).inc()
        with jax.named_scope("rope"):
            cos, sin = tables(rotary(self.rope, dh), jnp.arange(t))
            q, k = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
        # query head i reads KV head i // (h / kv)
        k, v = (jnp.repeat(y, h // kv, axis=2) for y in (k, v))
        sliding = self.kind == SLIDING
        with jax.named_scope("attention/window" if sliding
                             else "attention/full"):
            o = causal_attention(q, k, v,
                                 window=self.window if sliding else None)
        gate = dense(h, self.dtype, "gate")(x)
        with jax.named_scope("attention/gate"):
            o = o * jax.nn.sigmoid(gate.astype(jnp.float32))[
                ..., None].astype(o.dtype)
        return dense(d, self.dtype, "o")(o.reshape(b, t, h * dh))


class LagunaBlock(nn.Module):
    kind: str
    mlp: str
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int
    rope: dict
    intermediate_size: int
    router_experts: int
    held: int
    held_offset: int
    top_k: int
    width: int
    shared_width: int
    scaling: float
    stand_in: bool
    eps: float
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        if self.kind not in (FULL, SLIDING) or self.mlp not in (DENSE,
                                                                SPARSE):
            raise ValueError(f"layer ({self.kind!r}, {self.mlp!r}): have "
                             f"{FULL!r}, {SLIDING!r} and {DENSE!r}, "
                             f"{SPARSE!r}")
        attn = LagunaAttention(self.num_heads, self.num_kv_heads,
                               self.head_dim, self.kind, self.window,
                               self.rope, self.dtype, name="attn")
        h = x + attn(RMSNorm(self.eps, name="attn_norm")(x))
        y = RMSNorm(self.eps, name="mlp_norm")(h)
        zero = jnp.zeros((), jnp.int32)
        counts = {"pairs": zero, "load_max": zero, "live_tiles": zero,
                  "buffer_tiles": zero}
        if self.mlp == DENSE:
            y = SwiGLU(self.intermediate_size, self.dtype, INIT,
                       name="mlp")(y)
        else:
            y, stats = SparseMoe(
                self.router_experts, self.held, self.held_offset, self.top_k,
                self.width, self.shared_width, self.scaling, True,
                self.dtype, correction_bias=False, stand_in=self.stand_in,
                name="mlp")(y)
            counts = {name: stats[name] for name in counts}
        return h + y, counts


class Laguna(nn.Module):
    """tokens int32 (B, T) -> {"hidden": (B, T, D), "head": (D, V),
    "report": {"count/moe_routed_pairs", "count/moe_held_load_max",
    "count/moe_live_tiles", "count/moe_buffer_tiles"}}."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    layer_types: Sequence[str]
    mlp_layer_types: Sequence[str]
    num_attention_heads_per_layer: Sequence[int]
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_experts: int
    router_experts: int
    num_experts_per_tok: int
    sliding_window: int
    rope_parameters: dict
    held_offset: int = 0
    moe_routed_scaling_factor: float = 1.0
    stand_in: bool = False
    rms_norm_eps: float = 1e-6
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        dt = self.dtype or jnp.float32
        x = nn.Embed(self.vocab_size, self.hidden_size, embedding_init=INIT,
                     name="embed")(tokens).astype(dt)
        block_cls = nn.remat(LagunaBlock, policy=KEPT)
        counts = []
        for i in range(self.num_hidden_layers):
            x, layer_counts = block_cls(
                self.layer_types[i], self.mlp_layer_types[i],
                self.num_attention_heads_per_layer[i],
                self.num_key_value_heads, self.head_dim,
                self.sliding_window,
                dict(self.rope_parameters[self.layer_types[i]]),
                self.intermediate_size, self.router_experts,
                self.num_experts, self.held_offset, self.num_experts_per_tok,
                self.moe_intermediate_size,
                self.shared_expert_intermediate_size,
                self.moe_routed_scaling_factor, self.stand_in,
                self.rms_norm_eps, self.dtype, name=f"block_{i}")(x)
            counts.append(layer_counts)
        total = lambda name: sum(c[name] for c in counts)
        head = self.param("head", INIT, (self.hidden_size, self.vocab_size),
                          jnp.float32)
        return {"hidden": RMSNorm(self.rms_norm_eps, name="final_norm")(x),
                "head": head,
                "report": {
                    COUNT_PREFIX + "moe_routed_pairs": total("pairs"),
                    COUNT_PREFIX + "moe_held_load_max": functools.reduce(
                        jnp.maximum, [c["load_max"] for c in counts]),
                    COUNT_PREFIX + "moe_live_tiles": total("live_tiles"),
                    COUNT_PREFIX + "moe_buffer_tiles": total("buffer_tiles")}}


def _period(first, rest, n, every=4):
    return tuple(first if i % every == 0 else rest for i in range(n))


@register_model("laguna_xs2")
def laguna_xs2(dtype=None, vocab_size: int = 100352,
               num_hidden_layers: int = 40, hidden_size: int = 2048,
               num_key_value_heads: int = 8, head_dim: int = 128,
               intermediate_size: int = 8192,
               moe_intermediate_size: int = 512,
               shared_expert_intermediate_size: int = 512,
               num_experts: int = 256, router_experts: int = 256,
               num_experts_per_tok: int = 8, sliding_window: int = 512,
               moe_routed_scaling_factor: float = 2.5,
               rope_parameters=None, held_offset: int = 0,
               stand_in: bool = False, layer_types=None,
               mlp_layer_types=None, num_attention_heads_per_layer=None):
    """The published widths and counts, under the config's names. A chip's
    share passes the experts it holds (`num_experts` from `held_offset`;
    the router keeps `router_experts`; `stand_in`: the experts held
    elsewhere computed by the held ones, `parallel/moe.held_experts`), the
    first `num_hidden_layers` and the slice of the vocabulary held here."""
    return Laguna(
        vocab_size=vocab_size, hidden_size=hidden_size,
        num_hidden_layers=num_hidden_layers,
        layer_types=tuple(layer_types or _period(FULL, SLIDING, 40)),
        mlp_layer_types=tuple(mlp_layer_types
                              or (DENSE,) + (SPARSE,) * 39),
        num_attention_heads_per_layer=tuple(
            num_attention_heads_per_layer or _period(48, 64, 40)),
        num_key_value_heads=num_key_value_heads, head_dim=head_dim,
        intermediate_size=intermediate_size,
        moe_intermediate_size=moe_intermediate_size,
        shared_expert_intermediate_size=shared_expert_intermediate_size,
        num_experts=num_experts, router_experts=router_experts,
        num_experts_per_tok=num_experts_per_tok,
        sliding_window=sliding_window,
        rope_parameters={kind: dict((rope_parameters or ROPE_PARAMETERS)[
            kind]) for kind in (FULL, SLIDING)},
        held_offset=held_offset,
        moe_routed_scaling_factor=moe_routed_scaling_factor,
        stand_in=stand_in, rms_norm_eps=1e-6, dtype=dtype)
