"""What the token decoders share (`models/olmo_hybrid.py`,
`models/solar_open2.py`, `models/laguna.py`): the initialisers FLA's
delta-rule layers start from, the recomputation policy of a block, the
per-head l2 norm, causal attention (whole or in a sliding window) by the
path its shape chose, the counters a traced mixer bumps, and a chip's
share of a routed expert layer (`SparseMoe`).
"""
from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deep_vision_tpu.models.vit import attention_path, flash_attention
from deep_vision_tpu.obs.registry import get_registry
from deep_vision_tpu.ops.gated_delta import INVERSE_NAME
from deep_vision_tpu.ops.pallas.flash_attention import window_key_blocks
from deep_vision_tpu.parallel.moe import bias_update, held_experts

# the windowed kernels' blocks: at a window of 512 a query block of 512
# visits 2 key blocks, both loaded whole (PERF.md section 6 has the bench)
WINDOW_BLOCK_Q = WINDOW_BLOCK_K = 512

INIT = nn.initializers.normal(0.02)
# what a recomputed block keeps from its first forward: every product with
# a kernel (no batch dimension: not the delta rule's, not the scores), and
# the delta rule's inverse triangles, by name
KEPT = jax.checkpoint_policies.save_from_both_policies(
    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    jax.checkpoint_policies.save_only_these_names(INVERSE_NAME))


def dense(features, dtype: Optional[jnp.dtype], name: str,
          use_bias: bool = False):
    return nn.Dense(features, use_bias=use_bias, dtype=dtype,
                    kernel_init=INIT, name=name)


def count_mixer_site(kind: str) -> None:
    # counted while tracing, beside `attention_sites_total{path}`
    get_registry().counter(
        "sequence_mixer_sites_total", "Sequence mixers traced, by kind",
        labels={"kind": kind}).inc()


def conv_init(key, shape, dtype=jnp.float32):
    """torch's `Conv1d` default over a fan-in of the taps: U(-K^-1/2, K^-1/2)."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def a_log_init(key, shape, dtype=jnp.float32, lo=1e-3, hi=16.0):
    """FLA's: `A` uniform in (lo, hi), kept as its logarithm."""
    return jnp.log(jax.random.uniform(key, shape, dtype, lo, hi))


def dt_bias_init(key, shape, dtype=jnp.float32, lo=1e-3, hi=0.1):
    """FLA's (Mamba's): a step `dt` log-uniform in (lo, hi), kept as the
    inverse of softplus, so that `softplus(dt_bias)` starts at `dt`."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (jnp.log(hi) - jnp.log(lo)) + jnp.log(lo))
    return dt + jnp.log(-jnp.expm1(-dt))


def l2_unit(y):
    """`y / |y|` over the last axis (eps 1e-6 inside the root); float32 in,
    float32 out."""
    return y * jax.lax.rsqrt(
        jnp.sum(jnp.square(y), axis=-1, keepdims=True) + 1e-6)


def causal_attention(q, k, v, window: Optional[int] = None):
    """Causal `softmax(q k^T / sqrt(d)) v`, q, k, v of (B, T, H, d) -> (B,
    T, H, d); with a `window` W query t sees keys t - W + 1 .. t. The
    streaming flash kernel where ViT's `attention_path` chooses it by shape
    (with a window, its windowed grids: path "window"), else the dense
    expression (its one-block fused kernel has no causal mask)."""
    b, t, h, d = q.shape
    path = attention_path(t, h, h * d)
    path = "dense" if path == "fused" else path
    if path == "streaming" and window is not None and window < t:
        path = "window"
    count = lambda name, doc, **labels: get_registry().counter(
        name, doc, labels=labels)
    count("attention_sites_total", "Attention sites traced, by the path "
          "their shape chose", path=path).inc()
    if path == "window":
        visited, causal = window_key_blocks(t, WINDOW_BLOCK_Q,
                                            WINDOW_BLOCK_K, window)
        blocks = "Key blocks the windowed forward grids visit, beside " \
            "those a causal grid would"
        count("attention_key_blocks_total", blocks,
              kind="visited").inc(b * h * visited)
        count("attention_key_blocks_total", blocks,
              kind="causal").inc(b * h * causal)
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=WINDOW_BLOCK_Q, block_k=WINDOW_BLOCK_K)
    if path == "streaming":
        return flash_attention(q, k, v, causal=True)
    s = jnp.einsum("bthd,bshd->bhts", q, k) * d ** -0.5
    seen = jnp.tril(jnp.ones((t, t), bool))
    if window is not None:
        seen = seen & ~jnp.tril(seen, -window)
    s = jnp.where(seen, s.astype(jnp.float32), -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", p, v)


class SparseMoe(nn.Module):
    """The held experts' part of a routed layer and the shared expert:
    `parallel/moe.held_experts` over this module's parameters. With a
    `correction_bias` (in `batch_stats`) the layer routes by sigmoid
    (`sigmoid_route`), without one by softmax (`softmax_route`);
    `stand_in` as `held_experts` takes it."""

    router_experts: int
    held: int
    held_offset: int
    top_k: int
    width: int
    shared_width: int
    scaling: float = 1.0
    train: bool = True
    dtype: Optional[jnp.dtype] = None
    correction_bias: bool = True
    stand_in: bool = False

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
        bias = None
        if self.correction_bias:
            bias = self.variable("batch_stats", "router_bias", jnp.zeros,
                                 (self.router_experts,), jnp.float32)
        y, stats = held_experts(
            x.reshape(b * t, d).astype(self.dtype or x.dtype), router,
            None if bias is None else bias.value, experts, shared,
            top_k=self.top_k, held_offset=self.held_offset,
            scaling=self.scaling, stand_in=self.stand_in)
        if bias is not None and self.train and not self.is_initializing():
            bias.value = bias_update(bias.value, stats["load"])
        return y.reshape(b, t, d), stats
