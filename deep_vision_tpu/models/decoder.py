"""What the token decoders share (`models/olmo_hybrid.py`,
`models/solar_open2.py`): the initialisers FLA's delta-rule layers start
from, the recomputation policy of a block, the per-head l2 norm, causal
attention by the path its shape chose, and the counters a traced mixer
bumps.
"""
from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deep_vision_tpu.models.vit import attention_path, flash_attention
from deep_vision_tpu.obs.registry import get_registry
from deep_vision_tpu.ops.gated_delta import INVERSE_NAME

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


def causal_attention(q, k, v):
    """Causal `softmax(q k^T / sqrt(d)) v`, q, k, v of (B, T, H, d) -> (B,
    T, H, d): the streaming flash kernel where ViT's `attention_path`
    chooses it by shape, else the dense expression (its one-block fused
    kernel has no causal mask)."""
    b, t, h, d = q.shape
    path = attention_path(t, h, h * d)
    path = "dense" if path == "fused" else path
    get_registry().counter(
        "attention_sites_total", "Attention sites traced, by the path "
        "their shape chose", labels={"path": path}).inc()
    if path == "streaming":
        return flash_attention(q, k, v, causal=True)
    s = jnp.einsum("bthd,bshd->bhts", q, k) * d ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)),
                  s.astype(jnp.float32), -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", p, v)
