"""Vision Transformer (+ V-MoE variant): the framework's attention flagship.

Net-new beyond the reference (its zoo is all-CNN, SURVEY.md §2.9): an
attention-based image classifier is what the framework's long-context and
expert-parallel machinery exists for, so the zoo ships one. Architecture
follows ViT (Dosovitskiy 2020) with the TPU-friendly choices:

- patchify as a single stride-P conv (one big MXU matmul, no gather);
- token global-average pooling instead of a class token (keeps the sequence
  length a power-of-two-ish multiple of 8/128 tiling at common resolutions
  and sidesteps concat-of-one ragged shapes);
- attention routes by shape (`attention_path`): a sequence that fits one
  block (224px: 196 tokens) runs the single-block Pallas kernel in the
  projections' own layouts, a long one the streaming flash kernel with
  O(T) memory (`ops/pallas/flash_attention.py`), anything else and every
  platform without compiled Pallas the exact dense einsum;
- pre-norm blocks, GELU MLP, bf16-friendly: LayerNorm statistics in f32,
  params f32, activations in the module dtype.

The V-MoE variant (Riquelme 2021) swaps every other MLP for a top-1
(Switch) mixture-of-experts whose expert params are STACKED on a leading
expert axis — exactly the layout `parallel.moe.expert_param_sharding`
shards for expert-parallel training and `parallel.moe.moe_ffn` consumes
under shard_map. Inside the module the routing runs the dense einsum
formulation (`moe_ffn_dense` semantics, no capacity drops: exact, and the
right thing on a single chip); the router's gates feed the Switch
load-balancing aux loss, returned as an aux output in train mode like
Inception V1's aux heads (losses/classification.py handles the plumbing).
"""
from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deep_vision_tpu.core.backend import get_backend
from deep_vision_tpu.models import register_model
from deep_vision_tpu.obs.registry import get_registry
# the flash routing floor lives with the kernel (shared by this backbone
# and parallel/ring_attention.py); re-exported here for the historical
# import path (tests, train_cli)
from deep_vision_tpu.ops.pallas.flash_attention import (  # noqa: F401
    FLASH_MIN_TOKENS,
    flash_attention,
    flash_min_tokens,
    fused_attention,
    fused_attention_fits,
)
from deep_vision_tpu.ops.pallas.partition import batch_parallel_only
from deep_vision_tpu.parallel.moe import load_balancing_loss


def attention_path(t: int, num_heads: int, dim: int) -> str:
    """Which attention a site of T tokens runs, from its shape alone: the
    whole sequence in one block -> "fused" (unless the context mesh splits
    the heads over a second axis: XLA partitions the dense expression by
    heads, a kernel would gather them); long and a multiple of the
    streaming kernel's 512 x 1024 blocks (t % 128 alone would admit the
    1280- and 1536-token inputs it rejects) -> "streaming"; anything else,
    and every platform without compiled Pallas -> "dense"."""
    if not get_backend().pallas_compiled:
        return "dense"
    if fused_attention_fits(t, num_heads, dim):
        return "fused" if batch_parallel_only() else "dense"
    if t >= flash_min_tokens() and t % 1024 == 0:
        return "streaming"
    return "dense"


class QkvProjection(nn.Module):
    """`nn.DenseGeneral((3, H, Dh))` with its bias handed back, not added:
    -> (x @ kernel (B, T, 3, H, Dh), bias (3, H, Dh)). The fused attention
    adds the bias itself, because its backward kernel returns the bias's
    gradient (`fused_attention`). The parameters are DenseGeneral's own,
    `kernel` and `bias` in this module's scope, from the same initialisers
    in the same order: a seed gives the same weights, and a checkpoint
    loads, whichever of the two wrote it."""

    num_heads: int
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        features = (3, self.num_heads, x.shape[-1] // self.num_heads)
        dense = nn.DenseGeneral(features, use_bias=False, dtype=self.dtype)
        nn.share_scope(self, dense)
        y = dense(x)
        bias = self.param("bias", nn.initializers.zeros, features, jnp.float32)
        return y, bias


class Attention(nn.Module):
    num_heads: int
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        h = self.num_heads
        assert d % h == 0, f"dim {d} not divisible by {h} heads"
        qkv, bias = QkvProjection(h, dtype=self.dtype, name="qkv")(x)
        path = attention_path(t, h, d)
        # the choice is made while tracing, so that is where it is counted
        get_registry().counter(
            "attention_sites_total", "Attention sites traced, by the path "
            "their shape chose", labels={"path": path}).inc()
        if path == "fused":
            o = fused_attention(qkv, h, bias).reshape(b, t, h, d // h)
        else:
            qkv = qkv + bias.astype(qkv.dtype)  # (B, T, 3, H, Dh)
            q, k, v = (qkv[:, :, i] for i in range(3))  # (B, T, H, Dh)
            if path == "streaming":
                o = flash_attention(q, k, v)
            else:
                scale = (d // h) ** -0.5
                s = jnp.einsum("bthd,bshd->bhts", q, k) * scale
                p = jax.nn.softmax(s.astype(jnp.float32),
                                   axis=-1).astype(q.dtype)
                o = jnp.einsum("bhts,bshd->bthd", p, v)
        return nn.DenseGeneral(d, axis=(-2, -1), dtype=self.dtype,
                               name="out")(o)


class Mlp(nn.Module):
    hidden: int
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        x = nn.Dense(self.hidden, dtype=self.dtype)(x)
        x = nn.gelu(x)
        return nn.Dense(d, dtype=self.dtype)(x)


class MoeMlp(nn.Module):
    """Top-1 Switch MoE MLP; expert params stacked on a leading E axis.

    Returns (out, gates) — gates (B*T, E) feed the load-balancing loss.
    Expert weights use the (E, d_in, d_out) layout of `parallel.moe`, so
    `expert_param_sharding` / `moe_ffn` apply unchanged for expert-parallel
    training across a mesh axis.
    """

    num_experts: int
    hidden: int
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        e, h = self.num_experts, self.hidden
        tok = x.reshape(b * t, d)
        router = self.param(
            "router", nn.initializers.lecun_normal(), (d, e), jnp.float32
        )
        w1 = self.param(
            "w1", nn.initializers.lecun_normal(), (e, d, h), jnp.float32
        )
        b1 = self.param("b1", nn.initializers.zeros, (e, h), jnp.float32)
        w2 = self.param(
            "w2", nn.initializers.lecun_normal(), (e, h, d), jnp.float32
        )
        b2 = self.param("b2", nn.initializers.zeros, (e, d), jnp.float32)
        dt = self.dtype or x.dtype
        # router in f32 (softmax over logits is precision-sensitive)
        gates = jax.nn.softmax(tok.astype(jnp.float32) @ router)
        choice = jnp.argmax(gates, axis=-1)
        prob = jnp.take_along_axis(gates, choice[:, None], axis=-1)
        # dense dispatch: one-hot einsum packs each token's chosen expert
        # contribution; E small (<=16) so compute is E x the MLP, all MXU
        onehot = jax.nn.one_hot(choice, e, dtype=dt)
        hmid = jax.nn.gelu(
            jnp.einsum("te,td,edh->teh", onehot, tok.astype(dt),
                       w1.astype(dt)) + b1.astype(dt)
        )
        # onehot on BOTH sides: hmid rows of unselected experts are
        # gelu(0 + b1[e]) != 0 once b1 trains, and must not leak into the
        # output sum (top-1 Switch semantics == parallel/moe.moe_ffn_dense)
        out = jnp.einsum(
            "te,teh,ehd->td", onehot, hmid, w2.astype(dt)
        ) + jnp.einsum("te,ed->td", onehot, b2.astype(dt))
        out = out * prob.astype(dt)
        return out.reshape(b, t, d), gates


class ViTBlock(nn.Module):
    num_heads: int
    mlp_ratio: int = 4
    num_experts: int = 0  # 0 = dense MLP
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        y = nn.LayerNorm(dtype=jnp.float32)(x).astype(x.dtype)
        x = x + Attention(self.num_heads, dtype=self.dtype)(y)
        y = nn.LayerNorm(dtype=jnp.float32)(x).astype(x.dtype)
        gates = None
        if self.num_experts:
            y, gates = MoeMlp(
                self.num_experts, d * self.mlp_ratio, dtype=self.dtype
            )(y)
        else:
            y = Mlp(d * self.mlp_ratio, dtype=self.dtype)(y)
        return x + y, gates


class ViT(nn.Module):
    """ViT classifier. Input NHWC; output logits (f32)."""

    depth: int = 12
    dim: int = 384
    num_heads: int = 6
    patch: int = 16
    num_classes: int = 1000
    mlp_ratio: int = 4
    num_experts: int = 0  # >0: MoE every other block (V-MoE "last-2"-ish)
    moe_every: int = 2
    dropout: float = 0.0
    remat: bool = False  # rematerialize each block: activations are
    # recomputed in backward instead of stored — O(sqrt) activation memory,
    # the lever for long-token-count training (jax.checkpoint under flax)
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        b, hh, ww, _ = x.shape
        p = self.patch
        assert hh % p == 0 and ww % p == 0, (
            f"image {hh}x{ww} not divisible by patch {p}"
        )
        dt = self.dtype or x.dtype
        x = nn.Conv(
            self.dim, (p, p), strides=(p, p), padding="VALID", dtype=dt,
            name="patch_embed",
        )(x.astype(dt))
        x = x.reshape(b, -1, self.dim)  # (B, T, D)
        t = x.shape[1]
        pos = self.param(
            "pos_embed", nn.initializers.normal(0.02), (1, t, self.dim),
            jnp.float32,
        )
        x = x + pos.astype(dt)
        if self.dropout:
            x = nn.Dropout(self.dropout, deterministic=not train)(x)
        all_gates = []
        block_cls = nn.remat(ViTBlock) if self.remat else ViTBlock
        for i in range(self.depth):
            moe = (
                self.num_experts
                if self.num_experts
                and (i % self.moe_every == self.moe_every - 1)
                else 0
            )
            # explicit name: nn.remat would auto-name the module
            # remat(CheckpointViTBlock_i), breaking checkpoint
            # interchangeability with the stored-activation variant
            x, gates = block_cls(
                self.num_heads, self.mlp_ratio, num_experts=moe,
                dtype=self.dtype, name=f"ViTBlock_{i}",
            )(x)
            if gates is not None:
                all_gates.append(gates)
        x = nn.LayerNorm(dtype=jnp.float32)(x.astype(jnp.float32))
        x = jnp.mean(x, axis=1)  # token-mean pool
        logits = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        if train and all_gates:
            # Switch aux loss per MoE block, averaged; the classification
            # loss adds `moe_aux_weight * aux` (losses/classification.py)
            aux = jnp.mean(
                jnp.stack([load_balancing_loss(g) for g in all_gates])
            )
            # router telemetry ('_'-prefixed = metrics-only, never added to
            # the loss): mean per-token gate entropy in nats (ln E = uniform
            # routing, 0 = hard routing) and the max fraction of tokens any
            # one expert receives (1/E = balanced, 1.0 = collapse) — the
            # instruments for diagnosing router cold-start stalls
            gates = jnp.stack(all_gates)  # (L, T, E)
            ent = -jnp.sum(gates * jnp.log(gates + 1e-9), axis=-1)
            top1 = jax.nn.one_hot(
                jnp.argmax(gates, axis=-1), gates.shape[-1],
                dtype=jnp.float32,
            )
            load_max = jnp.max(jnp.mean(top1, axis=1))
            return logits, {
                "moe_aux": aux,
                "_router_entropy": jnp.mean(ent),
                "_expert_load_max": load_max,
            }
        return logits


def pipeline_vit_trunk(model: ViT, variables, x, mesh, *,
                       num_microbatches: int, axis_name: str = "model"):
    """Run a dense ViT's block trunk as a GPipe pipeline over `axis_name`.

    The ViT trunk is the textbook pipeline workload — `depth` blocks with
    identical param shapes and one fixed (B, T, D) activation shape. This
    bridges the zoo model to `parallel.pipeline.pipeline_apply`: blocks are
    grouped into `mesh.shape[axis_name]` stages (depth must divide evenly),
    per-stage params are stacked/sharded, and each device runs its
    contiguous block span with one ppermute hop between stages.

    x: (B, T, D) tokens (after patch embed + pos). Returns (B, T, D).
    Matches the sequential trunk exactly (see tests/test_vit.py); grads flow,
    so a pipelined train step is jax.grad over this. MoE blocks are not
    pipelineable this way (their param shapes differ); use dense ViT.
    """
    from deep_vision_tpu.parallel.pipeline import (
        pipeline_apply,
        pipeline_param_sharding,
        stack_pipeline_params,
    )

    assert model.num_experts == 0, "pipeline trunk requires a dense ViT"
    n_stages = mesh.shape[axis_name]
    depth = model.depth
    assert depth % n_stages == 0, (
        f"depth {depth} not divisible into {n_stages} stages"
    )
    span = depth // n_stages
    params = variables["params"]
    block = ViTBlock(model.num_heads, model.mlp_ratio, dtype=model.dtype)
    # stage s holds blocks [s*span, (s+1)*span), stacked on a span axis
    stage_params = [
        jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[params[f"ViTBlock_{s * span + j}"] for j in range(span)],
        )
        for s in range(n_stages)
    ]
    stacked = stack_pipeline_params(stage_params)
    stacked = jax.device_put(
        stacked, pipeline_param_sharding(mesh, stacked, axis_name)
    )

    def stage_fn(p, h):
        def body(h, block_p):
            h, _ = block.apply({"params": block_p}, h)
            return h, None

        h, _ = jax.lax.scan(body, h, p)
        return h

    return pipeline_apply(
        stage_fn, stacked, x, mesh,
        num_microbatches=num_microbatches, axis_name=axis_name,
    )


@register_model("vit_s16")
def vit_s16(num_classes: int = 1000, dtype=None, remat: bool = False, **_):
    return ViT(depth=12, dim=384, num_heads=6, num_classes=num_classes,
               remat=remat, dtype=dtype)


@register_model("vit_b16")
def vit_b16(num_classes: int = 1000, dtype=None, remat: bool = False, **_):
    return ViT(depth=12, dim=768, num_heads=12, num_classes=num_classes,
               remat=remat, dtype=dtype)


@register_model("vmoe_s16")
def vmoe_s16(num_classes: int = 1000, dtype=None, num_experts: int = 8,
             remat: bool = False, **_):
    return ViT(depth=12, dim=384, num_heads=6, num_classes=num_classes,
               num_experts=num_experts, remat=remat, dtype=dtype)
