"""Next-token cross-entropy for a decoder that hands over its last hidden
states and its head (`models/olmo_hybrid.py`), taken a block of tokens at a
time.

`outputs` is `{"hidden": (B, T, D), "head": (D, V)}`. Position `t`
predicts token `t + 1`, so the loss is the mean over positions `0..T-2` of
every row (rows a final partial batch pads are masked out, `_mask`). The
logits of one block, `(B, block, V)` float32, are made from operands in the
hidden states' dtype with float32 accumulation, their log-sum-exp and the
loss are float32, and each block is recomputed in the backward pass: a step
holds one block's logits and their gradient, never `(B, T, V)`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK_TOKENS = 512


def logits(outputs):
    """(B, T, V) float32: all of them at once, for a caller that wants
    them (a test, a sampler); the loss never calls this."""
    hidden, head = outputs["hidden"], outputs["head"]
    return jnp.einsum("btd,dv->btv", hidden, head.astype(hidden.dtype),
                      preferred_element_type=jnp.float32)


@jax.checkpoint
def _block_nll(hidden, head, targets, weights):
    z = logits({"hidden": hidden, "head": head})
    nll = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
        z, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * weights)


def causal_lm_loss_fn(outputs, batch, block_tokens: int = BLOCK_TOKENS):
    """-> (loss, {"loss": loss, **the model's "report"}). batch:
    {"tokens": int (B, T)}. A model that counts in its step (the held
    experts' pairs: `models/solar_open2.py`) hands the counts over as
    `outputs["report"]`, and they ride in the step's metrics."""
    tokens = batch["tokens"]
    hidden, head = outputs["hidden"], outputs["head"]
    b, t = tokens.shape
    targets = jnp.roll(tokens, -1, axis=1)  # the last position's has weight 0
    weights = jnp.broadcast_to(
        (jnp.arange(t) < t - 1).astype(jnp.float32), (b, t))
    if "_mask" in batch:
        weights = weights * batch["_mask"][:, None]
    block = block_tokens if t % block_tokens == 0 else t
    total = sum(_block_nll(hidden[:, i:i + block], head,
                           targets[:, i:i + block], weights[:, i:i + block])
                for i in range(0, t, block))
    loss = total / jnp.maximum(jnp.sum(weights), 1.0)
    return loss, {"loss": loss, **outputs.get("report", {})}
