"""All five parallelism flavors on one mesh, in ~80 lines.

The reference's only distribution is single-host data parallel
(MirroredStrategy at YOLO/tensorflow/train.py:281); this example shows the
TPU-native spectrum on a (data, model) mesh: DP (batch sharding), TP
(Megatron-style weight sharding via `infer_tp_sharding`), SP (ring
attention), PP (GPipe over the model axis), EP (Switch MoE with all_to_all).

Run without hardware on a virtual mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/distributed_training.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402


import jax
import jax.numpy as jnp
import numpy as np

from deep_vision_tpu.core.train_state import create_train_state
from deep_vision_tpu.losses import classification_loss_fn
from deep_vision_tpu.models import get_model
from deep_vision_tpu.parallel import (
    create_mesh,
    data_sharding,
    expert_param_sharding,
    moe_ffn,
    pipeline_apply,
    pipeline_param_sharding,
    stack_pipeline_params,
)
from deep_vision_tpu.parallel.mesh import infer_tp_sharding
from deep_vision_tpu.parallel.ring_attention import ring_attention
from deep_vision_tpu.train import build_optimizer


def main():
    n = len(jax.devices())
    model_par = 2 if n % 2 == 0 and n > 1 else 1
    mesh = create_mesh(data=n // model_par, model=model_par)
    print(f"mesh: {dict(mesh.shape)}")

    # --- DP x TP: the full ResNet-50 train step, sharded ------------------
    model = get_model("resnet50", num_classes=64)
    tx = build_optimizer("sgd", 0.1, momentum=0.9)
    state = create_train_state(model, tx, jnp.ones((2, 64, 64, 3)))
    state = jax.device_put(state, infer_tp_sharding(state, mesh, min_size=1024))
    batch = {
        "image": np.random.RandomState(0).rand(
            2 * mesh.shape["data"], 64, 64, 3).astype(np.float32),
        "label": np.arange(2 * mesh.shape["data"], dtype=np.int32) % 64,
    }
    batch = {k: jax.device_put(v, data_sharding(mesh, np.ndim(v)))
             for k, v in batch.items()}

    @jax.jit
    def train_step(state, batch):
        def loss_fn(params):
            variables = {"params": params, "batch_stats": state.batch_stats}
            out, nms = state.apply_fn(variables, batch["image"], train=True,
                                      rngs={"dropout": state.rng},
                                      mutable=["batch_stats"])
            return classification_loss_fn(out, batch)[0], nms["batch_stats"]

        (loss, bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params)
        return state.apply_gradients(grads).replace(batch_stats=bs), loss

    with mesh:
        state, loss = train_step(state, batch)
    print(f"DPxTP train step: loss {float(loss):.4f}")

    # --- SP: ring attention, sequence sharded over 'data' -----------------
    from jax.sharding import NamedSharding, PartitionSpec as P

    t = 8 * mesh.shape["data"]
    q, k, v = (np.random.RandomState(1).randn(2, t, 2, 8).astype(np.float32)
               for _ in range(3))
    spec = NamedSharding(mesh, P(None, "data", None, None))
    out = ring_attention(*(jax.device_put(x, spec) for x in (q, k, v)),
                         mesh, causal=True)
    print(f"SP ring attention: out {out.shape}")

    # --- PP: a 4-stage GPipe over the model axis (when it exists) ---------
    if model_par > 1:
        stages = [{"w": jnp.asarray(
            np.random.RandomState(s).randn(16, 16) * 0.1, jnp.float32)}
            for s in range(model_par)]
        stacked = stack_pipeline_params(stages)
        stacked = jax.device_put(stacked, pipeline_param_sharding(mesh, stacked))
        y = pipeline_apply(lambda p, h: h + jnp.tanh(h @ p["w"]), stacked,
                           jnp.ones((8, 16)), mesh, num_microbatches=4)
        print(f"PP GPipe: out {y.shape}")

    # --- EP: Switch MoE with all_to_all dispatch over 'data' --------------
    e = 2 * mesh.shape["data"]
    rng = np.random.RandomState(2)
    router = jnp.asarray(rng.randn(16, e) * 0.5, jnp.float32)
    experts = {"w1": jnp.asarray(rng.randn(e, 16, 32) * 0.1, jnp.float32),
               "b1": jnp.zeros((e, 32)),
               "w2": jnp.asarray(rng.randn(e, 32, 16) * 0.1, jnp.float32),
               "b2": jnp.zeros((e, 16))}
    tokens = jnp.asarray(rng.randn(4 * mesh.shape["data"], 16), jnp.float32)
    out = moe_ffn(router, jax.device_put(
        experts, expert_param_sharding(mesh, experts)), tokens, mesh,
        capacity=4)
    print(f"EP MoE: out {out.shape}")
    print("OK")


if __name__ == "__main__":
    main()
