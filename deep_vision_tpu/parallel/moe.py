"""Expert parallelism: mixture-of-experts FFN with all_to_all dispatch.

The reference is a dense CNN zoo with no conditional computation (SURVEY.md
§2), but expert parallelism is part of this framework's first-class
distributed story (DP x TP x PP x SP x EP) — vision MoEs (V-MoE) scale
exactly this way. Design is the GShard/Switch einsum formulation, which is
the TPU-native one: routing becomes two dense einsums against a one-hot
dispatch tensor (MXU work, static shapes, no gather/scatter), and the only
communication is a pair of `jax.lax.all_to_all` collectives that ride ICI —
tokens travel to the devices holding their expert and back.

Layout: tokens sharded over `axis_name` (each device routes its local
tokens), experts sharded over the same axis (each device owns E/n experts).
Capacity is static (TPU shapes must be): each expert accepts at most C
tokens per device per step; overflow tokens fall through the residual
connection untouched — the standard Switch-Transformer semantics.

`held_experts` is the other formulation: a chip's share of an expert
layer whose router scores every expert with a sigmoid and picks the top
`k` by score plus a correction bias (DeepSeek-V3's auxiliary-loss-free
routing, the `n_routed_experts` / `norm_topk_prob` / `routed_scaling_
factor` family), of which this chip holds `n` consecutive experts from
`held_offset`, plus a shared expert every token passes through. No pair
is dropped: the (token, choice) pairs are sorted so that the held
experts' come first, grouped by expert, in a buffer of `T k` rows (every
choice of every token may be held), and the grouped products
(`megablox.gmm`, a Pallas kernel: its grid visits the tiles of the held
groups alone, so its time follows the routed pairs, not the buffer) run
over the held groups. What the absent experts would add is left out, as
the chips that hold them would add it.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deep_vision_tpu.core import backend
from deep_vision_tpu.obs.registry import get_registry
from deep_vision_tpu.parallel.mesh import DATA_AXIS

GMM_ROWS = 128  # the grouped product's row tile: the buffer is padded to it
BIAS_RATE = 1e-3  # the correction bias's step a training step


def expert_ffn(params, x):
    """Default expert: 2-layer GELU MLP. params: {'w1','b1','w2','b2'}."""
    h = jax.nn.gelu(x @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


def _top1_dispatch(gates, capacity: int):
    """Switch top-1 routing -> (dispatch, combine) tensors.

    gates: (T, E) softmax router outputs.
    dispatch: (T, E, C) one-hot — token t occupies slot c of expert e.
    combine:  (T, E, C) = dispatch * gate prob (the output mixing weights).
    Tokens beyond an expert's capacity get an all-zero dispatch row.
    """
    t, e = gates.shape
    expert = jnp.argmax(gates, axis=-1)  # (T,)
    onehot = jax.nn.one_hot(expert, e, dtype=gates.dtype)  # (T, E)
    # position of each token within its expert's queue (0-based, in token
    # order — the deterministic tie-break the einsum formulation gives)
    pos = jnp.cumsum(onehot, axis=0) - onehot  # (T, E)
    keep = onehot * (pos < capacity)  # drop overflow
    slot = jax.nn.one_hot(
        jnp.sum(pos * onehot, axis=-1).astype(jnp.int32), capacity,
        dtype=gates.dtype,
    )  # (T, C)
    dispatch = keep[:, :, None] * slot[:, None, :]  # (T, E, C)
    prob = jnp.sum(gates * onehot, axis=-1)  # (T,) chosen-expert prob
    combine = dispatch * prob[:, None, None]
    return dispatch, combine


def _moe_local(router_w, expert_params, x, *, axis_name: str, capacity: int,
               expert_fn: Callable, n_experts: int):
    """Per-device body (under shard_map). x: (T_loc, D) local tokens."""
    n = jax.lax.psum(1, axis_name)
    e_loc = n_experts // n
    # route in f32 regardless of activation dtype (matching models/vit.py
    # MoeMlp): softmax + argmax over logits are precision-sensitive, and a
    # bf16 near-tie argmaxing to a different expert here than in the
    # in-model path would break checkpoint-deploy equivalence. The f32
    # gates feed dispatch (argmax inside); the resulting one-hot tensors
    # are cast back so expert compute stays in the activation dtype.
    gates = jax.nn.softmax(
        x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    )  # (T_loc, E) — router replicated
    dispatch, combine = _top1_dispatch(gates, capacity)
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(x.dtype)
    # pack: (E, C, D) expert inputs from the local tokens
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)
    # all_to_all #1: split the global-expert dim across devices, concat the
    # senders -> (E_loc, n, C, D): every device's slots for MY experts
    expert_in = expert_in.reshape(n, e_loc, capacity, -1)
    expert_in = jax.lax.all_to_all(
        expert_in, axis_name, split_axis=0, concat_axis=0, tiled=False
    )  # (n, E_loc, C, D) with leading dim = source device
    expert_in = expert_in.transpose(1, 0, 2, 3).reshape(
        e_loc, n * capacity, -1
    )
    # local experts run on their (n*C, D) batch — vmap over the expert dim,
    # each expert its own params slice
    expert_out = jax.vmap(expert_fn)(expert_params, expert_in)
    # all_to_all #2: route results back to the token-owning devices
    expert_out = expert_out.reshape(e_loc, n, capacity, -1).transpose(
        1, 0, 2, 3
    )
    expert_out = jax.lax.all_to_all(
        expert_out, axis_name, split_axis=0, concat_axis=0, tiled=False
    ).reshape(n_experts, capacity, -1)
    # unpack + mix; dropped tokens contribute 0 (pure residual pass-through)
    return jnp.einsum("tec,ecd->td", combine, expert_out)


def moe_ffn(
    router_w,
    expert_params,
    x,
    mesh: Mesh,
    *,
    capacity: int,
    expert_fn: Callable = expert_ffn,
    axis_name: str = DATA_AXIS,
):
    """Expert-parallel top-1 MoE layer over tokens sharded on `axis_name`.

    router_w: (D, E) routing weights (replicated).
    expert_params: pytree whose leaves have leading dim E, sharded over
    `axis_name` (device i holds experts [i*E/n, (i+1)*E/n)).
    x: (T, D) global tokens, T divisible by the axis size.
    capacity: per-expert, per-device slot count C. The output adds to a
    residual stream: dropped (over-capacity) tokens return zeros.
    """
    n = mesh.shape[axis_name]
    e = router_w.shape[-1]
    if e % n != 0:
        raise ValueError(f"{e} experts not divisible over {n} devices")
    body = functools.partial(
        _moe_local,
        axis_name=axis_name,
        capacity=capacity,
        expert_fn=expert_fn,
        n_experts=e,
    )
    expert_specs = jax.tree_util.tree_map(
        lambda p: P(axis_name, *([None] * (p.ndim - 1))), expert_params
    )
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), expert_specs, P(axis_name)),
        out_specs=P(axis_name),
    )
    return mapped(router_w, expert_params, x)


def expert_param_sharding(mesh: Mesh, expert_params,
                          axis_name: str = DATA_AXIS):
    """Shard the leading (expert) dim of every leaf over `axis_name`."""
    return jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh, P(axis_name, *([None] * (p.ndim - 1)))),
        expert_params,
    )


def load_balancing_loss(gates) -> jax.Array:
    """Switch-Transformer auxiliary loss: E * sum_e f_e * P_e.

    gates: (T, E) softmax router outputs. f_e is the fraction of tokens
    whose argmax picks expert e, P_e the mean router probability for e;
    minimized (== 1) when routing is uniform. Add `aux_weight *
    load_balancing_loss(gates)` to the task loss when training a router —
    without it top-1 routing collapses onto a few experts and the rest of
    the capacity (and the all_to_all bandwidth) idles.
    """
    t, e = gates.shape
    choice = jnp.argmax(gates, axis=-1)
    f = jnp.mean(jax.nn.one_hot(choice, e, dtype=gates.dtype), axis=0)
    p = jnp.mean(gates, axis=0)
    return e * jnp.sum(f * p)


def moe_ffn_dense(router_w, expert_params, x, *,
                  expert_fn: Callable = expert_ffn):
    """Single-device reference: every expert on all tokens (golden for tests).

    No capacity limit — equals `moe_ffn` exactly when capacity >= the
    busiest expert's per-device load.
    """
    gates = jax.nn.softmax(
        x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    )  # (T, E) — f32 routing + argmax, as _moe_local / MoeMlp
    choice = jnp.argmax(gates, axis=-1)
    prob = jnp.take_along_axis(gates, choice[:, None], axis=-1).astype(x.dtype)
    all_out = jax.vmap(expert_fn, in_axes=(0, None))(expert_params, x)
    # (E, T, D) -> pick each token's expert
    picked = jnp.take_along_axis(
        all_out, choice[None, :, None], axis=0
    )[0]
    return picked * prob


# -- a chip's share of a sigmoid-routed expert layer, no pair dropped --------

def sigmoid_route(x, kernel, bias, top_k: int, scaling: float = 1.0):
    """Scores `s = sigmoid(x W)` over every expert, in float32; the choice
    `top_k(s + bias)`; the weights the chosen scores over their sum, times
    `scaling`. x: (T, D); kernel: (D, E); bias: (E,), not trained. ->
    (choice (T, k) int32, weights (T, k) float32)."""
    logits = jnp.dot(x.astype(jnp.float32), kernel.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, choice = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    chosen = jnp.take_along_axis(scores, choice, axis=-1)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scaling
    return choice, weights


def bias_update(bias, load, rate: float = BIAS_RATE):
    """The correction bias after a step whose expert loads (E,) were
    `load`: up where an expert took fewer pairs than the mean, down where
    more (DeepSeek-V3, arXiv:2412.19437, sec. 2.1.2)."""
    load = load.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(load) - load)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dispatch(x, order, inv, held, k):
    """(T, D) -> (R, D): buffer row `p` is the token of pair `order[p]`.
    The transpose gathers too (`inv`), where autodiff would scatter-add
    all `T k` rows; rows of pairs not held are garbage the products never
    visit, and are not read back."""
    return x[order // k]


def _dispatch_fwd(x, order, inv, held, k):
    return _dispatch(x, order, inv, held, k), (order, inv, held)


def _dispatch_bwd(k, res, g):
    order, inv, held = res
    rows = jnp.where(held[:, None], g[inv], 0).astype(jnp.float32)
    dx = jnp.sum(rows.reshape(-1, k, g.shape[-1]), axis=1)
    return dx.astype(g.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _combine(y, weights, order, inv, held, k):
    """(R, D) buffer rows -> (T, D) float32: `sum_j weights[t, j] y[inv[t k
    + j]]` over the held pairs of each token."""
    return _combine_fwd(y, weights, order, inv, held, k)[0]


def _combine_fwd(y, weights, order, inv, held, k):
    rows = jnp.where(held[:, None], y[inv], 0).astype(jnp.float32)
    rows = rows.reshape(-1, k, y.shape[-1])
    out = jnp.einsum("tkd,tk->td", rows, weights)
    return out, (y, weights, order, inv, held)


def _combine_bwd(k, res, g):
    y, weights, order, inv, held = res
    rows = jnp.where(held[:, None], y[inv], 0).astype(jnp.float32)
    d_weights = jnp.einsum("tkd,td->tk", rows.reshape(-1, k, y.shape[-1]), g)
    # the (T k, D) gather in y's dtype, not float32: `g` is the cotangent
    # of a sum the caller rounds to y's dtype, so it holds no more bits
    d_y = g.astype(y.dtype)[order // k] * weights.reshape(-1)[order][:, None]
    return d_y.astype(y.dtype), d_weights, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _tile(dim: int, cap: int) -> int:
    """The largest multiple of 128 up to `cap` that divides `dim`; the whole
    dimension where none does (a tiny test's)."""
    for t in range(cap, 127, -128):
        if dim % t == 0:
            return t
    return dim


def gmm_tiling(m: int, k: int, n: int):
    """(rows, contraction, columns) tiles of a grouped product."""
    return GMM_ROWS, _tile(k, 512), _tile(n, 1024)


def grouped_matmul(lhs, rhs, group_sizes):
    """`lhs` rows grouped in order by `group_sizes` (n,), each group times
    its matrix of `rhs` (n, K, N) -> (rows, N) in lhs's dtype, float32
    accumulation. Rows past the groups are left unwritten."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    return gmm(lhs, rhs, group_sizes, lhs.dtype, gmm_tiling, None, None,
               False, backend.pallas_interpret())


def _swiglu(x, gate, up, down):
    h = jax.nn.silu(x @ gate.astype(x.dtype)) * (x @ up.astype(x.dtype))
    return h @ down.astype(x.dtype)


def held_experts(x, router, bias, experts, shared=None, *, top_k: int,
                 held_offset: int = 0, scaling: float = 1.0):
    """A chip's share of a sigmoid-routed expert layer (module docstring).

    x: (T, D) in the compute dtype; router: (D, E) over every expert; bias:
    (E,) the correction bias; experts: {"gate", "up": (n, D, F), "down":
    (n, F, D)}, the held experts `held_offset .. + n`; shared: {"gate",
    "up": (D, F_s), "down": (F_s, D)} or None.
    -> (y (T, D) in x's dtype, {"load": (E,) pairs each expert was chosen
    for, int32; "pairs": the pairs the held experts computed; "load_max":
    the largest held expert's}). Each expert is `down(silu(gate x) * up
    x)`, weighted by its routing weight."""
    get_registry().counter(
        "moe_sites_total", "Expert layers traced (held_experts)").inc()
    t, d = x.shape
    n, e = experts["gate"].shape[0], router.shape[-1]
    with jax.named_scope("moe/route"):
        choice, weights = sigmoid_route(x, router, bias, top_k, scaling)
        load = jnp.sum(jax.nn.one_hot(choice, e, dtype=jnp.int32),
                       axis=(0, 1))
    with jax.named_scope("moe/dispatch"):
        local = choice.reshape(-1) - held_offset
        held = (local >= 0) & (local < n)
        key = jnp.where(held, local, n)  # the held experts' groups, then rest
        pairs = t * top_k
        rows = -(-pairs // GMM_ROWS) * GMM_ROWS
        first = jax.nn.one_hot(key, n + 1, dtype=jnp.int32)
        sizes = jnp.sum(first, axis=0)
        # a pair's buffer row: its group's start, then its rank inside it
        starts = jnp.cumsum(sizes) - sizes
        inv = jnp.sum((starts + jnp.cumsum(first, axis=0) - 1) * first,
                      axis=1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        order = jnp.pad(order, (0, rows - pairs))
        xs = _dispatch(x, order, inv, held, top_k)
        group_sizes = sizes[:n].astype(jnp.int32)
    with jax.named_scope("moe/experts"):
        # gate and up as one product: one pass over the buffer each way
        f = experts["gate"].shape[-1]
        gate_up = grouped_matmul(xs, jnp.concatenate(
            [experts["gate"], experts["up"]], axis=-1).astype(x.dtype),
            group_sizes)
        h = jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:]
        ys = grouped_matmul(h, experts["down"].astype(x.dtype), group_sizes)
    with jax.named_scope("moe/combine"):
        y = _combine(ys, weights * held.reshape(t, top_k), order, inv, held,
                     top_k)
    if shared is not None:
        with jax.named_scope("moe/shared"):
            y = y + _swiglu(x, shared["gate"], shared["up"], shared["down"])
    stats = {"load": load, "pairs": jnp.sum(group_sizes),
             "load_max": jnp.max(group_sizes)}
    return y.astype(x.dtype), stats
