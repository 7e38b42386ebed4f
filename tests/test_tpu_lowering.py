"""Every Pallas entry point lowers for the TPU, checked from the CPU.

`jax.jit(f).trace(*specs).lower(lowering_platforms=("tpu",))` runs the
Pallas-to-Mosaic lowering with no chip: block shapes the TPU refuses, ops
Mosaic has no rule for, and a kernel left inside a multi-device program all
fail here. The second half goes further where libtpu is installed: a
compile-only v5e topology runs the real Mosaic and XLA:TPU compilers, so
running out of VMEM fails here too. Only execution (numbers, times) is left
to chip_smoke.py. Shapes are the production ones chip_smoke.py runs.

BatchNorm's tail was such a kernel until PR 30 and is plain jax.numpy now
(tests/test_bn_tail.py); a test here holds what that bought: a ResNet block
compiled for the v5e has no custom call and no layout copy of an
activation, and (PR 35) no `pred` mask of an activation's size stored
beside the gradient it masks. The last test holds what the single-block
attention kernel (PR 32) buys a ViT block and what it does not: no (T, T) tensor in the
program, and exactly the four layout copies of the kernel's own operands;
the one after it what the backward kernel's second output buys (PR 34): the
qkv bias's gradient without a pass over d(qkv), the bias still in the
projection matmul's epilogue. The last holds the delta rule's inverse (PR
37): one `gdn_inverse` call a layer, kept across the block's recomputation
by its name, and no inversion of XLA's.
"""
import functools
import json
import math
import os
import re
import subprocess
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from deep_vision_tpu.ops.pallas.flash_attention import (
    flash_attention,
    fused_attention,
)
from deep_vision_tpu.ops.pallas import moe_rows
from deep_vision_tpu.ops.pallas.nms import pallas_nms
from deep_vision_tpu.ops.pallas.tril_inverse import tril_inverse

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S = jax.ShapeDtypeStruct


def lower_for_tpu(fn, *specs):
    text = jax.jit(fn).trace(*specs).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text  # the kernel is in the program, compiled
    return text


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t", [1024, 4096])
def test_flash_attention_lowers_fwd_bwd(t, causal):
    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=causal, interpret=False)
            return jnp.sum(out.astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    q = S((2, t, 12, 64), jnp.bfloat16)
    lower_for_tpu(fwd_bwd, q, q, q)


def _fused_fwd_bwd(qkv):
    def loss(qkv):
        return jnp.sum(fused_attention(qkv, 12, interpret=False)
                       .astype(jnp.float32))
    return jax.value_and_grad(loss)(qkv)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_fused_attention_lowers_fwd_bwd_at_vit_b16(dtype):
    # the cell's shape: batch 128, 196 tokens, 3 x 12 heads x 64
    text = lower_for_tpu(_fused_fwd_bwd, S((128, 196, 2304), dtype))
    assert text.count("tpu_custom_call") == 2  # one forward, one backward


@pytest.mark.parametrize("batch", [1, 8])
def test_nms_lowers_at_yolo_scale(batch):
    # batch 8 is the case a (1, N) block of a (B, N) array cannot lower
    lower_for_tpu(
        lambda boxes, scores: pallas_nms(boxes, scores, 100, 0.5, 0.5,
                                         interpret=False),
        S((batch, 10647, 4), jnp.float32), S((batch, 10647), jnp.float32))


def _flash_fwd_bwd(q, k, v):
    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=False)
                       .astype(jnp.float32))
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


_Q = S((2, 1024, 12, 64), jnp.bfloat16)


@pytest.mark.parametrize("name, fn, specs", [
    ("flash_fwd", _flash_fwd_bwd, (_Q, _Q, _Q)),
    ("flash_bwd_dq", _flash_fwd_bwd, (_Q, _Q, _Q)),
    ("flash_bwd_dkv", _flash_fwd_bwd, (_Q, _Q, _Q)),
    ("nms", lambda b, s: pallas_nms(b, s, 100, 0.5, 0.5, interpret=False),
     (S((1, 10647, 4), jnp.float32), S((1, 10647), jnp.float32))),
    ("attn_fused_fwd", _fused_fwd_bwd, (S((2, 196, 2304), jnp.bfloat16),)),
    ("attn_fused_bwd", _fused_fwd_bwd, (S((2, 196, 2304), jnp.bfloat16),)),
    ("gdn_inverse", lambda a: tril_inverse(a, interpret=False),
     (S((2, 32, 30, 64, 64), jnp.float32),)),
    ("moe_gather_rows", lambda x, i: moe_rows.gather_rows(
        x, i, jnp.sum(i), interpret=False),
     (S((256, 512), jnp.bfloat16), S((1024,), jnp.int32))),
    ("moe_sum_rows", lambda b, o, lo: moe_rows.sum_rows(
        b, o, lo, lo + 1, tokens=256, k=4, interpret=False),
     (S((1024, 512), jnp.bfloat16), S((1024,), jnp.int32),
      S((2, 8), jnp.int32))),
    ("moe_gate", lambda gu, live: moe_rows.gate_rows(gu, live[0],
                                                     interpret=False),
     (S((1024, 512), jnp.bfloat16), S((1,), jnp.int32))),
])
def test_each_kernel_carries_its_name_into_the_program(name, fn, specs):
    """A `name=` on every `pallas_call`: a trace's reader finds the kernel
    by it, on one chip and on four, not by its position in the program."""
    text = lower_for_tpu(fn, *specs)
    assert re.search(rf"\b{name}\b", text), name


@pytest.mark.parametrize("fn, shapes", [
    (_flash_fwd_bwd, [(8, 1024, 12, 64)] * 3),
    (_fused_fwd_bwd, [(8, 196, 2304)]),
    (lambda a: tril_inverse(a, interpret=False), [(8, 6, 64, 64)]),
], ids=["streaming", "fused", "inverse"])
def test_kernel_in_a_multi_device_program_needs_the_mesh_context(
        mesh8, fn, shapes):
    """XLA cannot partition a Mosaic call: in a program over 8 devices the
    lowering refuses it, and under the trainers' `jax.set_mesh` context the
    kernel runs per data-axis shard instead (ops/pallas/partition.py)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    specs = [S(shape, jnp.bfloat16, sharding=NamedSharding(mesh8, P("data")))
             for shape in shapes]
    with pytest.raises(NotImplementedError, match="shard_map"):
        lower_for_tpu(fn, *specs)
    with jax.set_mesh(mesh8):
        text = lower_for_tpu(fn, *specs)
    assert "all-gather" not in text and "all_gather" not in text


def test_unknown_platform_is_an_error_not_the_cpu_row(monkeypatch):
    from deep_vision_tpu.core import backend

    monkeypatch.setattr(backend, "current_platform", lambda: "rocm")
    with pytest.raises(RuntimeError, match="'rocm'"):
        backend.get_backend()
    with pytest.raises(RuntimeError, match="'rocm'"):
        backend.pallas_interpret()


def test_no_interpret_on_tpu(monkeypatch):
    from deep_vision_tpu.core import backend

    monkeypatch.setattr(backend, "current_platform", lambda: "tpu")
    assert backend.pallas_interpret() is False
    assert backend.default_nms_impl() == "pallas"


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode != 0
    assert "needs a TPU" in res.stderr and "'cpu'" in res.stderr
    assert '"ok"' not in res.stdout  # no result line


# -- the per-shard wrap computes the same numbers (interpreted, 8 devices) ---

def _sharded(mesh, *arrays):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return [jax.device_put(a, NamedSharding(mesh, P("data"))) for a in arrays]


def test_kernels_per_shard_match_their_references(mesh8):
    import numpy as np

    from deep_vision_tpu.ops import nms as lax_nms
    from deep_vision_tpu.ops.pallas import flash_attention as _  # noqa: F401

    fa = sys.modules["deep_vision_tpu.ops.pallas.flash_attention"]
    rng = np.random.RandomState(1)
    q, k, v = _sharded(mesh8, *(rng.randn(8, 32, 2, 8).astype(np.float32)
                                for _ in range(3)))

    def attn(impl):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(impl(q, k, v) ** 2), argnums=(0, 1, 2)))

    with jax.set_mesh(mesh8):
        got = attn(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16,
            interpret=True))(q, k, v)
    want = attn(lambda q, k, v: fa._dense_reference(
        q, k, v, True, 8 ** -0.5))(q, k, v)
    for u, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(u), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)

    (qkv,) = _sharded(mesh8, rng.randn(8, 20, 3 * 128).astype(np.float32))
    with jax.set_mesh(mesh8):
        got = jax.jit(jax.value_and_grad(lambda x: jnp.sum(fused_attention(
            x, 2, interpret=True) ** 2)))(qkv)
    heads = lambda x, i: x[..., i * 128:(i + 1) * 128].reshape(8, 20, 2, 64)
    want = jax.jit(jax.value_and_grad(lambda x: jnp.sum(fa._dense_reference(
        heads(x, 0), heads(x, 1), heads(x, 2), False, 64 ** -0.5) ** 2)))(qkv)
    for u, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(u), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)

    xy = rng.rand(8, 200, 2).astype(np.float32) * 0.8
    wh = rng.rand(8, 200, 2).astype(np.float32) * 0.25 + 0.02
    boxes, scores = _sharded(mesh8, np.concatenate([xy, xy + wh], -1),
                             rng.rand(8, 200).astype(np.float32))
    with jax.set_mesh(mesh8):
        sel_s, sel_i = jax.jit(lambda b, s: pallas_nms(
            b, s, 20, 0.5, 0.3, interpret=True))(boxes, scores)
    ref_s, ref_i = jax.vmap(lambda b, s: lax_nms._nms_single(
        b, s, 20, 0.5, 0.3))(boxes, scores)
    np.testing.assert_array_equal(np.asarray(sel_i), np.asarray(ref_i))
    np.testing.assert_array_equal(np.asarray(sel_s), np.asarray(ref_s))

    (a,) = _sharded(mesh8, np.tril(rng.randn(8, 3, 16, 16), -1).astype(
        np.float32))
    with jax.set_mesh(mesh8):
        inverse = jax.jit(lambda a: tril_inverse(a, interpret=True))(a)
    np.testing.assert_allclose(
        np.asarray(inverse), np.linalg.inv(np.asarray(a) + np.eye(16)),
        rtol=2e-4, atol=2e-4)


# -- the real compiler, no chip: a compile-only v5e topology -----------------

@pytest.fixture(scope="module")
def v5e():
    """One compile-only TPU v5e device (libtpu installed, no TPU attached)."""
    from jax.experimental import topologies

    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to compile with
        pytest.skip(f"no compile-only TPU topology: {type(e).__name__}: {e}")
    return topo.devices[0]


def compile_for_v5e(device, fn, *specs):
    from jax.sharding import SingleDeviceSharding

    here = SingleDeviceSharding(device)
    specs = [S(s.shape, s.dtype, sharding=here) for s in specs]
    return jax.jit(fn).lower(*specs).compile()


def test_nms_and_flash_compile_for_v5e(v5e):
    compile_for_v5e(
        v5e, lambda boxes, scores: pallas_nms(boxes, scores, 100, 0.5, 0.5,
                                              interpret=False),
        S((8, 10647, 4), jnp.float32), S((8, 10647), jnp.float32))

    def fwd_bwd(q, k, v):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=False).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    q = S((1, 4096, 12, 64), jnp.bfloat16)
    compile_for_v5e(v5e, fwd_bwd, q, q, q)
    # the single-block kernel at its bound: 256 tokens, ViT-L's 16 heads
    compile_for_v5e(v5e, jax.value_and_grad(lambda x: jnp.sum(fused_attention(
        x, 16, interpret=False).astype(jnp.float32))),
        S((8, 256, 3 * 1024), jnp.bfloat16))


@functools.lru_cache(maxsize=None)
def _resnet_blocks_hlo(v5e, hw: int, c: int) -> str:
    """The optimized HLO of a convolution and two identity bottlenecks,
    forward and backward, bf16, batch 128: every activation has a
    convolution before and after it, as in the model, and the loss gives
    the last tail a cotangent that is no constant."""
    from jax.sharding import SingleDeviceSharding

    from deep_vision_tpu.models.resnet import BottleneckBlock

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Conv(c, (1, 1), dtype=jnp.bfloat16)(x)
            for _ in range(2):
                x = BottleneckBlock(c // 4, dtype=jnp.bfloat16)(x, True)
            return x

    x = S((128, hw, hw, 16), jnp.bfloat16)
    variables = jax.eval_shape(Net().init, jax.random.PRNGKey(0), x)

    def fwd_bwd(variables, x):
        def loss(params):
            y, _ = Net().apply({**variables, "params": params}, x,
                               mutable=["batch_stats"])
            return jnp.sum(jnp.square(y.astype(jnp.float32)))
        return jax.value_and_grad(loss)(variables["params"])

    here = SingleDeviceSharding(v5e)
    specs = jax.tree.map(lambda s: S(s.shape, s.dtype, sharding=here),
                         (variables, x))
    return jax.jit(fwd_bwd).lower(*specs).compile().as_text()


def _entry_arrays(text: str, pattern: str, elements: int) -> list:
    """Matches of `pattern` (group 1: a shape's dims) in the entry
    computation whose array has at least `elements` elements."""
    entry = text[text.index("\nENTRY "):]
    return [m.group(0) for m in re.finditer(pattern, entry)
            if math.prod(map(int, m.group(1).split(","))) >= elements]


@pytest.mark.parametrize("hw, c", [(56, 256), (7, 2048)])
def test_resnet_blocks_compile_to_xla_fusions_alone(v5e, hw, c):
    """BatchNorm's tail is XLA's to fuse in the convolutions' layouts: no
    `tpu_custom_call`, and no `copy`, `reshape` or `transpose` of its own
    that writes a tensor of the activation's size (the Pallas tail cost two
    or three per site, PERF.md §5)."""
    text = _resnet_blocks_hlo(v5e, hw, c)
    assert "tpu_custom_call" not in text
    moved = _entry_arrays(
        text, r"= \w+\[([\d,]+)\]\S* (?:copy|reshape|transpose)\(",
        128 * hw * hw * c)
    assert not moved, moved


@pytest.mark.parametrize("hw, c", [(56, 256), (7, 2048)])
def test_resnet_blocks_store_no_mask_beside_the_gradient(v5e, hw, c):
    """The tail's backward masks the cotangent in bf16, and XLA stores the
    masked array once (PR 35). Masked in float32, XLA pushed the
    `where(y > 0, g, 0)` into the three consumers and made its operands
    the producer's outputs: a `pred` array of the activation's size beside
    the unmasked gradient, three bytes an element across HBM four times
    where two do."""
    masks = _entry_arrays(_resnet_blocks_hlo(v5e, hw, c),
                          r"pred\[([\d,]+)\]", 128 * hw * hw * c)
    assert not masks, masks


def _vit_blocks_hlo(v5e, monkeypatch, blocks: int) -> str:
    """The optimized HLO of `blocks` ViTBlocks at ViT-B/16's shape,
    bf16[128,196,768], forward and backward, as a TPU routes them."""
    from jax.sharding import SingleDeviceSharding

    from deep_vision_tpu.core import backend
    from deep_vision_tpu.models.vit import ViTBlock

    monkeypatch.setattr(backend, "current_platform", lambda: "tpu")

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            for _ in range(blocks):
                x, _ = ViTBlock(12, dtype=jnp.bfloat16)(x)
            return x

    x = S((128, 196, 768), jnp.bfloat16)
    variables = jax.eval_shape(Net().init, jax.random.PRNGKey(0), x)

    def fwd_bwd(variables, x):
        def loss(params, x):
            y = Net().apply({"params": params}, x)
            return jnp.sum(y.astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1))(variables["params"], x)

    here = SingleDeviceSharding(v5e)
    specs = jax.tree.map(lambda s: S(s.shape, s.dtype, sharding=here),
                         (variables, x))
    return jax.jit(fwd_bwd).lower(*specs).compile().as_text()


def test_vit_block_compiles_with_attention_in_vmem(v5e, monkeypatch):
    """A ViTBlock at ViT-B/16's shape, bf16[128,196,768], forward and
    backward, as a TPU routes it: the scores never reach the program (no
    array with two 196 extents; the dense expression keeps ten passes over
    a bf16[128,12,196,196] a block), and the attention is two Mosaic calls.

    What the kernel does NOT buy (PERF.md §6, PR 32): XLA keeps this
    model's activations batch-minor ({0,2,1}: the batch of 128 fills the
    lanes, 196 tokens would pad them), and a Pallas call's operands are
    row-major by contract, so each of the kernel's four operands (qkv, o,
    dO, d(qkv)) costs one layout copy. Held here at exactly those four, so
    that a fifth, or their removal, shows."""
    text = _vit_blocks_hlo(v5e, monkeypatch, 1)
    entry = text[text.index("\nENTRY "):]
    assert entry.count('custom_call_target="tpu_custom_call"') == 2
    for name in ("attn_fused_fwd", "attn_fused_bwd"):
        assert re.search(rf"\b{name}\b", entry), name
    shapes = [list(map(int, dims.split(",")))
              for dims in re.findall(r"\w+\[([\d,]+)\]", entry)]
    assert not [s for s in shapes if s.count(196) >= 2]
    moved = [m.group(1) for m in re.finditer(
        r"= \w+\[([\d,]+)\]\S* (?:copy|reshape|transpose)\(", entry)
        if math.prod(map(int, m.group(1).split(","))) >= 128 * 196 * 768]
    assert sorted(moved) == sorted(["128,196,2304"] * 2 + ["128,196,768"] * 2)


def test_vit_blocks_take_the_qkv_bias_gradient_from_the_kernel(v5e,
                                                               monkeypatch):
    """Two ViTBlocks at ViT-B/16's shape, forward and backward. The qkv
    bias's gradient is d(qkv) summed over images and tokens; `attn_fused_bwd`
    sums each image's in VMEM (its second output, f32[128,1,2304]), so the
    program holds no `reduce` over the bf16[128,196,2304] the kernel wrote
    (a pass of 115.6 MB a block, 2.0 ms a step over ViT-B's 12: PERF.md §6,
    PR 34). Where the bias is ADDED decides whether that is a gain: in the
    projection's own (B, T, 3, H, Dh) it stays in the projection matmul's
    epilogue (a `convolution` fusion that takes the [3,12,64] bias); after
    the reshape to (B, T, 2304) XLA splits it out as a pass of its own over
    the activation, which costs more than the `reduce` did. So nothing may
    write a tensor of that size but, a block: the projection, the two
    kernels' operands' copies in and the backward kernel and its copy out."""
    text = _vit_blocks_hlo(v5e, monkeypatch, 2)
    entry = text[text.index("\nENTRY "):]
    instr = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$",
                       re.M)
    size = 128 * 196 * 2304
    sizes, writes, reduced = {}, [], []
    for name, result, op, rest in instr.findall(entry):
        sizes[name] = [math.prod(map(int, dims.split(",")))
                       for dims in re.findall(r"\w+\[([\d,]+)\]", result)]
        if op == "reduce":
            reduced += [(name, operand) for operand in re.findall(
                r"%([\w.\-]+)", rest.split(")")[0])
                if size in sizes.get(operand, ())]
        if size in sizes[name] and op not in ("bitcast", "get-tuple-element"):
            writes.append((op, name, rest))
    assert not reduced, reduced
    assert (sorted(op for op, _, _ in writes)
            == ["copy"] * 4 + ["custom-call"] * 2 + ["fusion"] * 2), [
                w[:2] for w in writes]
    assert all("attn_fused_bwd" in name
               for op, name, _ in writes if op == "custom-call")
    for op, name, rest in writes:
        if op == "fusion":  # the projection, the bias inside
            called = re.search(r"calls=%([\w.\-]+)", rest).group(1)
            body = text[text.index(f"\n%{called} ("):]
            body = body[:body.index("\n}")]
            assert " convolution(" in body and "[3,12,64]" in body, name


_DELTA_LAYER_HLO = {}


def _delta_layer_hlo(v5e, monkeypatch, kept: bool) -> str:
    """The optimized HLO of a `GatedDeltaNet` layer at the cell's shapes
    (2 x 2048 tokens, width 3840, 30 heads of 96 / 192, bf16), forward and
    backward, recomputed as `OlmoHybrid` recomputes a block: under `_KEPT`,
    or under the plain policy that keeps the projections' outputs alone.
    Traced as a TPU routes it (the kernel compiled, not interpreted).
    Compiled once a policy: 20 s each."""
    if kept in _DELTA_LAYER_HLO:
        return _DELTA_LAYER_HLO[kept]
    from jax.sharding import SingleDeviceSharding

    from deep_vision_tpu.core import backend
    from deep_vision_tpu.models.decoder import KEPT as _KEPT
    from deep_vision_tpu.models.olmo_hybrid import GatedDeltaNet

    policy = _KEPT if kept else \
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    layer = nn.remat(GatedDeltaNet, policy=policy)(30, 96, 192,
                                                   dtype=jnp.bfloat16)
    x = S((2, 2048, 3840), jnp.bfloat16)
    variables = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)

    def fwd_bwd(params, x):
        return jax.value_and_grad(lambda p, x: jnp.sum(jnp.square(
            layer.apply({"params": p}, x).astype(jnp.float32))),
            argnums=(0, 1))(params, x)

    here = SingleDeviceSharding(v5e)
    specs = jax.tree.map(lambda s: S(s.shape, s.dtype, sharding=here),
                         (variables["params"], x))
    monkeypatch.setattr(backend, "current_platform", lambda: "tpu")
    _DELTA_LAYER_HLO[kept] = jax.jit(fwd_bwd).lower(
        *specs).compile().as_text()
    return _DELTA_LAYER_HLO[kept]


@pytest.mark.parametrize("kept, inversions", [(True, 1), (False, 2)],
                         ids=["kept_by_name", "projections_alone"])
def test_delta_rule_layer_inverts_its_triangles_once(v5e, monkeypatch, kept,
                                                     inversions):
    """`T = (I + A)^-1` is made by one Pallas call, `gdn_inverse`, under the
    scope `delta_inverse`, and a recomputed block keeps it by its name
    (`INVERSE_NAME` in `_KEPT`): one call a layer, forward and backward
    together. Under the plain policy the second forward makes it again:
    two, which is what the name buys. Either way nothing is left for XLA
    to invert (the parent's `solve_triangular` cost two
    `InvertDiagBlocksLowerTriangular` calls a layer, 5.1 ms each on a v5e:
    PERF.md §6, PR 37), and the scans stay three `while` ops: forward, the
    recomputed forward, backward."""
    text = _delta_layer_hlo(v5e, monkeypatch, kept)
    entry = text[text.index("\nENTRY "):]
    assert "InvertDiagBlocksLowerTriangular" not in text
    calls = re.findall(r"^\s*%?(\S+) = .*custom_call_target=\"tpu_custom_call\""
                       r".*op_name=\"([^\"]*)\"", entry, re.M)
    assert len(calls) == inversions, calls
    for name, op_name in calls:
        assert name.startswith("gdn_inverse"), name
        assert "/gated_delta/delta_inverse/" in op_name, op_name
    assert len(re.findall(r"^\s*%?\S+ = .*? while\(", entry, re.M)) == 3


@pytest.mark.parametrize("kept, float32_gb, all_gb", [
    (True, 0.07, 1.85), (False, 0.13, 2.05)],
    ids=["kept_by_name", "projections_alone"])
def test_delta_rule_layer_crosses_to_chunks_in_the_stored_dtype(
        v5e, monkeypatch, kept, float32_gb, all_gb):
    """q, k, v and o change between token-major `(B, T, H, d)` and
    chunk-major `(N, B, H, C, d)` once each way a pass (forward, recomputed
    forward, backward), as the bf16 they are stored in: `to_chunks` moves
    what it is given and pins its result, `unit()` and `o_norm` do their
    float32 row math on the chunk side (PERF.md §6, PR 39). Before, the
    row math stood token-side of the crossing and XLA moved every one of
    these tensors as float32, in two and three passes: 22 float32 `copy` /
    `reshape` / `transpose` ops over 10 MB outside `delta_inverse` moving
    2.71 GB a layer, 3.41 GB for all movers; without the pin XLA fuses the
    widening into the silu in front of the crossing and 11 ops / 1.20 GB
    stay. What is left in float32 is `A`'s layout for `gdn_inverse` (31.5
    MB, once where `T` is kept, twice where it is made again). Bytes: an
    op reads what it writes. Limits just above the count, so that a
    crossing that widens again, or a third pass, shows."""
    text = _delta_layer_hlo(v5e, monkeypatch, kept)
    entry = text[text.index("\nENTRY "):]
    movers = [(dtype, 2 * {"f32": 4, "bf16": 2}[dtype]
               * math.prod(map(int, dims.split(","))), rest)
              for dtype, dims, rest in re.findall(
                  r"= (f32|bf16)\[([\d,]+)\]\S* (?:copy|reshape|transpose)"
                  r"\((.*)$", entry, re.M)]
    wide = [moved for dtype, moved, rest in movers
            if dtype == "f32" and moved > 2 * 10e6
            and "/delta_inverse/" not in rest]
    assert sum(wide) / 1e9 <= float32_gb, (len(wide), sum(wide) / 1e9)
    assert len(wide) == (1 if kept else 2)
    assert sum(moved for _, moved, _ in movers) / 1e9 <= all_gb
    # and the crossings are there, narrow: q, k (23.6 MB) and v, o (47.2),
    # 22 passes (the scans and the one `gdn_inverse`: the test above)
    narrow = sum(moved for dtype, moved, _ in movers
                 if dtype == "bf16" and moved > 2 * 10e6)
    assert narrow / 1e9 == pytest.approx(1.51, abs=0.01)


def test_held_experts_compile_to_grouped_kernels_and_gathers(v5e,
                                                             monkeypatch):
    """`parallel/moe.held_experts` at `solar_open2_250b_train`'s shapes (2 x
    2048 tokens, width 4096, 8 held of 320 experts of 1280, a shared one),
    forward and backward, for a v5e: the grouped products are `megablox`'s
    Pallas calls (`gmm` forward and for the input's gradient, `tgmm` for
    the experts'), and every other pass over the `T k` = 32,768-row buffer
    is one of `ops/pallas/moe_rows.py`'s, which visit its live tiles alone:
    the dispatch and the combine's backward fill it (`moe_gather_rows`),
    the combine, its weights' gradient and the dispatch's transpose read
    it by token (`moe_sum_rows`), the gate crosses it each way
    (`moe_gate`). No XLA instruction of the dispatch or the combine, and
    no elementwise one of the experts, has an operand or result of the
    buffer's rows and 128 or more columns (before, 3.86 GB a layer of such
    passes: PERF.md section 6), and no activation is scattered. The
    new kernels' names are outside the prefixes the Solar cell's
    `expert_kernel_ops` and `delta_rule_ops` read."""
    from deep_vision_tpu.core import backend
    from deep_vision_tpu.parallel import moe

    n, e, d, f, t = 8, 320, 4096, 1280, 4096
    names = ("gate", "up", "down", "shared_gate", "shared_up", "shared_down")
    shapes = ((n, d, f), (n, d, f), (n, f, d), (d, f), (d, f), (f, d))

    def fwd_bwd(x, router, bias, *weights):
        def loss(x, router, weights):
            w = dict(zip(names, weights))
            y, _ = moe.held_experts(
                x, router, bias, {k: w[k] for k in names[:3]},
                {k: w["shared_" + k] for k in names[:3]}, top_k=8)
            return jnp.sum(jnp.square(y.astype(jnp.float32)))
        return jax.grad(loss, argnums=(0, 1, 2))(x, router, weights)

    monkeypatch.setattr(backend, "current_platform", lambda: "tpu")
    text = compile_for_v5e(
        v5e, fwd_bwd, S((t, d), jnp.bfloat16), S((d, e), jnp.float32),
        S((e,), jnp.float32),
        *(S(shape, jnp.float32) for shape in shapes)).as_text()
    entry = text[text.index("\nENTRY "):]
    calls = re.findall(
        r"^\s*%?(\S+) = .*custom_call_target=\"tpu_custom_call\"", entry,
        re.M)
    kinds = sorted(re.sub(r"\.\d+$", "", c) for c in calls)
    assert kinds == (["gmm"] * 4 + ["moe_gate"] * 2 + ["moe_gather_rows"] * 2
                     + ["moe_sum_rows"] * 3 + ["tgmm"] * 2), calls
    with open(os.path.join(REPO, "benchmark", "cells",
                           "solar_open2_250b_train.json")) as fh:
        cell = json.load(fh)
    for kind in {"moe_gate", "moe_gather_rows", "moe_sum_rows"}:
        assert not kind.startswith(tuple(cell["expert_kernel_ops"]
                                         + cell["delta_rule_ops"])), kind
    shape_of = dict(re.findall(r"^\s*(?:ROOT )?%?(\S+) = \w+\[([\d,]*)\]",
                               entry, re.M))
    rows = t * 8

    def buffer_sized(dims):
        dims = [int(v) for v in dims.split(",") if v]
        return len(dims) >= 2 and dims[0] == rows and dims[1] >= 128

    wide = []
    for line in entry.splitlines():
        m = re.match(r"^\s*(?:ROOT )?%?(\S+) = \w+\[([\d,]*)\]\S* (\S+?)\((.*)",
                     line)
        scope = re.search(r'op_name="[^"]*(moe/(?:dispatch|combine|experts))',
                          line)
        if not m or not scope or m.group(3) == "custom-call":
            continue
        if scope.group(1) == "moe/experts" and "convolution" in line:
            continue  # a product, not an elementwise pass
        operands = re.findall(r"%([\w.\-]+)", m.group(4).split("), ")[0])
        if buffer_sized(m.group(2)) or any(
                buffer_sized(shape_of.get(o, "")) for o in operands):
            wide.append((m.group(1), m.group(3), scope.group(1)))
    assert not wide, wide
    scattered = [math.prod(map(int, dims.split(",")))
                 for dims in re.findall(r"= \w+\[([\d,]*)\]\S* scatter\(",
                                        entry)]
    assert max(scattered, default=0) < 1e5, scattered
