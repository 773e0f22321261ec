"""Main-path Pallas kernels compiled for a described TPU v5e at real widths.

Interpret mode (the CPU suite) cannot see what the Mosaic compiler refuses:
tiles not aligned to the (8, 128) vector layout, more VMEM than a kernel may
use, loads from HBM refs, value ops Mosaic has no lowering for.  These tests
lower and compile each main-path kernel for one chip of a described
``v5e:2x2`` topology — no chip is attached; nothing runs — at the widths the
serve engine and the paper path use (deepseek-7b: 32 heads of 128, d_ff
11008; VGG stage at 224x224 ImageNet resolution).  The serve engine's
whole mixed step is compiled too, to read what the compiler made of the
KV pool around the kernel.
"""
from __future__ import annotations

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.configs import get_config
from repro.configs.base import RuntimeConfig
from repro.core import ir
from repro.kernels.attention import decode
from repro.kernels.fused_stack import nhwc, nhwc_bwd
from repro.kernels.fused_stack import ops as fused_ops
from repro.kernels.swiglu import swiglu
from repro.launch import engine as engine_mod
from repro.layers import stacks
from repro.models import lm

SLOTS, HEADS, HEAD_DIM, D_FF = 8, 32, 128, 11008   # deepseek-7b serving
MAX_LEN, BLOCK = 2048, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compile_for(one_chip, no_compile_cache, monkeypatch):
    """Lower and compile ``fn`` for the described chip with the kernels in
    compiled (Mosaic) mode; returns the HLO text.  Each argument is a
    ``(shape, dtype)`` pair or a pytree of ``jax.ShapeDtypeStruct``; a
    ``fn`` that is jitted already keeps its own options (donation)."""
    # The backend here is the CPU, which derives interpret mode: steer the
    # kernels to the chip's compiled path for this test only.
    monkeypatch.setattr(kernels, "pallas_interpret", lambda: False)

    def on_chip(a):
        if isinstance(a, tuple) and len(a) == 2 and isinstance(a[0], tuple):
            return jax.ShapeDtypeStruct(*a, sharding=one_chip)
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), a)

    def compile_(fn, *shapes):
        args = [on_chip(a) for a in shapes]
        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        text = jitted.lower(*args).compile().as_text()
        assert "tpu_custom_call" in text      # the Mosaic kernel is there
        return text
    return compile_


@pytest.mark.parametrize("kv_heads", [HEADS, 8], ids=["mha", "gqa"])
def test_paged_decode_compiles(compile_for, kv_heads):
    """deepseek-7b widths (32 query heads, 32 KV heads) and a GQA width
    (32 / 8): each compiles to exactly one paged-decode kernel call."""
    n_blocks = SLOTS * MAX_LEN // BLOCK
    text = compile_for(decode.paged_flash_decode,
                       ((SLOTS, HEADS, 1, HEAD_DIM), jnp.bfloat16),
                       ((n_blocks, kv_heads, BLOCK, HEAD_DIM), jnp.bfloat16),
                       ((n_blocks, kv_heads, BLOCK, HEAD_DIM), jnp.bfloat16),
                       ((SLOTS, MAX_LEN // BLOCK), jnp.int32),
                       ((SLOTS,), jnp.int32))
    bodies = _kernel_bodies(text)
    assert len(bodies) == 1 and b"paged_decode_kernel" in bodies[0]


def test_dense_decode_compiles(compile_for):
    compile_for(decode.flash_decode,
                ((SLOTS, HEADS, 1, HEAD_DIM), jnp.bfloat16),
                ((SLOTS, HEADS, MAX_LEN, HEAD_DIM), jnp.bfloat16),
                ((SLOTS, HEADS, MAX_LEN, HEAD_DIM), jnp.bfloat16),
                ((SLOTS,), jnp.int32))


def test_swiglu_compiles_at_d_ff_11008(compile_for):
    compile_for(swiglu.swiglu_fwd, ((2048, D_FF), jnp.bfloat16),
                ((2048, D_FF), jnp.bfloat16))


def test_rows_glu_stack_bf16_fwd_and_bwd_compile(compile_for):
    prog = stacks.glu_program("silu")

    def loss(gate, up):
        y = fused_ops.fused_stack_apply(prog, {"gate": gate, "up": up}, {},
                                        mode="brainslug")["y"]
        return jnp.sum(y.astype(jnp.float32))

    compile_for(jax.grad(loss, argnums=(0, 1)),
                ((2048, D_FF), jnp.bfloat16), ((2048, D_FF), jnp.bfloat16))


def _vgg_stage() -> ir.StackProgram:
    """One VGG stage's depth-first stack after its conv: BN, ReLU, 2x2/2
    max-pool — what ``optimize(cnn.vgg_fn, ...)`` collapses."""
    return ir.StackProgram(
        name="vgg_stage", inputs=("x",), outputs=("m",), layout="nhwc",
        ops=(ir.OpNode(ir.OpKind.AFFINE, "bn", ("x",), "b",
                       params=("s", "o")),
             ir.OpNode(ir.OpKind.EW_UNARY, "relu", ("b",), "r", fn="relu"),
             ir.OpNode(ir.OpKind.POOL2D, "mp", ("r",), "m", fn="max",
                       attrs={"window": (2, 2), "stride": (2, 2),
                              "padding": (0, 0)})))


IMAGE = ((1, 224, 224, 64), jnp.float32)
CHANNEL = ((64,), jnp.float32)


def test_nhwc_forward_compiles(compile_for):
    prog = _vgg_stage()
    compile_for(lambda x, s, o: nhwc.fused_nhwc_call(prog, x, {"s": s,
                                                              "o": o}),
                IMAGE, CHANNEL, CHANNEL)


def test_nhwc_backward_compiles(compile_for):
    prog = _vgg_stage()
    compile_for(lambda x, s, o, g: nhwc_bwd.fused_nhwc_bwd_call(
                    prog, x, {}, {"s": s, "o": o}, g),
                IMAGE, CHANNEL, CHANNEL, ((1, 112, 112, 64), jnp.float32))


def test_engine_step_keeps_the_bf16_pool_in_bf16(compile_for):
    """The serve engine's mixed step at deepseek-7b widths (2 layers, 8
    slots, a small paged pool): every buffer the size of one layer's pool
    or of the layer-stacked pool is bfloat16, the model's dtype, and no
    convert reads one: the per-token scatter, the layer scan's slices and
    the kernel's operands all stay in the pool's dtype."""
    cfg = dataclasses.replace(get_config("deepseek-7b"), n_layers=2)
    assert cfg.dtype == "bfloat16"
    mb = 9                                    # blocks per slot: 144 tokens
    n_blocks = SLOTS * mb
    rt = RuntimeConfig(mode="brainslug", kv_layout="paged",
                       kv_block_size=BLOCK)
    eng = engine_mod.Engine(
        cfg, jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg)[0]),
        rt, slots=SLOTS, max_len=mb * BLOCK)
    vec = ((SLOTS,), jnp.int32)
    text = compile_for(eng._step, eng.params, jax.eval_shape(eng.init_cache),
                       ((SLOTS, mb), jnp.int32), ((SLOTS, 8), jnp.int32),
                       vec, vec, vec, ((SLOTS,), jnp.float32),
                       ((2,), jnp.uint32))
    pool = n_blocks * cfg.n_kv_heads * BLOCK * cfg.head_dim
    sizes = (pool, cfg.n_layers * pool)

    def pool_sized(dims: str) -> bool:
        return math.prod(int(d) for d in dims.split(",") if d) in sizes

    dtypes = {dt for dt, dims in re.findall(r"\b(\w+)\[([\d,]*)\]", text)
              if pool_sized(dims)}
    assert dtypes == {"bf16"}
    assert not [dims for dims in re.findall(
        r"convert\(\w+\[([\d,]*)\]", text) if pool_sized(dims)]


def _kernel_bodies(text: str) -> list[bytes]:
    """The Mosaic body of every Pallas kernel call in a compiled program."""
    import base64

    return [base64.b64decode(m) for m in re.findall(
        r'custom_call_target="tpu_custom_call".*?"body":"([A-Za-z0-9+/=]+)"',
        text)]


def _rows_glu_grad(gate, up):
    prog = stacks.glu_program("silu")
    return jax.value_and_grad(lambda g, u: jnp.sum(
        fused_ops.fused_stack_apply(prog, {"gate": g, "up": u}, {},
                                    mode="brainslug")["y"].astype(
            jnp.float32)), argnums=(0, 1))(gate, up)


@pytest.mark.parametrize("fn, shapes, names", [
    (decode.paged_flash_decode,
     [((SLOTS, HEADS, 1, HEAD_DIM), jnp.bfloat16),
      ((256, HEADS, BLOCK, HEAD_DIM), jnp.bfloat16),
      ((256, HEADS, BLOCK, HEAD_DIM), jnp.bfloat16),
      ((SLOTS, 32), jnp.int32), ((SLOTS,), jnp.int32)],
     {b"paged_decode_kernel"}),
    (lambda x, s, o: nhwc.fused_nhwc_call(_vgg_stage(), x, {"s": s, "o": o}),
     [IMAGE, CHANNEL, CHANNEL], {b"nhwc_fwd_kernel"}),
    (lambda x, s, o, g: nhwc_bwd.fused_nhwc_bwd_call(
        _vgg_stage(), x, {}, {"s": s, "o": o}, g),
     [IMAGE, CHANNEL, CHANNEL, ((1, 112, 112, 64), jnp.float32)],
     {b"nhwc_bwd_kernel"}),
    (_rows_glu_grad, [((2048, D_FF), jnp.bfloat16)] * 2,
     {b"rows_fwd_kernel", b"rows_bwd_kernel"}),
], ids=["paged_decode", "nhwc_fwd", "nhwc_bwd", "rows_fwd_bwd"])
def test_compiled_kernels_carry_their_names(compile_for, fn, shapes, names):
    """Each ``pallas_call`` names its kernel; the name reaches the Mosaic
    body, where a trace reader can find it.  Generated backwards keep the
    ``_bwd_kernel`` ending that tells them apart from forwards."""
    bodies = _kernel_bodies(compile_for(fn, *shapes))
    assert bodies
    found = {n for n in names if any(n in b for b in bodies)}
    assert found == names
