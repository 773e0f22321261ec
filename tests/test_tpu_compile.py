"""Main-path Pallas kernels compiled for a described TPU v5e at real widths.

Interpret mode (the CPU suite) cannot see what the Mosaic compiler refuses:
tiles not aligned to the (8, 128) vector layout, more VMEM than a kernel may
use, loads from HBM refs, value ops Mosaic has no lowering for.  These tests
lower and compile each main-path kernel for one chip of a described
``v5e:2x2`` topology — no chip is attached; nothing runs — at the widths the
serve engine and the paper path use (deepseek-7b: 32 heads of 128, d_ff
11008; VGG stage at 224x224 ImageNet resolution).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.core import ir
from repro.kernels.attention import decode
from repro.kernels.fused_stack import nhwc, nhwc_bwd
from repro.kernels.fused_stack import ops as fused_ops
from repro.kernels.swiglu import swiglu
from repro.layers import stacks

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
    compiled (Mosaic) mode; returns the HLO text."""
    # The backend here is the CPU, which derives interpret mode: steer the
    # kernels to the chip's compiled path for this test only.
    monkeypatch.setattr(kernels, "pallas_interpret", lambda: False)

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text      # the Mosaic kernel is there
        return text
    return compile_


def test_paged_decode_compiles(compile_for):
    n_blocks = SLOTS * MAX_LEN // BLOCK
    compile_for(decode.paged_flash_decode,
                ((SLOTS, HEADS, 1, HEAD_DIM), jnp.bfloat16),
                ((n_blocks, HEADS, BLOCK, HEAD_DIM), jnp.bfloat16),
                ((n_blocks, HEADS, BLOCK, HEAD_DIM), jnp.bfloat16),
                ((SLOTS, MAX_LEN // BLOCK), jnp.int32),
                ((SLOTS,), jnp.int32))


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
