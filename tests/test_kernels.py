"""Per-kernel shape/dtype sweeps: every Pallas kernel (interpret mode)
against its pure-jnp ref.py oracle, forward and backward."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ir
from repro.kernels.attention import ops as attn_ops
from repro.kernels.attention import ref as attn_ref
from repro.kernels.fused_stack import nhwc as fs_nhwc
from repro.kernels.fused_stack import ops as fs_ops
from repro.kernels.fused_stack import ref as fs_ref
from repro.kernels.fused_stack import rows as fs_rows
from repro.kernels.rmsnorm import ops as rms_ops
from repro.kernels.rmsnorm import ref as rms_ref
from repro.kernels.ssd import chunked as ssd_chunked
from repro.kernels.ssd import ops as ssd_ops
from repro.kernels.ssd import ref as ssd_ref
from repro.kernels.swiglu import ops as sw_ops
from repro.kernels.swiglu import ref as sw_ref

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _randn(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape, np.float32)).astype(dtype)


def _close(a, b, dtype):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), **TOL[dtype])


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

class TestRMSNorm:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("shape", [(4, 128), (2, 7, 384), (1, 1, 256),
                                       (3, 129, 512)])
    @pytest.mark.parametrize("with_residual", [True, False])
    def test_fwd_matches_ref(self, rng, dtype, shape, with_residual):
        x = _randn(rng, shape, dtype)
        res = _randn(rng, shape, dtype) if with_residual else None
        scale = _randn(rng, shape[-1:], dtype)
        y, h = rms_ops.rmsnorm(x, scale, res, 1e-6, 64)
        yr, hr = rms_ref.rmsnorm_ref(x, scale, res, eps=1e-6)
        _close(y, yr, dtype)
        _close(h, hr, dtype)

    def test_grads_match_ref(self, rng):
        x = _randn(rng, (4, 64), jnp.float32)
        res = _randn(rng, (4, 64), jnp.float32)
        scale = _randn(rng, (64,), jnp.float32)

        def f_kernel(x, s, r):
            y, h = rms_ops.rmsnorm(x, s, r, 1e-6, 8)
            return jnp.sum(y * 1.3 + h * 0.7)

        def f_ref(x, s, r):
            y, h = rms_ref.rmsnorm_ref(x, s, r, eps=1e-6)
            return jnp.sum(y * 1.3 + h * 0.7)

        gk = jax.grad(f_kernel, argnums=(0, 1, 2))(x, scale, res)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(x, scale, res)
        for a, b in zip(gk, gr):
            _close(a, b, jnp.float32)


# ---------------------------------------------------------------------------
# swiglu
# ---------------------------------------------------------------------------

class TestSwiGLU:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("act", ["silu", "gelu", "squared_relu"])
    @pytest.mark.parametrize("shape", [(8, 128), (2, 5, 256), (1, 130, 384)])
    def test_fwd_matches_ref(self, rng, dtype, act, shape):
        g = _randn(rng, shape, dtype)
        u = _randn(rng, shape, dtype)
        y = sw_ops.swiglu(g, u, act, 64)
        _close(y, sw_ref.swiglu_ref(g, u, act=act), dtype)

    def test_grads_match_ref(self, rng):
        g = _randn(rng, (6, 96), jnp.float32)
        u = _randn(rng, (6, 96), jnp.float32)
        gk = jax.grad(lambda a, b: jnp.sum(sw_ops.swiglu(a, b, "silu", 8)),
                      argnums=(0, 1))(g, u)
        gr = jax.grad(lambda a, b: jnp.sum(sw_ref.swiglu_ref(a, b)),
                      argnums=(0, 1))(g, u)
        for a, b in zip(gk, gr):
            _close(a, b, jnp.float32)


# ---------------------------------------------------------------------------
# attention (flash fwd + decode)
# ---------------------------------------------------------------------------

class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("h,g", [(4, 4), (8, 2), (4, 1)])
    @pytest.mark.parametrize("sq,block", [(64, 32), (100, 32), (128, 128),
                                          (33, 16)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_fwd_matches_ref(self, rng, dtype, h, g, sq, block, causal):
        b, d = 2, 32
        q = _randn(rng, (b, h, sq, d), dtype)
        k = _randn(rng, (b, g, sq, d), dtype)
        v = _randn(rng, (b, g, sq, d), dtype)
        o = attn_ops.flash_attention(q, k, v, causal, block, block)
        oref = attn_ref.attention_ref(q, k, v, causal=causal)
        _close(o, oref, dtype)

    def test_grads_match_ref(self, rng):
        b, h, g, s, d = 1, 4, 2, 48, 16
        q = _randn(rng, (b, h, s, d), jnp.float32)
        k = _randn(rng, (b, g, s, d), jnp.float32)
        v = _randn(rng, (b, g, s, d), jnp.float32)
        gk = jax.grad(lambda *a: jnp.sum(
            attn_ops.flash_attention(*a, True, 16, 16)),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: jnp.sum(
            attn_ref.attention_ref(*a, causal=True)),
            argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gk, gr):
            _close(a, b_, jnp.float32)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("s,block_k", [(128, 64), (100, 64), (512, 512)])
    def test_decode_matches_ref(self, rng, dtype, s, block_k):
        b, h, g, d = 3, 8, 2, 32
        q = _randn(rng, (b, h, 1, d), dtype)
        k = _randn(rng, (b, g, s, d), dtype)
        v = _randn(rng, (b, g, s, d), dtype)
        lengths = jnp.asarray(rng.integers(1, s + 1, (b,)), jnp.int32)
        o = attn_ops.flash_decode(q, k, v, lengths, block_k=block_k)
        oref = attn_ref.decode_ref(q, k, v, lengths)
        _close(o, oref, dtype)

    def test_decode_ignores_tail_garbage(self, rng):
        """Cache positions beyond `length` must not affect the output."""
        b, h, g, s, d = 1, 2, 1, 64, 16
        q = _randn(rng, (b, h, 1, d), jnp.float32)
        k = _randn(rng, (b, g, s, d), jnp.float32)
        v = _randn(rng, (b, g, s, d), jnp.float32)
        lengths = jnp.asarray([40], jnp.int32)
        o1 = attn_ops.flash_decode(q, k, v, lengths)
        k2 = k.at[:, :, 40:].set(99.0)
        v2 = v.at[:, :, 40:].set(-99.0)
        o2 = attn_ops.flash_decode(q, k2, v2, lengths)
        _close(o1, o2, jnp.float32)

    @pytest.mark.parametrize("block_k", [32, 128])
    def test_decode_zero_length_emits_zeros(self, rng, block_k):
        """A fully-masked slot (length 0 — a freed continuous-batching
        slot) attends over zero keys: the all-masked online softmax must
        produce exactly zero output, not NaN and not a stale-cache
        average.  Ref and kernel implement the same convention."""
        b, h, g, s, d = 2, 4, 2, 128, 16
        q = _randn(rng, (b, h, 1, d), jnp.float32)
        k = _randn(rng, (b, g, s, d), jnp.float32)
        v = _randn(rng, (b, g, s, d), jnp.float32)
        lengths = jnp.asarray([0, s // 2], jnp.int32)
        o = attn_ops.flash_decode(q, k, v, lengths, block_k=block_k)
        assert bool(jnp.isfinite(o).all())
        np.testing.assert_array_equal(np.asarray(o[0]),
                                      np.zeros_like(np.asarray(o[0])))
        oref = attn_ref.decode_ref(q, k, v, lengths)
        np.testing.assert_array_equal(np.asarray(oref[0]),
                                      np.zeros_like(np.asarray(oref[0])))
        _close(o, oref, jnp.float32)

    def test_decode_full_length_no_tail_mask(self, rng):
        """lengths == S: every cache position is valid — the kernel must
        match an unmasked softmax over the whole cache exactly."""
        b, h, g, s, d = 2, 4, 2, 96, 16
        q = _randn(rng, (b, h, 1, d), jnp.float32)
        k = _randn(rng, (b, g, s, d), jnp.float32)
        v = _randn(rng, (b, g, s, d), jnp.float32)
        lengths = jnp.full((b,), s, jnp.int32)
        o = attn_ops.flash_decode(q, k, v, lengths, block_k=32)
        oref = attn_ref.attention_ref(q, k, v, causal=False)  # no length op
        _close(o, oref, jnp.float32)

    def test_decode_ragged_lengths_parity(self, rng):
        """Per-slot ragged lengths in one dispatch (the continuous-batching
        batch shape): every edge in one batch — empty slot, single token,
        mid-cache, full cache."""
        h, g, s, d = 4, 2, 64, 16
        lengths = jnp.asarray([0, 1, 37, s], jnp.int32)
        b = lengths.shape[0]
        q = _randn(rng, (b, h, 1, d), jnp.float32)
        k = _randn(rng, (b, g, s, d), jnp.float32)
        v = _randn(rng, (b, g, s, d), jnp.float32)
        o = attn_ops.flash_decode(q, k, v, lengths, block_k=32)
        oref = attn_ref.decode_ref(q, k, v, lengths)
        assert bool(jnp.isfinite(o).all())
        _close(o, oref, jnp.float32)


# ---------------------------------------------------------------------------
# SSD (mamba2): pallas kernel vs chunked-jnp vs sequential oracle
# ---------------------------------------------------------------------------

def _ssd_operands(rng, b, s, h, p, n, dtype):
    x = _randn(rng, (b, s, h, p), dtype)
    dt = jax.nn.softplus(_randn(rng, (b, s, h), jnp.float32))
    A = -jnp.exp(0.5 * _randn(rng, (h,), jnp.float32))
    B = _randn(rng, (b, s, n), dtype)
    C = _randn(rng, (b, s, n), dtype)
    D = jnp.ones((h,), jnp.float32)
    return x, dt, A, B, C, D


class TestSSD:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("s,chunk", [(64, 16), (50, 16), (128, 64)])
    def test_chunked_matches_sequential(self, rng, dtype, s, chunk):
        x, dt, A, B, C, D = _ssd_operands(rng, 2, s, 3, 16, 8, dtype)
        y = ssd_chunked.ssd_chunked(x, dt, A, B, C, D, chunk=chunk)
        yr = ssd_ref.ssd_ref(x, dt, A, B, C, D)
        _close(y, yr, dtype)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("s,chunk", [(64, 16), (96, 32)])
    def test_pallas_matches_sequential(self, rng, dtype, s, chunk):
        x, dt, A, B, C, D = _ssd_operands(rng, 2, s, 3, 16, 8, dtype)
        y = ssd_ops.ssd(x, dt, A, B, C, D, chunk)
        yr = ssd_ref.ssd_ref(x, dt, A, B, C, D)
        _close(y, yr, dtype)

    def test_pallas_grads_match_ref(self, rng):
        x, dt, A, B, C, D = _ssd_operands(rng, 1, 32, 2, 8, 4, jnp.float32)
        gk = jax.grad(lambda *a: jnp.sum(ssd_ops.ssd(*a, 16)),
                      argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C, D)
        gr = jax.grad(lambda *a: jnp.sum(ssd_ref.ssd_ref(*a)),
                      argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C, D)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-4)

    def test_decode_steps_match_full_sequence(self, rng):
        """Running the recurrent decode step token-by-token must equal the
        full-sequence chunked path (prefill/decode consistency)."""
        b, s, h, p, n = 2, 24, 2, 8, 4
        x, dt, A, B, C, D = _ssd_operands(rng, b, s, h, p, n, jnp.float32)
        y_full = ssd_chunked.ssd_chunked(x, dt, A, B, C, D, chunk=8)
        state = jnp.zeros((b, h, n, p), jnp.float32)
        ys = []
        for t in range(s):
            state, y_t = ssd_chunked.ssd_decode_step(
                state, x[:, t], dt[:, t], A, B[:, t], C[:, t], D)
            ys.append(y_t)
        y_steps = jnp.stack(ys, axis=1)
        _close(y_steps, y_full, jnp.float32)


# ---------------------------------------------------------------------------
# fused_stack generic kernels (rows + nhwc)
# ---------------------------------------------------------------------------

def _rows_program():
    return ir.StackProgram(
        name="glu_norm", inputs=("g", "u"), outputs=("o",), layout="rows",
        ops=(
            ir.OpNode(ir.OpKind.EW_UNARY, "act", ("g",), "a", fn="silu"),
            ir.OpNode(ir.OpKind.EW_BINARY, "mul", ("a", "u"), "m", fn="mul"),
            ir.OpNode(ir.OpKind.ROW_NORM, "norm", ("m",), "o",
                      params=("scale",), attrs={"norm": "rms", "eps": 1e-6}),
        ))


class TestFusedRows:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("shape,tile", [((4, 128), 8), ((2, 9, 64), 16),
                                            ((257, 128), 64)])
    def test_matches_ref(self, rng, dtype, shape, tile):
        prog = _rows_program()
        g = _randn(rng, shape, dtype)
        u = _randn(rng, shape, dtype)
        scale = _randn(rng, shape[-1:], dtype)
        out = fs_rows.fused_rows_call(prog, {"g": g, "u": u},
                                      {"scale": scale}, tile_rows=tile)
        want = fs_ref.fused_stack_ref(prog, {"g": g, "u": u},
                                      {"scale": scale})
        _close(out["o"], want["o"], dtype)

    def test_dispatcher_modes_agree(self, rng):
        prog = _rows_program()
        g = _randn(rng, (6, 96), jnp.float32)
        u = _randn(rng, (6, 96), jnp.float32)
        scale = jnp.ones((96,), jnp.float32)
        outs = [fs_ops.fused_stack_apply(prog, {"g": g, "u": u},
                                         {"scale": scale}, mode=m)["o"]
                for m in fs_ops.MODES]
        _close(outs[0], outs[1], jnp.float32)
        _close(outs[0], outs[2], jnp.float32)

    def test_brainslug_grads_match_xla(self, rng):
        prog = _rows_program()
        g = _randn(rng, (4, 64), jnp.float32)
        u = _randn(rng, (4, 64), jnp.float32)
        scale = _randn(rng, (64,), jnp.float32)

        def loss(mode, g_, u_, s_):
            out = fs_ops.fused_stack_apply(prog, {"g": g_, "u": u_},
                                           {"scale": s_}, mode=mode)
            return jnp.sum(jnp.square(out["o"]))

        gb = jax.grad(lambda *a: loss("brainslug", *a),
                      argnums=(0, 1, 2))(g, u, scale)
        gx = jax.grad(lambda *a: loss("xla", *a),
                      argnums=(0, 1, 2))(g, u, scale)
        for a, b in zip(gb, gx):
            _close(a, b, jnp.float32)


def _pool_chain_program(n_blocks=2, window=(3, 3), stride=(1, 1),
                        padding=(1, 1)):
    ops = []
    v = "x"
    for i in range(n_blocks):
        ops += [
            ir.OpNode(ir.OpKind.POOL2D, f"p{i}", (v,), f"pp{i}", fn="max",
                      attrs={"window": window, "stride": stride,
                             "padding": padding}),
            ir.OpNode(ir.OpKind.AFFINE, f"bn{i}", (f"pp{i}",), f"b{i}",
                      params=(f"s{i}", f"o{i}")),
            ir.OpNode(ir.OpKind.EW_UNARY, f"r{i}", (f"b{i}",), f"v{i}",
                      fn="relu"),
        ]
        v = f"v{i}"
    return ir.StackProgram(name="chain", inputs=("x",), outputs=(v,),
                           ops=tuple(ops), layout="nhwc")


class TestFusedNHWC:
    @pytest.mark.parametrize("dtype", [jnp.float32])
    @pytest.mark.parametrize("hw,tile", [((16, 16), 8), ((17, 13), 4),
                                         ((8, 8), 8)])
    @pytest.mark.parametrize("blocks", [1, 3])
    def test_padded_pool_chain_matches_ref(self, rng, dtype, hw, tile,
                                           blocks):
        prog = _pool_chain_program(blocks)
        x = _randn(rng, (2, *hw, 8), dtype)
        params = {}
        for i in range(blocks):
            params[f"s{i}"] = 1.0 + 0.1 * _randn(rng, (8,), dtype)
            params[f"o{i}"] = 0.1 * _randn(rng, (8,), dtype)
        y = fs_nhwc.fused_nhwc_call(prog, x, params, tile_out_h=tile,
                                    tile_out_w=tile)
        want = fs_ref.fused_stack_ref(prog, {"x": x}, params)
        _close(y, want[prog.outputs[0]], dtype)

    @pytest.mark.parametrize("window,stride,padding", [
        ((2, 2), (2, 2), (0, 0)),       # downsampling, no halo
        ((3, 3), (2, 2), (1, 1)),       # strided overlap
        ((3, 3), (1, 1), (1, 1)),       # stride-1 halo growth
    ])
    def test_pool_geometries(self, rng, window, stride, padding):
        prog = _pool_chain_program(2, window, stride, padding)
        x = _randn(rng, (1, 20, 20, 8), jnp.float32)
        params = {f"s{i}": jnp.ones((8,)) for i in range(2)}
        params.update({f"o{i}": jnp.zeros((8,)) for i in range(2)})
        y = fs_nhwc.fused_nhwc_call(prog, x, params, tile_out_h=4,
                                    tile_out_w=4)
        want = fs_ref.fused_stack_ref(prog, {"x": x}, params)
        _close(y, want[prog.outputs[0]], jnp.float32)

    def test_avg_pool_padding_semantics(self, rng):
        """avg pooling counts padded zeros (count_include_pad) — the masked
        kernel must reproduce that exactly at the borders."""
        prog = ir.StackProgram(
            name="avg", inputs=("x",), outputs=("y",), layout="nhwc",
            ops=(ir.OpNode(ir.OpKind.POOL2D, "p", ("x",), "y", fn="avg",
                           attrs={"window": (3, 3), "stride": (1, 1),
                                  "padding": (1, 1)}),))
        x = jnp.ones((1, 5, 5, 8), jnp.float32)
        y = fs_nhwc.fused_nhwc_call(prog, x, {}, tile_out_h=4, tile_out_w=4)
        want = fs_ref.fused_stack_ref(prog, {"x": x}, {})["y"]
        _close(y, want, jnp.float32)
        # corner value must be 4/9, not 1 (padding included in the count)
        assert abs(float(y[0, 0, 0, 0]) - 4.0 / 9.0) < 1e-6
