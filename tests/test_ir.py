"""Unit tests for the BrainSlug op IR (randomized properties: see
``test_properties.py``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ir


def _addnorm_program():
    return ir.StackProgram(
        name="t", inputs=("x", "res"), outputs=("y",), layout="rows",
        ops=(
            ir.OpNode(ir.OpKind.EW_BINARY, "add", ("x", "res"), "h",
                      fn="add"),
            ir.OpNode(ir.OpKind.ROW_NORM, "norm", ("h",), "y",
                      params=("scale",), attrs={"norm": "rms", "eps": 1e-6}),
        ))


class TestValidation:
    def test_undefined_input_rejected(self):
        with pytest.raises(ValueError, match="undefined"):
            ir.StackProgram(
                name="bad", inputs=("x",), outputs=("y",), layout="rows",
                ops=(ir.OpNode(ir.OpKind.EW_UNARY, "r", ("zz",), "y",
                               fn="relu"),))

    def test_redefinition_rejected(self):
        with pytest.raises(ValueError, match="redefined"):
            ir.StackProgram(
                name="bad", inputs=("x",), outputs=("x",), layout="rows",
                ops=(ir.OpNode(ir.OpKind.EW_UNARY, "r", ("x",), "x",
                               fn="relu"),))

    def test_unknown_fn_rejected(self):
        with pytest.raises(ValueError, match="unknown unary"):
            ir.StackProgram(
                name="bad", inputs=("x",), outputs=("y",), layout="rows",
                ops=(ir.OpNode(ir.OpKind.EW_UNARY, "r", ("x",), "y",
                               fn="nope"),))

    def test_missing_output_rejected(self):
        with pytest.raises(ValueError, match="never defined"):
            ir.StackProgram(name="bad", inputs=("x",), outputs=("q",),
                            layout="rows", ops=())

    def test_pool_missing_attrs_rejected(self):
        with pytest.raises(ValueError, match="missing attr"):
            ir.StackProgram(
                name="bad", inputs=("x",), outputs=("y",), layout="nhwc",
                ops=(ir.OpNode(ir.OpKind.POOL2D, "p", ("x",), "y",
                               fn="max", attrs={"window": (2, 2)}),))


class TestInterpreter:
    def test_addnorm_matches_manual(self, rng):
        prog = _addnorm_program()
        x = jnp.asarray(rng.standard_normal((4, 16), np.float32))
        res = jnp.asarray(rng.standard_normal((4, 16), np.float32))
        scale = jnp.asarray(rng.standard_normal((16,), np.float32))
        out = ir.run_program(prog, {"x": x, "res": res}, {"scale": scale})
        h = x + res
        want = h * jax.lax.rsqrt(
            jnp.mean(jnp.square(h), -1, keepdims=True) + 1e-6) * scale
        np.testing.assert_allclose(np.asarray(out["y"]), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

    def test_barrier_mode_same_result(self, rng):
        prog = _addnorm_program()
        x = jnp.asarray(rng.standard_normal((4, 16), np.float32))
        res = jnp.asarray(rng.standard_normal((4, 16), np.float32))
        scale = jnp.ones((16,), jnp.float32)
        a = ir.run_program(prog, {"x": x, "res": res}, {"scale": scale})
        b = jax.jit(lambda e, p: ir.run_program(prog, e, p, barrier=True))(
            {"x": x, "res": res}, {"scale": scale})
        np.testing.assert_allclose(np.asarray(a["y"]), np.asarray(b["y"]),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("fn", ["max", "avg"])
    @pytest.mark.parametrize("window,stride,padding", [
        ((2, 2), (2, 2), (0, 0)), ((3, 3), (1, 1), (1, 1)),
        ((3, 2), (2, 1), (1, 0)),
    ])
    def test_pool_matches_reduce_window(self, rng, fn, window, stride,
                                        padding):
        op = ir.OpNode(ir.OpKind.POOL2D, "p", ("x",), "y", fn=fn,
                       attrs={"window": window, "stride": stride,
                              "padding": padding})
        x = jnp.asarray(rng.standard_normal((2, 9, 8, 3), np.float32))
        y = ir.apply_op(op, {"x": x}, {})
        n, h, w, c = x.shape
        oh = ir.pool_out_extent(h, window[0], stride[0], padding[0])
        ow = ir.pool_out_extent(w, window[1], stride[1], padding[1])
        assert y.shape == (n, oh, ow, c)
        # brute-force oracle
        ph, pw = padding
        fill = -np.inf if fn == "max" else 0.0
        xp = np.pad(np.asarray(x), ((0, 0), (ph, ph), (pw, pw), (0, 0)),
                    constant_values=fill)
        want = np.zeros((n, oh, ow, c), np.float32)
        for i in range(oh):
            for j in range(ow):
                win = xp[:, i * stride[0]: i * stride[0] + window[0],
                         j * stride[1]: j * stride[1] + window[1], :]
                if fn == "max":
                    want[:, i, j] = win.max(axis=(1, 2))
                else:
                    want[:, i, j] = win.sum(axis=(1, 2)) / (window[0]
                                                            * window[1])
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-5)


class TestShapes:
    def test_infer_shapes_pool_chain(self):
        ops = (
            ir.OpNode(ir.OpKind.POOL2D, "p0", ("x",), "a", fn="max",
                      attrs={"window": (2, 2), "stride": (2, 2),
                             "padding": (0, 0)}),
            ir.OpNode(ir.OpKind.EW_UNARY, "r", ("a",), "b", fn="relu"),
            ir.OpNode(ir.OpKind.POOL2D, "p1", ("b",), "y", fn="avg",
                      attrs={"window": (3, 3), "stride": (1, 1),
                             "padding": (1, 1)}),
        )
        prog = ir.StackProgram(name="t", inputs=("x",), outputs=("y",),
                               ops=ops, layout="nhwc")
        shapes = ir.infer_shapes(prog, {"x": (2, 16, 12, 8)})
        assert shapes["a"] == (2, 8, 6, 8)
        assert shapes["y"] == (2, 8, 6, 8)

    def test_signature_reuse_key(self):
        assert _addnorm_program().signature() == \
            _addnorm_program().signature()
        other = ir.StackProgram(
            name="t2", inputs=("x", "res"), outputs=("y",), layout="rows",
            ops=(
                ir.OpNode(ir.OpKind.EW_BINARY, "add", ("x", "res"), "h",
                          fn="add"),
                ir.OpNode(ir.OpKind.ROW_NORM, "norm", ("h",), "y",
                          params=("scale",),
                          attrs={"norm": "layer", "eps": 1e-6}),
            ))
        assert other.signature() != _addnorm_program().signature()
