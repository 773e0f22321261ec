"""Tests for the depth-first backward: the per-op VJP rule table against
``jax.vjp`` of the interpreter (oracle), the generated rows backward kernel,
gradient parity of the brainslug executor vs the xla reference (incl.
multi-sequence splits), generate-once executor reuse, and the joint fwd+bwd
VMEM accounting."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api, autodiff, codegen, collapse, ir, resource
from repro.kernels.fused_stack import ops as fs_ops
from repro.kernels.fused_stack import rows_bwd


@pytest.fixture(autouse=True)
def _clear_caches():
    codegen.clear_cache()
    fs_ops.STATS.reset()
    yield


def _randn(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape, np.float32)).astype(dtype)


def _forward_env(program, inputs, params):
    env = dict(inputs)
    for op in program.ops:
        env[op.output] = ir.apply_op(op, env, params)
    return env


def _oracle_check(program, inputs, params, rng, rtol=1e-4, atol=1e-5):
    """program_vjp against jax.vjp of the interpreter, random cotangents."""
    in_names = list(program.inputs)
    p_names = list(program.param_names)

    def f(in_list, p_list):
        out = ir.run_program(program, dict(zip(in_names, in_list)),
                             dict(zip(p_names, p_list)))
        return tuple(out[v] for v in program.outputs)

    in_list = tuple(inputs[n] for n in in_names)
    p_list = tuple(params[p] for p in p_names)
    outs, vjp = jax.vjp(f, in_list, p_list)
    gouts = tuple(_randn(rng, o.shape, o.dtype) for o in outs)
    want_din, want_dp = vjp(gouts)

    env = _forward_env(program, inputs, params)
    got_din, got_dp = autodiff.program_vjp(
        program, env, params, dict(zip(program.outputs, gouts)))

    for n, want in zip(in_names, want_din):
        np.testing.assert_allclose(np.asarray(got_din[n]), np.asarray(want),
                                   rtol=rtol, atol=atol, err_msg=f"din[{n}]")
    for p, want in zip(p_names, want_dp):
        np.testing.assert_allclose(np.asarray(got_dp[p]), np.asarray(want),
                                   rtol=rtol, atol=atol, err_msg=f"dp[{p}]")


# ---------------------------------------------------------------------------
# Rule-table oracle tests.
# ---------------------------------------------------------------------------

class TestOpRules:
    @pytest.mark.parametrize("fn", sorted(autodiff._UNARY_DERIVS))
    def test_unary_rules(self, rng, fn):
        prog = ir.StackProgram(
            name="u", inputs=("x",), outputs=("y",), layout="rows",
            ops=(ir.OpNode(ir.OpKind.EW_UNARY, "f", ("x",), "y", fn=fn),))
        x = _randn(rng, (5, 32))
        _oracle_check(prog, {"x": x}, {}, rng)

    @pytest.mark.parametrize("fn", ["add", "sub", "mul", "div", "max", "min"])
    def test_binary_value_rules(self, rng, fn):
        prog = ir.StackProgram(
            name="b", inputs=("a", "b"), outputs=("y",), layout="rows",
            ops=(ir.OpNode(ir.OpKind.EW_BINARY, "f", ("a", "b"), "y",
                           fn=fn),))
        a = _randn(rng, (4, 16))
        b = _randn(rng, (4, 16)) + 3.0          # keep div well-conditioned
        _oracle_check(prog, {"a": a, "b": b}, {}, rng)

    @pytest.mark.parametrize("fn", ["add", "mul", "sub", "div"])
    def test_binary_param_rules(self, rng, fn):
        prog = ir.StackProgram(
            name="bp", inputs=("x",), outputs=("y",), layout="rows",
            ops=(ir.OpNode(ir.OpKind.EW_BINARY, "f", ("x",), "y", fn=fn,
                           params=("p",)),))
        x = _randn(rng, (6, 24))
        p = _randn(rng, (24,)) + 3.0
        _oracle_check(prog, {"x": x}, {"p": p}, rng)

    def test_same_value_consumed_twice(self, rng):
        prog = ir.StackProgram(
            name="xx", inputs=("x",), outputs=("y",), layout="rows",
            ops=(ir.OpNode(ir.OpKind.EW_BINARY, "sq", ("x", "x"), "y",
                           fn="mul"),))
        _oracle_check(prog, {"x": _randn(rng, (3, 8))}, {}, rng)

    def test_affine_rule(self, rng):
        prog = ir.StackProgram(
            name="aff", inputs=("x",), outputs=("y",), layout="rows",
            ops=(ir.OpNode(ir.OpKind.AFFINE, "a", ("x",), "y",
                           params=("s", "b")),))
        _oracle_check(prog, {"x": _randn(rng, (5, 16))},
                      {"s": _randn(rng, (16,)), "b": _randn(rng, (16,))}, rng)

    @pytest.mark.parametrize("norm,n_params", [("rms", 0), ("rms", 1),
                                               ("layer", 1), ("layer", 2)])
    def test_row_norm_rules(self, rng, norm, n_params):
        pnames = ("scale", "bias")[:n_params]
        prog = ir.StackProgram(
            name="n", inputs=("x",), outputs=("y",), layout="rows",
            ops=(ir.OpNode(ir.OpKind.ROW_NORM, "n", ("x",), "y",
                           params=pnames,
                           attrs={"norm": norm, "eps": 1e-6}),))
        params = {p: _randn(rng, (48,)) for p in pnames}
        _oracle_check(prog, {"x": _randn(rng, (6, 48))}, params, rng)

    def test_softmax_rule(self, rng):
        prog = ir.StackProgram(
            name="sm", inputs=("x",), outputs=("y",), layout="rows",
            ops=(ir.OpNode(ir.OpKind.ROW_SOFTMAX, "s", ("x",), "y"),))
        _oracle_check(prog, {"x": _randn(rng, (4, 32))}, {}, rng)

    def test_residual_chain_with_intermediate_output(self, rng):
        """addnorm shape: the residual sum h is both a program output and an
        internal consumer — cotangents must accumulate."""
        prog = ir.StackProgram(
            name="addnorm", inputs=("x", "res"), outputs=("y", "h"),
            layout="rows",
            ops=(
                ir.OpNode(ir.OpKind.EW_BINARY, "add", ("x", "res"), "h",
                          fn="add"),
                ir.OpNode(ir.OpKind.ROW_NORM, "norm", ("h",), "y",
                          params=("scale",),
                          attrs={"norm": "rms", "eps": 1e-6}),
            ))
        _oracle_check(prog, {"x": _randn(rng, (5, 64)),
                             "res": _randn(rng, (5, 64))},
                      {"scale": _randn(rng, (64,))}, rng)

    def test_supports(self):
        rows_prog = ir.StackProgram(
            name="ok", inputs=("x",), outputs=("y",), layout="rows",
            ops=(ir.OpNode(ir.OpKind.EW_UNARY, "r", ("x",), "y",
                           fn="relu"),))
        assert autodiff.supports(rows_prog)
        # pooling chains are differentiable since the nhwc backward landed
        pool_prog = ir.StackProgram(
            name="pool", inputs=("x",), outputs=("y",), layout="nhwc",
            ops=(ir.OpNode(ir.OpKind.POOL2D, "p", ("x",), "y", fn="max",
                           attrs={"window": (2, 2), "stride": (2, 2),
                                  "padding": (0, 0)}),))
        assert autodiff.supports(pool_prog)
        # opaque kinds still have no VJP rule
        opaque_prog = ir.StackProgram(
            name="no", inputs=("x",), outputs=("y",), layout="rows",
            ops=(ir.OpNode(ir.OpKind.MATMUL, "mm", ("x",), "y",
                           params=("w",),
                           attrs={"features_out": 8}),))
        assert not autodiff.supports(opaque_prog)


# ---------------------------------------------------------------------------
# Generated backward kernel vs oracle (incl. row padding).
# ---------------------------------------------------------------------------

def _glu_norm_program():
    return ir.StackProgram(
        name="glu_norm", inputs=("g", "u"), outputs=("o",), layout="rows",
        ops=(
            ir.OpNode(ir.OpKind.EW_UNARY, "act", ("g",), "a", fn="silu"),
            ir.OpNode(ir.OpKind.EW_BINARY, "mul", ("a", "u"), "m", fn="mul"),
            ir.OpNode(ir.OpKind.ROW_NORM, "norm", ("m",), "o",
                      params=("scale",), attrs={"norm": "rms", "eps": 1e-6}),
        ))


class TestRowsBwdKernel:
    @pytest.mark.parametrize("shape,tile", [((4, 128), 8), ((2, 9, 64), 16),
                                            ((257, 128), 64), ((7, 64), 4)])
    def test_kernel_matches_oracle(self, rng, shape, tile):
        prog = _glu_norm_program()
        inputs = {"g": _randn(rng, shape), "u": _randn(rng, shape)}
        params = {"scale": _randn(rng, shape[-1:])}
        gout = {"o": _randn(rng, shape)}

        dins, dps = rows_bwd.fused_rows_bwd_call(prog, inputs, params, gout,
                                                 tile_rows=tile)
        env = _forward_env(prog, inputs, params)
        want_din, want_dp = autodiff.program_vjp(prog, env, params, gout)
        for n in prog.inputs:
            np.testing.assert_allclose(np.asarray(dins[n]),
                                       np.asarray(want_din[n]),
                                       rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dps["scale"]),
                                   np.asarray(want_dp["scale"]),
                                   rtol=1e-4, atol=1e-5)

    def test_padded_rows_do_not_poison_param_grads(self, rng):
        """Zero-padded tail rows recompute 0/0 = NaN through a value/value
        div; the row-validity mask must keep that NaN out of the
        grid-summed parameter gradients."""
        prog = ir.StackProgram(
            name="div_norm", inputs=("a", "b"), outputs=("y",),
            layout="rows",
            ops=(
                ir.OpNode(ir.OpKind.EW_BINARY, "div", ("a", "b"), "d",
                          fn="div"),
                ir.OpNode(ir.OpKind.ROW_NORM, "norm", ("d",), "y",
                          params=("scale",),
                          attrs={"norm": "rms", "eps": 1e-6}),
            ))
        a = _randn(rng, (7, 32))
        b = _randn(rng, (7, 32)) + 3.0
        scale = _randn(rng, (32,))

        def loss(mode, s_):
            out = fs_ops.fused_stack_apply(prog, {"a": a, "b": b},
                                           {"scale": s_}, mode=mode,
                                           tile_rows=4)   # 1 padded row
            return jnp.sum(jnp.square(out["y"]))

        gb = jax.grad(lambda s_: loss("brainslug", s_))(scale)
        gx = jax.grad(lambda s_: loss("xla", s_))(scale)
        assert bool(jnp.all(jnp.isfinite(gb)))
        np.testing.assert_allclose(np.asarray(gb), np.asarray(gx),
                                   rtol=1e-4, atol=1e-5)

    def test_grad_through_dispatcher_matches_xla(self, rng):
        prog = _glu_norm_program()
        g = _randn(rng, (6, 96))
        u = _randn(rng, (6, 96))
        scale = _randn(rng, (96,))

        def loss(mode, g_, u_, s_):
            out = fs_ops.fused_stack_apply(prog, {"g": g_, "u": u_},
                                           {"scale": s_}, mode=mode,
                                           tile_rows=8)
            return jnp.sum(jnp.square(out["o"]))

        gb = jax.grad(lambda *a: loss("brainslug", *a),
                      argnums=(0, 1, 2))(g, u, scale)
        gx = jax.grad(lambda *a: loss("xla", *a),
                      argnums=(0, 1, 2))(g, u, scale)
        for a, b in zip(gb, gx):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
        # the generated backward ran; the reference interpreter did not
        assert fs_ops.STATS.counts["bwd_generated"] >= 1
        assert fs_ops.STATS.counts["bwd_reference"] == 0


# ---------------------------------------------------------------------------
# Executor-level parity: brainslug vs xla through optimize_stack, incl.
# multi-sequence splits on a tiny budget.
# ---------------------------------------------------------------------------

def _norm_chain_program(n_norms=3, features=64):
    ops = []
    v = "x"
    for i in range(n_norms):
        ops.append(ir.OpNode(ir.OpKind.ROW_NORM, f"n{i}", (v,), f"nv{i}",
                             params=(f"scale{i}",),
                             attrs={"norm": "rms", "eps": 1e-6}))
        ops.append(ir.OpNode(ir.OpKind.EW_UNARY, f"a{i}", (f"nv{i}",),
                             f"v{i}", fn="silu"))
        v = f"v{i}"
    return ir.StackProgram(name="chain", inputs=("x",), outputs=(v,),
                           ops=tuple(ops), layout="rows")


#: Budget that forces the 3-norm chain to split under joint fwd+bwd
#: accounting but not under forward-only accounting (see test below).
_SPLIT_DEVICE = resource.DeviceSpec(name="split", vmem_bytes=24 * 1024,
                                    vmem_budget_fraction=1.0)


class TestExecutorGradParity:
    @pytest.mark.parametrize("shape", [(4, 64), (2, 5, 64), (33, 64)])
    def test_single_sequence_parity(self, rng, shape):
        prog = _glu_norm_program()
        inputs = {"g": _randn(rng, shape), "u": _randn(rng, shape)}
        params = {"scale": _randn(rng, shape[-1:])}
        shapes = {k: v.shape for k, v in inputs.items()}

        def loss(mode, p):
            exe = api.optimize_stack(prog, shapes,
                                     api.OptimizeConfig(mode=mode,
                                                        differentiable=True))
            return jnp.sum(jnp.square(exe(inputs, p)["o"]))

        gb = jax.grad(lambda p: loss("brainslug", p))(params)
        gx = jax.grad(lambda p: loss("xla", p))(params)
        np.testing.assert_allclose(np.asarray(gb["scale"]),
                                   np.asarray(gx["scale"]),
                                   rtol=1e-4, atol=1e-5)

    def test_multi_sequence_split_parity(self, rng):
        """On the tiny joint budget the chain splits into several sequences;
        gradients must still match the xla reference."""
        prog = _norm_chain_program(3, 64)
        x = _randn(rng, (12, 64))
        params = {f"scale{i}": _randn(rng, (64,)) for i in range(3)}
        shapes = {"x": x.shape}

        plan = collapse.collapse(prog, shapes, _SPLIT_DEVICE, itemsize=4,
                                 differentiable=True)
        assert len(plan.sequences) > 1          # the split actually happened

        def loss(mode, device, p):
            exe = api.optimize_stack(
                prog, shapes, api.OptimizeConfig(mode=mode, device=device,
                                                 differentiable=True))
            out = exe({"x": x}, p)
            return jnp.sum(jnp.square(out[prog.outputs[0]]))

        gb = jax.grad(lambda p: loss("brainslug", _SPLIT_DEVICE, p))(params)
        gx = jax.grad(lambda p: loss("xla", resource.TPU_V5E, p))(params)
        for k in params:
            np.testing.assert_allclose(np.asarray(gb[k]), np.asarray(gx[k]),
                                       rtol=1e-4, atol=1e-5, err_msg=k)
        assert fs_ops.STATS.counts["bwd_generated"] >= 2
        assert fs_ops.STATS.counts["bwd_reference"] == 0

    def test_grad_hot_path_uses_generated_kernel(self, rng):
        """Acceptance criterion: jax.grad through a rows brainslug executor
        dispatches the generated backward, never the reference interpreter."""
        prog = _glu_norm_program()
        inputs = {"g": _randn(rng, (8, 64)), "u": _randn(rng, (8, 64))}
        params = {"scale": _randn(rng, (64,))}
        exe = api.optimize_stack(prog, {k: v.shape for k, v in
                                        inputs.items()},
                                 api.OptimizeConfig(mode="brainslug",
                                                    differentiable=True))
        fs_ops.STATS.reset()
        jax.grad(lambda p: jnp.sum(exe(inputs, p)["o"]))(params)
        assert fs_ops.STATS.counts["bwd_generated"] == 1
        assert fs_ops.STATS.counts["bwd_reference"] == 0


# ---------------------------------------------------------------------------
# Generate-once: fwd+bwd executable reuse across structurally equal stacks.
# ---------------------------------------------------------------------------

class TestExecutableReuse:
    def test_identical_stacks_share_executable(self, rng):
        """Two structurally identical stacks (different program names) share
        one cached forward+backward pair."""
        shapes = {"g": (8, 64), "u": (8, 64)}
        prog_a = _glu_norm_program()
        prog_b = ir.StackProgram(name="other_name", inputs=prog_a.inputs,
                                 outputs=prog_a.outputs, ops=prog_a.ops,
                                 layout="rows")
        cfg = api.OptimizeConfig(mode="brainslug", differentiable=True)
        exe_a = api.optimize_stack(prog_a, shapes, cfg)
        n_after_first = len(fs_ops._EXEC_CACHE)
        exe_b = api.optimize_stack(prog_b, shapes, cfg)
        assert len(fs_ops._EXEC_CACHE) == n_after_first == 1

        # both executors still compute correct grads off the shared pair
        inputs = {"g": _randn(rng, (8, 64)), "u": _randn(rng, (8, 64))}
        params = {"scale": _randn(rng, (64,))}
        ga = jax.grad(lambda p: jnp.sum(exe_a(inputs, p)["o"]))(params)
        gb = jax.grad(lambda p: jnp.sum(exe_b(inputs, p)["o"]))(params)
        np.testing.assert_allclose(np.asarray(ga["scale"]),
                                   np.asarray(gb["scale"]))

    def test_compile_plan_prebuilds_backward(self):
        prog = _glu_norm_program()
        plan = collapse.collapse(prog, {"g": (8, 64), "u": (8, 64)},
                                 resource.TPU_V5E, itemsize=4,
                                 differentiable=True)
        codegen.compile_plan(plan, mode="brainslug")
        assert len(fs_ops._EXEC_CACHE) == 1
        exe = next(iter(fs_ops._EXEC_CACHE.values()))
        assert exe.generated_bwd


# ---------------------------------------------------------------------------
# Joint fwd+bwd resource accounting.
# ---------------------------------------------------------------------------

class TestJointBudget:
    def test_bwd_live_exceeds_fwd_live(self):
        prog = _norm_chain_program(3, 64)
        assert (resource.max_live_values_bwd(prog)
                > resource.max_live_values(prog))

    def test_differentiable_tile_never_larger(self):
        prog = _glu_norm_program()
        fwd = resource.pick_row_tile(prog, 4096, 4, resource.TPU_V5E)
        joint = resource.pick_row_tile(prog, 4096, 4, resource.TPU_V5E,
                                       differentiable=True)
        assert joint <= fwd

    def test_differentiable_plan_splits_earlier(self):
        prog = _norm_chain_program(3, 64)
        shapes = {"x": (12, 64)}
        fwd_plan = collapse.collapse(prog, shapes, _SPLIT_DEVICE, itemsize=4)
        joint_plan = collapse.collapse(prog, shapes, _SPLIT_DEVICE,
                                       itemsize=4, differentiable=True)
        assert len(joint_plan.sequences) > len(fwd_plan.sequences)

    def test_plan_respects_joint_budget(self):
        """Every sequence of a differentiable plan fits the joint fwd+bwd
        working set in the device budget (acceptance criterion)."""
        prog = _norm_chain_program(4, 64)
        plan = collapse.collapse(prog, {"x": (12, 64)}, _SPLIT_DEVICE,
                                 itemsize=4, differentiable=True)
        for i, seq in enumerate(plan.sequences):
            sub = plan.subprogram(i)
            n_live = resource.max_live_values_bwd(sub)
            assert resource.rows_tile_bytes(
                n_live, seq.tile_rows, 64, 4,
                _SPLIT_DEVICE) <= _SPLIT_DEVICE.resource_limit
