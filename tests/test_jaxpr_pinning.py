"""jaxpr pattern-table pinning for the installed jax (0.9.0).

The tracer's recognition tables (repro.core.trace) encode structural
assumptions about how this jax version lowers the standard activations:

* ``jax.nn.gelu`` inlines (the tanh polynomial appears as plain eqns — the
  elementwise-chain prober finds it; there is no call boundary),
* ``jax.nn.relu`` stages as a ``custom_jvp_call`` whose ``call_jaxpr``
  wraps a ``jit`` of ``max(x, 0)`` — the call-boundary behavioral prober
  handles it,
* ``jax.nn.softmax`` inlines with a ``stop_gradient`` fence on its row max
  (``reduce_max`` -> ``max(-inf, .)`` -> ``broadcast_in_dim`` ->
  ``stop_gradient``) — the structural softmax matcher must hop exactly
  that fence.

A jax upgrade that changes any of these would silently drop tracer
coverage to OPAQUE (correct output, no acceleration).  These tests stage
fresh jaxprs and assert the assumptions directly, so the upgrade fails
*loudly* in this file instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ir, trace


def _eqn_names(jaxpr, recursive=False):
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if recursive:
            for v in eqn.params.values():
                subs = v if isinstance(v, (tuple, list)) else (v,)
                for s in subs:
                    core = getattr(s, "jaxpr", s)
                    if hasattr(core, "eqns"):
                        names.extend(_eqn_names(core, recursive=True))
    return names


def _x():
    return jnp.asarray(np.linspace(-3, 3, 8, dtype=np.float32)
                       .reshape(2, 4))


class TestStagingAssumptions:
    def test_gelu_inlines_no_call_boundary(self):
        """gelu(approximate=True) must appear as inline eqns (tanh chain),
        not behind a call primitive — the chain prober depends on it."""
        jaxpr = jax.make_jaxpr(
            lambda v: jax.nn.gelu(v, approximate=True))(_x()).jaxpr
        top = _eqn_names(jaxpr)
        assert "tanh" in top, (
            "jax.nn.gelu no longer inlines its tanh polynomial; "
            "re-check trace._CHAIN_PRIMS / the chain prober")
        assert not (set(top) & set(trace._CALL_JAXPR_KEYS)), (
            f"jax.nn.gelu now stages behind a call primitive {top}; "
            "the elementwise-chain prober will no longer see it")

    def test_relu_is_custom_jvp_call(self):
        """relu must reach the jaxpr as a custom_jvp call boundary (under
        at most pjit wrapping) — the behavioral prober's entry condition."""
        names = set(_eqn_names(
            jax.make_jaxpr(jax.nn.relu)(_x()).jaxpr, recursive=True))
        assert names & trace._CUSTOM_GRAD_CALLS, (
            f"jax.nn.relu no longer stages a custom_jvp call ({names}); "
            "re-check trace._CALL_JAXPR_KEYS / _CUSTOM_GRAD_CALLS")
        # and every call wrapper on the way down is one the tracer knows
        # how to open
        wrappers = names & set(trace._CALL_JAXPR_KEYS)
        assert wrappers, (
            f"relu's call wrapping {names} has no overlap with "
            "trace._CALL_JAXPR_KEYS — the prober cannot open it")

    def test_softmax_inlines_with_stop_gradient_fence(self):
        """softmax must inline with the row-max stop_gradient fence the
        structural matcher explicitly hops (hop_stop_gradient=True)."""
        names = _eqn_names(
            jax.make_jaxpr(lambda v: jax.nn.softmax(v, axis=-1))(_x()).jaxpr,
            recursive=True)
        for prim in ("reduce_max", "exp", "reduce_sum", "div"):
            assert prim in names, (
                f"jax.nn.softmax lowering lost the {prim!r} step "
                f"(got {names}); re-check trace._try_softmax")
        assert "stop_gradient" in names, (
            "jax.nn.softmax lost its row-max stop_gradient fence; "
            "re-check trace._try_softmax's hop_stop_gradient walk")

    def test_silu_stages_as_probeable_call_or_chain(self):
        """silu is either a recognized call boundary or an inline
        x*sigmoid(x) chain; both paths must keep lifting to EW_UNARY."""
        tr = trace.trace(jax.nn.silu, _x())
        kinds = [op.kind for op in tr.graph.ops]
        assert kinds == [ir.OpKind.EW_UNARY]
        assert tr.graph.ops[0].fn == "silu"

    def test_log_softmax_fence_inside_matmul_tail(self):
        """log_softmax keeps the stop_gradient fence on its max — the
        vocab-CE registry matcher walks straight through it (the fence is
        semantically inert for log_softmax's true gradient)."""
        names = _eqn_names(
            jax.make_jaxpr(
                lambda v: jax.nn.log_softmax(v, axis=-1))(_x()).jaxpr,
            recursive=True)
        assert "reduce_sum" in names and "log" in names
        assert "stop_gradient" in names


class TestLiftingPinned:
    """End-to-end pinning: each staging disguise still lifts to the IR op
    the pattern tables promise.  A jax upgrade that changes the lowering
    fails here even if the structural assertions above drift."""

    @pytest.mark.parametrize("fn,expected_fn", [
        (jax.nn.relu, "relu"),
        (jax.nn.relu6, "relu6"),
        (lambda v: jax.nn.gelu(v, approximate=True), "gelu"),
        (jax.nn.softplus, "softplus"),
    ])
    def test_activation_lifts_to_single_unary(self, fn, expected_fn):
        tr = trace.trace(fn, _x())
        assert [op.kind for op in tr.graph.ops] == [ir.OpKind.EW_UNARY], (
            f"{expected_fn} no longer lifts to one EW_UNARY op — a jax "
            "upgrade changed its staging; update the tracer's tables")
        assert tr.graph.ops[0].fn == expected_fn

    def test_softmax_lifts_to_row_softmax(self):
        tr = trace.trace(lambda v: jax.nn.softmax(v, axis=-1), _x())
        assert [op.kind for op in tr.graph.ops] == [ir.OpKind.ROW_SOFTMAX]

    def test_relu_custom_jvp_rule_preserved_when_unmatched(self):
        """The flip side of the call-boundary assumption: a custom_jvp fn
        that is NOT a table activation must keep its derivative rule
        (bound as an opaque fragment, not inlined flat)."""
        @jax.custom_jvp
        def ste(v):
            return jnp.where(v > 0, 1.0, 0.0)

        @ste.defjvp
        def _jvp(primals, tangents):
            (v,), (t,) = primals, tangents
            return ste(v), t            # straight-through estimator

        # the traced graph must reproduce the custom backward
        from repro import api
        net = api.optimize(lambda v: ste(v) * 2.0, _x())
        g1 = jax.grad(lambda v: jnp.sum(net(v)))(_x())
        np.testing.assert_allclose(np.asarray(g1), 2.0 * np.ones((2, 4)),
                                   rtol=1e-6)
