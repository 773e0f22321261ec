"""Kernel-registry tests: traced OPAQUE backbones dispatch to the
dedicated pallas kernels (attention / rmsnorm / swiglu / vocab-CE), the
ref fallback is recorded rather than silent, gradient fences veto capture,
and the executor caches stay LRU-bounded with STATS resetting alongside
``clear_cache`` — the long-lived-serving defects of this PR.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import codegen, ir, registry, trace
from repro.kernels.fused_stack import ops as fused_ops
from repro.models import lm

TOL = dict(rtol=2e-4, atol=2e-4)


@pytest.fixture(autouse=True)
def _clear_codegen_cache():
    codegen.clear_cache()
    yield
    codegen.clear_cache()


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _kernel_ops(net):
    return [seg.op for seg in net.segments
            if not seg.is_stack and seg.op.kind == ir.OpKind.KERNEL]


def _optimize_all_modes(fn, *args, tol=TOL, **cfg_kw):
    ref = jax.tree_util.tree_leaves(fn(*args))
    nets = {}
    for mode in ("barrier", "xla", "brainslug"):
        net = api.optimize(fn, *args,
                           config=api.OptimizeConfig(mode=mode, **cfg_kw))
        got = jax.tree_util.tree_leaves(net(*args))
        for g, r in zip(got, ref):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), **tol)
        nets[mode] = net
    return nets


# ---------------------------------------------------------------------------
# Individual matchers.
# ---------------------------------------------------------------------------

class TestAttentionMatcher:
    def _attn(self, causal):
        def fn(q, k, v):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (1.0 / 4.0)
            if causal:
                sq = s.shape[-1]
                mask = jnp.where(jnp.arange(sq)[:, None]
                                 >= jnp.arange(sq)[None, :], 0.0, -1e30)
                s = s + mask
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhqk,bhkd->bhqd", p, v)
        return fn

    @pytest.mark.parametrize("causal", [False, True])
    def test_multihead_attention_dispatches(self, rng, causal):
        q, k, v = (jnp.asarray(rng.standard_normal((2, 2, 8, 16)),
                               jnp.float32) for _ in range(3))
        fn = self._attn(causal)
        nets = _optimize_all_modes(fn, q, k, v)
        for net in nets.values():
            rep = net.report()
            assert rep.kernel_hits == {"attention": 1}
            (kc,) = rep.kernels
            assert kc.kernel == "attention"
        # the mode decides the backend; brainslug takes the pallas kernel
        assert nets["brainslug"].report().kernels[0].backend == "pallas"
        assert nets["xla"].report().kernels[0].backend == "ref"
        (op,) = _kernel_ops(nets["brainslug"])
        assert op.attrs["causal"] is causal
        assert op.attrs["scale"] == pytest.approx(0.25)

    def test_single_head_3d_attention(self, rng):
        """(B, S, D) operands — the registry lifts them to (B, 1, S, D)."""
        q, k, v = (jnp.asarray(rng.standard_normal((2, 6, 8)), jnp.float32)
                   for _ in range(3))
        def fn(q, k, v):
            p = jax.nn.softmax(
                jnp.einsum("bqd,bkd->bqk", q, k) * 0.125, axis=-1)
            return jnp.einsum("bqk,bkd->bqd", p, v)
        nets = _optimize_all_modes(fn, q, k, v)
        assert nets["brainslug"].report().kernel_hits == {"attention": 1}

    def test_unscaled_attention_matches_with_scale_one(self, rng):
        q, k, v = (jnp.asarray(rng.standard_normal((1, 2, 4, 8)) * 0.3,
                               jnp.float32) for _ in range(3))
        def fn(q, k, v):
            p = jax.nn.softmax(jnp.einsum("bhqd,bhkd->bhqk", q, k), axis=-1)
            return jnp.einsum("bhqk,bhkd->bhqd", p, v)
        nets = _optimize_all_modes(fn, q, k, v)
        (op,) = _kernel_ops(nets["brainslug"])
        assert op.attrs["scale"] == pytest.approx(1.0)

    def test_non_triangular_mask_not_claimed(self, rng):
        """An additive mask without causal structure must not be rewritten
        to flash attention (which only knows causal / none)."""
        q, k, v = (jnp.asarray(rng.standard_normal((1, 2, 4, 8)),
                               jnp.float32) for _ in range(3))
        def fn(q, k, v):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k)
            mask = jnp.where((jnp.arange(4)[:, None] + jnp.arange(4)) % 2
                             == 0, 0.0, -1e30)      # checkerboard
            p = jax.nn.softmax(s + mask, axis=-1)
            return jnp.einsum("bhqk,bhkd->bhqd", p, v)
        nets = _optimize_all_modes(fn, q, k, v)
        assert nets["brainslug"].report().kernel_hits == {}


class TestRmsnormMatcher:
    def test_rmsnorm_before_matmul_dispatches(self, rng):
        x = jnp.asarray(rng.standard_normal((6, 16)), jnp.float32)
        g = jnp.asarray(1.0 + 0.1 * rng.standard_normal(16), jnp.float32)
        w = jnp.asarray(rng.standard_normal((16, 16)) * 0.25, jnp.float32)
        def fn(x, g, w):
            var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
            y = x * jax.lax.rsqrt(var + 1e-6) * g
            return y @ w
        nets = _optimize_all_modes(fn, x, g, w)
        rep = nets["brainslug"].report()
        assert rep.kernel_hits == {"rmsnorm": 1}
        assert rep.kernels[0].backend == "pallas"
        (op,) = _kernel_ops(nets["brainslug"])
        assert op.attrs["eps"] == pytest.approx(1e-6)

    def test_standalone_rmsnorm_stays_in_stack(self, rng):
        """Without a downstream matmul the norm chain belongs to the
        depth-first stack machinery, not the registry."""
        x = jnp.asarray(rng.standard_normal((6, 16)), jnp.float32)
        g = jnp.asarray(1.0 + 0.1 * rng.standard_normal(16), jnp.float32)
        def fn(x, g):
            var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
            return x * jax.lax.rsqrt(var + 1e-6) * g
        nets = _optimize_all_modes(fn, x, g)
        rep = nets["brainslug"].report()
        assert rep.kernel_hits == {}
        assert rep.n_captured >= 2            # ROW_NORM + scale mul


class TestSwigluMatcher:
    def test_glu_gate_dispatches(self, rng):
        x = jnp.asarray(rng.standard_normal((6, 16)), jnp.float32)
        w1 = jnp.asarray(rng.standard_normal((16, 32)) * 0.25, jnp.float32)
        w2 = jnp.asarray(rng.standard_normal((16, 32)) * 0.25, jnp.float32)
        def fn(x, w1, w2):
            return jax.nn.silu(x @ w1) * (x @ w2)
        nets = _optimize_all_modes(fn, x, w1, w2)
        rep = nets["brainslug"].report()
        assert rep.kernel_hits == {"swiglu": 1}
        (op,) = _kernel_ops(nets["brainslug"])
        assert op.attrs["act"] == "silu"

    def test_geglu_dispatches(self, rng):
        x = jnp.asarray(rng.standard_normal((6, 16)), jnp.float32)
        w1 = jnp.asarray(rng.standard_normal((16, 16)) * 0.25, jnp.float32)
        w2 = jnp.asarray(rng.standard_normal((16, 16)) * 0.25, jnp.float32)
        def fn(x, w1, w2):
            return (x @ w2) * jax.nn.gelu(x @ w1, approximate=True)
        nets = _optimize_all_modes(fn, x, w1, w2)
        (op,) = _kernel_ops(nets["brainslug"])
        assert op.attrs["act"] == "gelu"

    def test_stack_absorbable_left_to_stacks_outside_brainslug(self, rng):
        """rmsnorm/swiglu clusters are ROW_NORM / EW chains the stacks
        already absorb — in xla/barrier mode (ref backend) claiming them
        would be a deoptimization, so the registry must not."""
        x = jnp.asarray(rng.standard_normal((6, 16)), jnp.float32)
        w1 = jnp.asarray(rng.standard_normal((16, 32)) * 0.25, jnp.float32)
        w2 = jnp.asarray(rng.standard_normal((16, 32)) * 0.25, jnp.float32)
        def fn(x, w1, w2):
            return jax.nn.silu(x @ w1) * (x @ w2)
        for mode in ("xla", "barrier"):
            net = api.optimize(fn, x, w1, w2,
                               config=api.OptimizeConfig(mode=mode))
            rep = net.report()
            assert rep.kernel_hits == {}
            assert rep.n_captured >= 2       # silu + mul stay in a stack

    def test_stack_absorbable_constraint_violation_keeps_stack(self, rng):
        """brainslug mode but features % 8 != 0: the pallas swiglu kernel
        cannot run, and the cluster stays a depth-first stack instead of
        falling to a jnp ref call."""
        x = jnp.asarray(rng.standard_normal((6, 16)), jnp.float32)
        w1 = jnp.asarray(rng.standard_normal((16, 12)) * 0.25, jnp.float32)
        w2 = jnp.asarray(rng.standard_normal((16, 12)) * 0.25, jnp.float32)
        def fn(x, w1, w2):
            return jax.nn.silu(x @ w1) * (x @ w2)
        net = api.optimize(fn, x, w1, w2,
                           config=api.OptimizeConfig(mode="brainslug"))
        rep = net.report()
        assert rep.kernel_hits == {}
        assert rep.n_captured >= 2
        np.testing.assert_allclose(np.asarray(net(x, w1, w2)),
                                   np.asarray(fn(x, w1, w2)), **TOL)

    def test_non_matmul_operand_not_claimed(self, rng):
        """silu(x@w) * (x+g) is a plain elementwise chain for the stack
        machinery — the registry only claims the matmul-fed GLU idiom."""
        x = jnp.asarray(rng.standard_normal((6, 16)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((16, 16)) * 0.25, jnp.float32)
        g = jnp.asarray(rng.standard_normal(16), jnp.float32)
        def fn(x, w, g):
            return jax.nn.silu(x @ w) * (x + g)
        nets = _optimize_all_modes(fn, x, w, g)
        assert nets["brainslug"].report().kernel_hits == {}


class TestVocabCeMatcher:
    def test_ce_tail_dispatches_and_matches(self, rng):
        h = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((16, 32)) * 0.2, jnp.float32)
        labels = jnp.asarray([3, 5, -1, 0, 31, 2, 2, -1], jnp.int32)
        nets = _optimize_all_modes(lm.ce_loss_fn, h, w, labels,
                                   tol=dict(rtol=1e-5, atol=1e-5))
        rep = nets["brainslug"].report()
        assert rep.kernel_hits == {"vocab_ce": 1}
        assert rep.kernels[0].backend == "pallas"

    def test_ce_grad_parity(self, rng):
        h = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((16, 32)) * 0.2, jnp.float32)
        labels = jnp.asarray([3, 5, -1, 0, 31, 2, 2, -1], jnp.int32)
        net = api.optimize(lm.ce_loss_fn, h, w, labels,
                           config=api.OptimizeConfig(mode="brainslug",
                                                     differentiable=True))
        g1 = jax.grad(lambda hh, ww: net(hh, ww, labels),
                      argnums=(0, 1))(h, w)
        g2 = jax.grad(lambda hh, ww: lm.ce_loss_fn(hh, ww, labels),
                      argnums=(0, 1))(h, w)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Fallback accounting: a ref dispatch must be recorded, never invisible.
# ---------------------------------------------------------------------------

class TestFallbackRecorded:
    def test_constraint_violation_falls_back_to_ref_and_is_reported(
            self, rng):
        """head_dim 4 violates the flash kernel's lane-width constraint:
        the ref twin runs, the output still matches, and report() names
        the fallback with its reason."""
        q, k, v = (jnp.asarray(rng.standard_normal((1, 2, 6, 4)),
                               jnp.float32) for _ in range(3))
        def fn(q, k, v):
            p = jax.nn.softmax(
                jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.5, axis=-1)
            return jnp.einsum("bhqk,bhkd->bhqd", p, v)
        nets = _optimize_all_modes(fn, q, k, v)
        rep = nets["brainslug"].report()
        assert rep.kernel_hits == {"attention": 1}
        assert rep.kernel_fallbacks == {"attention": 1}
        (kc,) = rep.kernels
        assert kc.backend == "ref"
        assert "head_dim 4" in kc.fallback_reason
        assert "head_dim 4" in nets["brainslug"].explain()

    def test_registry_stats_count_backend_dispatches(self, rng):
        x = jnp.asarray(rng.standard_normal((6, 16)), jnp.float32)
        w1 = jnp.asarray(rng.standard_normal((16, 32)) * 0.25, jnp.float32)
        w2 = jnp.asarray(rng.standard_normal((16, 32)) * 0.25, jnp.float32)
        def fn(x, w1, w2):
            return jax.nn.silu(x @ w1) * (x @ w2)
        net = api.optimize(fn, x, w1, w2,
                           config=api.OptimizeConfig(mode="brainslug"))
        before = registry.STATS.snapshot()
        net(x, w1, w2)
        delta = registry.STATS.delta(before)
        assert delta["swiglu_pallas"] == 1
        assert delta["swiglu_ref"] == 0

    def test_registry_can_be_disabled(self, rng):
        x = jnp.asarray(rng.standard_normal((6, 16)), jnp.float32)
        w1 = jnp.asarray(rng.standard_normal((16, 32)) * 0.25, jnp.float32)
        w2 = jnp.asarray(rng.standard_normal((16, 32)) * 0.25, jnp.float32)
        def fn(x, w1, w2):
            return jax.nn.silu(x @ w1) * (x @ w2)
        net = api.optimize(fn, x, w1, w2,
                           config=api.OptimizeConfig(
                               mode="brainslug", kernel_registry=False))
        assert net.report().kernel_hits == {}
        np.testing.assert_allclose(np.asarray(net(x, w1, w2)),
                                   np.asarray(fn(x, w1, w2)), **TOL)


# ---------------------------------------------------------------------------
# Gradient fences veto registry capture (same discipline as the tracer's
# unary probes — PR 4's review fixes).
# ---------------------------------------------------------------------------

class TestFenceVetoesCapture:
    def test_fenced_logits_veto_vocab_ce(self, rng):
        """stop_gradient(logits) inside the loss tail: forward matches the
        kernel exactly, backward is zero — the gradient probe must veto."""
        h = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((16, 32)) * 0.2, jnp.float32)
        labels = jnp.asarray(rng.integers(0, 32, (8,)), jnp.int32)
        def fenced(h, w, labels):
            logits = jax.lax.stop_gradient(h @ w)
            logp = jax.nn.log_softmax(logits, axis=-1)
            gold = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
            return jnp.mean(-gold)
        nets = _optimize_all_modes(fenced, h, w, labels,
                                   tol=dict(rtol=1e-5, atol=1e-5))
        for net in nets.values():
            assert net.report().kernel_hits == {}
        # and the fence survives end to end
        net = nets["brainslug"]
        g = jax.grad(lambda hh: net(hh, w, labels))(h)
        np.testing.assert_allclose(np.asarray(g), 0.0, atol=1e-7)

    def test_fenced_up_operand_vetoes_swiglu(self, rng):
        x = jnp.asarray(rng.standard_normal((6, 16)), jnp.float32)
        w1 = jnp.asarray(rng.standard_normal((16, 32)) * 0.25, jnp.float32)
        w2 = jnp.asarray(rng.standard_normal((16, 32)) * 0.25, jnp.float32)
        def fenced(x, w1, w2):
            return jax.nn.silu(x @ w1) * jax.lax.stop_gradient(x @ w2)
        nets = _optimize_all_modes(fenced, x, w1, w2)
        for net in nets.values():
            assert net.report().kernel_hits == {}
        net = nets["brainslug"]
        g1 = jax.grad(lambda v: jnp.sum(net(v, w1, w2)))(x)
        g2 = jax.grad(lambda v: jnp.sum(fenced(v, w1, w2)))(x)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-5)

    def test_fenced_rms_scale_not_lifted_to_kernel(self, rng):
        """x * stop_gradient(rsqrt(mean(x^2)+eps)) * g never becomes a
        ROW_NORM (tracer fence rule), so the registry cannot claim it."""
        x = jnp.asarray(rng.standard_normal((6, 16)), jnp.float32)
        g = jnp.asarray(1.0 + 0.1 * rng.standard_normal(16), jnp.float32)
        w = jnp.asarray(rng.standard_normal((16, 16)) * 0.25, jnp.float32)
        def fenced(x, g, w):
            var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
            y = x * jax.lax.stop_gradient(jax.lax.rsqrt(var + 1e-6)) * g
            return y @ w
        nets = _optimize_all_modes(fenced, x, g, w)
        for net in nets.values():
            assert net.report().kernel_hits == {}


# ---------------------------------------------------------------------------
# Acceptance: the plain-jnp transformer block twin.
# ---------------------------------------------------------------------------

class TestTransformerBlockAcceptance:
    @pytest.fixture(scope="class")
    def block(self):
        d, nh, dff = 16, 2, 32
        params = lm.transformer_block_params(jax.random.PRNGKey(0), d, nh,
                                             dff)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, d), jnp.float32)
        fn = lambda xx, pp: lm.transformer_block_fn(xx, pp, n_heads=nh)  # noqa: E731
        return fn, x, params

    def test_block_dispatches_all_three_kernels_and_matches(self, block):
        fn, x, params = block
        nets = _optimize_all_modes(fn, x, params)
        rep = nets["brainslug"].report()
        assert rep.kernel_hits == {"attention": 1, "rmsnorm": 2,
                                   "swiglu": 1}
        assert all(k.backend == "pallas" for k in rep.kernels)
        assert rep.kernel_fallbacks == {}

    def test_block_grad_parity_differentiable(self, block):
        fn, x, params = block
        for mode in ("brainslug", "xla"):
            net = api.optimize(
                fn, x, params,
                config=api.OptimizeConfig(mode=mode, differentiable=True))
            g1 = jax.grad(lambda v: jnp.sum(jnp.square(net(v, params))))(x)
            g2 = jax.grad(lambda v: jnp.sum(jnp.square(fn(v, params))))(x)
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       rtol=2e-3, atol=2e-3)

    def test_block_jit_compatible(self, block):
        fn, x, params = block
        net = api.optimize(fn, x, params,
                           config=api.OptimizeConfig(mode="brainslug"))
        got = jax.jit(net)(x, params)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(fn(x, params)), **TOL)

    def test_noncausal_block_matches(self, block):
        _, x, params = block
        fn = lambda xx, pp: lm.transformer_block_fn(  # noqa: E731
            xx, pp, n_heads=2, causal=False)
        nets = _optimize_all_modes(fn, x, params)
        (op,) = [o for o in _kernel_ops(nets["brainslug"])
                 if o.attrs["kernel"] == "attention"]
        assert op.attrs["causal"] is False


# ---------------------------------------------------------------------------
# Cache bounds + STATS reset (the long-lived-serving bugfixes).
# ---------------------------------------------------------------------------

class TestCacheBounds:
    def test_code_cache_is_lru_bounded(self, rng):
        codegen.set_cache_limit(4)
        try:
            # a fresh shape signature per iteration — the leak scenario
            for rows in range(3, 11):
                x = jnp.asarray(rng.standard_normal((rows, 8)), jnp.float32)
                net = api.optimize(jax.nn.relu, x,
                                   config=api.OptimizeConfig(
                                       mode="brainslug", code_cache_size=4))
                net(x)
                assert len(codegen._CODE_CACHE) <= 4
                assert len(fused_ops._EXEC_CACHE) <= 4
        finally:
            codegen.set_cache_limit(256)

    def test_lru_evicts_oldest_not_hottest(self):
        codegen.set_cache_limit(2)
        try:
            codegen._cache_put(("a",), 1)
            codegen._cache_put(("b",), 2)
            assert codegen._cache_get(("a",)) == 1   # refresh a
            codegen._cache_put(("c",), 3)            # evicts b, not a
            assert codegen._cache_get(("a",)) == 1
            assert codegen._cache_get(("b",)) is None
            assert codegen._cache_get(("c",)) == 3
        finally:
            codegen.set_cache_limit(256)
            codegen.clear_cache()

    def test_clear_cache_resets_dispatch_stats(self, rng):
        """Back-to-back benchmark runs must not read stale counters —
        clear_cache() zeroes both the fused-stack and the registry STATS."""
        x = jnp.asarray(rng.standard_normal((6, 16)), jnp.float32)
        w1 = jnp.asarray(rng.standard_normal((16, 32)) * 0.25, jnp.float32)
        w2 = jnp.asarray(rng.standard_normal((16, 32)) * 0.25, jnp.float32)
        def fn(x, w1, w2):
            return jax.nn.relu(jax.nn.silu(x @ w1) * (x @ w2))
        net = api.optimize(fn, x, w1, w2,
                           config=api.OptimizeConfig(mode="brainslug"))
        net(x, w1, w2)
        assert registry.STATS.counts["swiglu_pallas"] >= 1
        assert fused_ops.STATS.counts["fwd_generated"] >= 1
        codegen.clear_cache()
        assert all(v == 0 for v in registry.STATS.counts.values())
        assert all(v == 0 for v in fused_ops.STATS.counts.values())
        assert len(codegen._CODE_CACHE) == 0
        assert len(fused_ops._EXEC_CACHE) == 0

    def test_cache_limit_validation(self):
        with pytest.raises(ValueError, match="cache limit"):
            codegen.set_cache_limit(0)
        with pytest.raises(ValueError, match="code_cache_size"):
            api.OptimizeConfig(code_cache_size=0)

    def test_explicit_limit_pinned_against_config_floors(self, rng):
        """An operator's explicit set_cache_limit() must survive later
        compiles with a larger per-config code_cache_size — config-driven
        sizing only raises an *unpinned* limit."""
        codegen.set_cache_limit(2)               # explicit: pins
        try:
            x = jnp.asarray(rng.standard_normal((5, 8)), jnp.float32)
            api.optimize(jax.nn.relu, x,
                         config=api.OptimizeConfig(mode="brainslug",
                                                   code_cache_size=512))
            assert codegen._CACHE_LIMIT == 2     # not silently reverted
            assert len(codegen._CODE_CACHE) <= 2
        finally:
            codegen.set_cache_limit(256)

    def test_identical_kernel_sites_share_one_compiled_closure(self, rng):
        """The kernel cache is keyed on kernel id + shapes + static attrs,
        not value names: two traced graphs with the same kernel shapes
        reuse one compiled inner closure."""
        x = jnp.asarray(rng.standard_normal((6, 16)), jnp.float32)
        w1 = jnp.asarray(rng.standard_normal((16, 32)) * 0.25, jnp.float32)
        w2 = jnp.asarray(rng.standard_normal((16, 32)) * 0.25, jnp.float32)
        def fn_a(x, w1, w2):
            return jax.nn.silu(x @ w1) * (x @ w2)
        def fn_b(x, w1, w2):
            return jax.nn.silu(x @ w1) * (x @ w2) + 0.0
        api.optimize(fn_a, x, w1, w2,
                     config=api.OptimizeConfig(mode="brainslug"))
        api.optimize(fn_b, x, w1, w2,
                     config=api.OptimizeConfig(mode="brainslug"))
        kernel_keys = [k for k in codegen._CODE_CACHE if k[0] == "kernel"]
        assert len(kernel_keys) == 1


class TestEntryVjpDeclaration:
    def test_vjp_ref_entry_gets_ref_backward(self, rng, monkeypatch):
        """An entry declaring vjp='ref' (pallas path without its own
        custom rule) must be wrapped by autodiff.with_ref_vjp: jax.grad
        recomputes through the jnp twin even when the raw pallas forward
        fences gradients."""
        import dataclasses as dc
        base = registry.REGISTRY["swiglu"]

        def fenced_pallas(args, attrs):
            # forward-correct but gradient-dead without the wrapper
            return jax.lax.stop_gradient(base.ref(args, attrs))

        monkeypatch.setitem(
            registry.REGISTRY, "swiglu",
            dc.replace(base, pallas=fenced_pallas, vjp="ref"))
        x = jnp.asarray(rng.standard_normal((7, 16)), jnp.float32)
        w1 = jnp.asarray(rng.standard_normal((16, 8)) * 0.25, jnp.float32)
        w2 = jnp.asarray(rng.standard_normal((16, 8)) * 0.25, jnp.float32)
        def fn(x, w1, w2):
            return jax.nn.silu(x @ w1) * (x @ w2)
        net = api.optimize(fn, x, w1, w2,
                           config=api.OptimizeConfig(mode="brainslug"))
        assert net.report().kernel_hits == {"swiglu": 1}
        g1 = jax.grad(lambda v: jnp.sum(net(v, w1, w2)))(x)
        g2 = jax.grad(lambda v: jnp.sum(fn(v, w1, w2)))(x)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-5)
        assert float(jnp.max(jnp.abs(g1))) > 0.0


# ---------------------------------------------------------------------------
# Engine dispatch STATS: per-run snapshot/delta (no cross-run bleed).
# ---------------------------------------------------------------------------

class TestEngineStatsDelta:
    def test_second_run_reports_its_own_counts(self):
        from repro.launch.engine import Request
        from repro.launch.serve import ServeConfig, Server
        server = Server(ServeConfig(arch="deepseek-7b", batch=2,
                                    prompt_len=4, new_tokens=4, max_len=12))
        engine = server.engine(slots=2, prefill_chunk=4)
        reqs = [Request(request_id=i, prompt=[1, 2, 3], max_new_tokens=3)
                for i in range(3)]
        engine.run(reqs)
        first = dict(engine.last_dispatch)
        engine.run(reqs)
        second = dict(engine.last_dispatch)
        # identical traffic => identical per-run counts; the cumulative
        # module STATS would have doubled
        assert first == second
        assert first["decode_slot_steps"] == 3 * 2   # 3 reqs x (3-1) steps
        from repro.launch import engine as engine_mod
        assert engine_mod.STATS.counts["decode_slot_steps"] \
            >= 2 * first["decode_slot_steps"]

    def test_static_server_reports_per_call_delta(self):
        from repro.launch.serve import ServeConfig, Server
        server = Server(ServeConfig(arch="deepseek-7b", batch=2,
                                    prompt_len=4, new_tokens=4, max_len=12))
        prompts = np.ones((2, 4), np.int32)
        server.generate(prompts, stop_lengths=np.asarray([2, 3]))
        first = dict(server.last_dispatch)
        server.generate(prompts, stop_lengths=np.asarray([2, 3]))
        assert dict(server.last_dispatch) == first


# ---------------------------------------------------------------------------
# Plumbing: the registry metadata the tracer now records.
# ---------------------------------------------------------------------------

class TestTracerRegistryMetadata:
    def test_opaque_ops_carry_prim_and_slots(self, rng):
        x = jnp.asarray(rng.standard_normal((4, 6)), jnp.float32)
        tr = trace.trace(lambda v: jnp.cumsum(v, axis=0), x)
        opaque = [op for op in tr.graph.ops
                  if op.kind == ir.OpKind.OPAQUE]
        assert opaque
        assert opaque[0].attrs["prim"] == "cumsum"
        slots = opaque[0].attrs["operand_slots"]
        assert slots[0] == ("in", "arg0")

    def test_trace_records_value_dtypes(self, rng):
        x = jnp.asarray(rng.standard_normal((4, 6)), jnp.float32)
        tr = trace.trace(lambda v: v * 2.0, x)
        assert tr.dtypes["arg0"] == jnp.float32
        out = tr.graph.ops[-1].output
        assert tr.dtypes[out] == jnp.float32
