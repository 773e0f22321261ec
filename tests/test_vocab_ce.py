"""Fused vocab cross-entropy kernel vs oracle: shape/dtype sweeps,
padding cases, masking, and gradients."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.vocab_ce import ce as ce_mod
from repro.kernels.vocab_ce import ops as ce_ops
from repro.kernels.vocab_ce import ref as ce_ref


def _operands(rng, t, d, v, dtype):
    h = jnp.asarray(rng.standard_normal((t, d), np.float32)).astype(dtype)
    w = jnp.asarray(rng.standard_normal((d, v), np.float32) / d ** 0.5
                    ).astype(dtype)
    labels = jnp.asarray(rng.integers(0, v, (t,)), jnp.int32)
    return h, w, labels


class TestFusedCE:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("t,d,v,br,bv,bd", [
        (16, 32, 64, 8, 16, 16),        # even splits
        (13, 32, 50, 8, 16, 16),        # row + vocab padding
        (16, 40, 64, 8, 16, 16),        # d padding
        (8, 16, 100, 8, 32, 16),        # vocab >> block
        (32, 32, 31, 16, 32, 32),       # single vocab chunk w/ padding
    ])
    def test_fwd_matches_ref(self, rng, dtype, t, d, v, br, bv, bd):
        h, w, labels = _operands(rng, t, d, v, dtype)
        lse, gold = ce_mod.fused_ce_fwd(h, w, labels, block_rows=br,
                                        block_v=bv, block_d=bd)
        lse_r, gold_r = ce_ref.ce_ref(h, w, labels)
        tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
            else dict(rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r), **tol)
        np.testing.assert_allclose(np.asarray(gold), np.asarray(gold_r),
                                   **tol)

    def test_negative_logits_with_padding(self, rng):
        """Padded vocab columns must not win the running max when all real
        logits are negative."""
        t, d, v = 8, 16, 30
        h, w, labels = _operands(rng, t, d, v, jnp.float32)
        h = h - 0.0
        w = -jnp.abs(w) - 1.0          # all logits strictly negative
        lse, gold = ce_mod.fused_ce_fwd(h, w, labels, block_rows=8,
                                        block_v=16, block_d=16)
        lse_r, _ = ce_ref.ce_ref(h, w, labels)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r),
                                   rtol=1e-5, atol=1e-5)

    def test_nll_and_masking(self, rng):
        t, d, v = 24, 32, 96
        h, w, labels = _operands(rng, t, d, v, jnp.float32)
        labels = labels.at[::3].set(-1)        # mask a third
        nll_k = ce_ops.fused_nll(h, w, labels, 8, 32, 16)
        nll_r = ce_ref.nll_ref(h, w, labels)
        np.testing.assert_allclose(float(nll_k), float(nll_r), rtol=1e-5)

    def test_grads_match_ref(self, rng):
        t, d, v = 12, 16, 40
        h, w, labels = _operands(rng, t, d, v, jnp.float32)
        labels = labels.at[0].set(-1)
        gk = jax.grad(lambda h_, w_: ce_ops.fused_nll(h_, w_, labels,
                                                      8, 16, 16),
                      argnums=(0, 1))(h, w)
        gr = jax.grad(lambda h_, w_: ce_ref.nll_ref(h_, w_, labels),
                      argnums=(0, 1))(h, w)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_fully_masked_is_zero(self, rng):
        h, w, _ = _operands(rng, 8, 16, 32, jnp.float32)
        labels = jnp.full((8,), -1, jnp.int32)
        assert float(ce_ops.fused_nll(h, w, labels, 8, 16, 16)) == 0.0
