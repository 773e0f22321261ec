"""Quickstart — the paper's Listing 3 experience, in JAX.

Write a plain-jnp VGG forward, call ``repro.api.optimize`` on it (one
function call — no IR construction), and run it.  The optimized callable
computes the same function; the depth-first schedule changes only memory
traffic.  Run:

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.launch import compile_cache
from repro.models import cnn

compile_cache.configure()

# 1. the model: an ordinary JAX function + its parameters
#    (paper: model = torchvision.models.vgg16(...))
_, params = cnn.vgg_net(stages=(32, 64, 128), batch_norm=True)
x = jax.random.normal(jax.random.PRNGKey(0), (8, 32, 32, 3), jnp.float32)

# 2. optimize with BrainSlug (paper: brainslug.optimize(model)) — the
#    tracer lifts the jaxpr into the IR, finds the optimizable stacks, and
#    collapses them against the device budget.
net = api.optimize(cnn.vgg_fn, x, params,
                   config=api.OptimizeConfig(mode="brainslug"))

# 3. execute: drop-in for the original function
y = net(x, params)
y_ref = cnn.vgg_fn(x, params)
np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                           rtol=2e-4, atol=2e-4)

print(f"output shape            : {y.shape}")
print(f"max |optimized - raw fn|: "
      f"{float(jnp.max(jnp.abs(y - y_ref))):.3e}")
print(f"stacks found            : {net.n_stacks}")
print(f"fused sequences emitted : {net.n_sequences}")

# 4. what the tracer captured and what the schedule change buys
#    (ops captured vs. left opaque, analytic HBM traffic per stack)
print(net.explain())
