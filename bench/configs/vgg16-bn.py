"""VGG-16 with batch norm (configuration D of Simonyan & Zisserman,
arXiv:1409.1556; torchvision ``vgg16_bn``) as plain JAX, the way a user
hands a network to ``repro.api.optimize`` (the paper's Listing-3 usage).

The same function, jitted by XLA alone at ``"highest"`` matmul precision,
is the plain reference the benchmark compares the optimized step with.
Departures from the paper, each listed under ``assumed`` in
``vgg16-bn.json``: batch norm in its per-channel affine form, no dropout,
the classifier input flattened in NHWC order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init(cfg: dict, key) -> dict:
    """All parameters from one key (call it under ``jax.jit``: one device
    program makes every leaf), initialised as torchvision's VGG is:
    convolutions Kaiming-normal over fan-out, linears N(0, 0.01), zero
    biases; the batch-norm affine is drawn near identity (1 + 0.1 N(0,1),
    0.1 N(0,1)) so that a kernel that drops it is seen."""
    params: dict = {}
    cin = cfg["in_channels"]
    keys = iter(jax.random.split(key, 64))
    i = 0
    for stage in cfg["conv_widths"]:
        for cout in stage:
            params[f"conv{i}_w"] = (jax.random.normal(
                next(keys), (3, 3, cin, cout), jnp.float32)
                * (2.0 / (9 * cout)) ** 0.5)
            params[f"conv{i}_b"] = jnp.zeros((cout,), jnp.float32)
            params[f"bn{i}_s"] = 1.0 + 0.1 * jax.random.normal(
                next(keys), (cout,), jnp.float32)
            params[f"bn{i}_o"] = 0.1 * jax.random.normal(
                next(keys), (cout,), jnp.float32)
            cin = cout
            i += 1
    side = cfg["image_size"] // 2 ** len(cfg["conv_widths"])
    fan_in = side * side * cin
    for j, width in enumerate(cfg["classifier_widths"]
                              + [cfg["num_classes"]]):
        params[f"fc{j}_w"] = 0.01 * jax.random.normal(
            next(keys), (fan_in, width), jnp.float32)
        params[f"fc{j}_b"] = jnp.zeros((width,), jnp.float32)
        fan_in = width
    return params


def make_forward(cfg: dict):
    """``forward(x, params) -> logits`` for NHWC images."""
    widths = [list(s) for s in cfg["conv_widths"]]
    n_fc = len(cfg["classifier_widths"]) + 1

    def forward(x, params):
        i = 0
        for stage in widths:
            for _ in stage:
                x = jax.lax.conv_general_dilated(
                    x, params[f"conv{i}_w"], window_strides=(1, 1),
                    padding=((1, 1), (1, 1)),
                    dimension_numbers=("NHWC", "HWIO", "NHWC"))
                x = x + params[f"conv{i}_b"]
                x = x * params[f"bn{i}_s"] + params[f"bn{i}_o"]
                x = jax.nn.relu(x)
                i += 1
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1),
                ((0, 0), (0, 0), (0, 0), (0, 0)))
        x = x.reshape(x.shape[0], -1)
        for j in range(n_fc):
            x = x @ params[f"fc{j}_w"] + params[f"fc{j}_b"]
            if j < n_fc - 1:
                x = jax.nn.relu(x)
        return x

    return forward


def cross_entropy(logits, labels):
    """Mean softmax cross-entropy over the batch."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
