"""The one traffic generator: reads a mix's parameters (a data file under
``bench/traffic/``) and makes that mix's inputs from ``--seed``.

Two kinds of mix, named by the file's ``"driver"`` key:

* ``serve`` — a queue of requests.  Prompt and output lengths follow the
  mix's clipped lognormals.  The *set* of lengths is the same for every
  seed (evenly spaced quantiles of each distribution); the seed only
  orders it and draws the tokens.  The queue is stratified in blocks of
  ``block`` requests, each block holding one length from each of
  ``block`` strata, so that any stretch of the queue a run reaches holds
  nearly the same work whatever the seed.  Prompts are distinct from their
  first token on, so no two share a prefix block.
* ``image`` — a pool of ``pool`` distinct batches of images and labels,
  made on the device in one jitted call.

The seeded-generator idiom (``numpy.random.default_rng(seed)`` drawing
lengths, then token ids) follows ``benchmarks/serve_throughput.py``'s
``make_queue``; this file is a copy, not an import, so that the yardstick
stays with the benchmark.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class QueuedRequest:
    """One request of a serve mix: a greedy generation of ``max_new``
    tokens after ``prompt``."""
    rid: int
    prompt: np.ndarray          # (P,) int32
    max_new: int


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of a lognormal, rounded and clipped
    to ``[lo, hi]``: the same lengths for every seed."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = np.rint(np.exp(math.log(median) + sigma * z))
    return np.clip(vals, lo, hi).astype(np.int64)


def stratified_order(values: np.ndarray, block: int,
                     rng: np.random.Generator) -> np.ndarray:
    """``values`` reordered so each consecutive run of ``block`` holds one
    value from each of ``block`` strata of the sorted values, in a seeded
    order."""
    n = len(values)
    if n % block:
        raise ValueError(f"{n} values do not split into blocks of {block}")
    k = n // block
    strata = np.sort(values).reshape(block, k)          # stratum per row
    picks = np.stack([rng.permutation(row) for row in strata])  # (block, k)
    out = []
    for j in range(k):
        out.extend(picks[rng.permutation(block), j])
    return np.asarray(out, np.int64)


def serve_queue(mix: dict, vocab: int, seed: int) -> list[QueuedRequest]:
    """The request queue of a ``serve`` mix for ``seed``."""
    n, block = mix["requests"], mix["block"]
    pr, out = mix["prompt"], mix["output"]
    max_total = mix["max_total"]
    rng = np.random.default_rng(seed)
    prompts = stratified_order(
        lognormal_quantiles(n, pr["median"], pr["sigma"], pr["min"],
                            pr["max"]), block, rng)
    outputs = stratified_order(
        lognormal_quantiles(n, out["median"], out["sigma"], out["min"],
                            out["max"]), block, rng)
    outputs = np.minimum(outputs, max_total - prompts)
    if np.any(outputs < 1) or np.any(prompts < 1):
        raise ValueError(f"mix leaves a request without tokens: {mix}")
    if n > vocab:
        raise ValueError(f"{n} requests cannot have distinct first tokens "
                         f"in a vocabulary of {vocab}")
    firsts = rng.choice(vocab, size=n, replace=False)
    queue = []
    for i in range(n):
        body = rng.integers(0, vocab, (int(prompts[i]) - 1,))
        prompt = np.concatenate([[firsts[i]], body]).astype(np.int32)
        queue.append(QueuedRequest(rid=i, prompt=prompt,
                                   max_new=int(outputs[i])))
    return queue


def image_pool(mix: dict, cfg: dict, key):
    """``(images (pool, B, H, W, C) float32, labels (pool, B) int32)`` for
    an ``image`` mix; call under ``jax.jit`` with ``mix``/``cfg`` static."""
    import jax
    import jax.numpy as jnp

    kx, ky = jax.random.split(key)
    side, c = cfg["image_size"], cfg["in_channels"]
    shape = (mix["pool"], mix["batch"], side, side, c)
    images = jax.random.normal(kx, shape, jnp.float32)
    labels = jax.random.randint(ky, shape[:2], 0, cfg["num_classes"],
                                jnp.int32)
    return images, labels
