"""The traffic generator: the same for the same seed, other for another,
the same set of lengths for every seed, and within its clips."""
from __future__ import annotations

import json

import numpy as np
import pytest

import generate
from conftest import BENCH

MIXES = ("chat", "reason")


def mix(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def lengths(queue):
    return (np.array([len(q.prompt) for q in queue]),
            np.array([q.max_new for q in queue]))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_queue_other_seed_other_queue(name):
    m = mix(name)
    a = generate.serve_queue(m, 102400, 3000000017)
    b = generate.serve_queue(m, 102400, 3000000017)
    c = generate.serve_queue(m, 102400, 3000000018)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt[:4], y.prompt[:4])
               for x, y in zip(a, c))
    assert any(len(x.prompt) != len(y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_draws_the_same_set_of_lengths(name):
    m = mix(name)
    sets = [tuple(np.sort(lengths(generate.serve_queue(m, 102400, s))[0]))
            for s in (1, 2, 2 ** 31 + 5)]
    assert sets[0] == sets[1] == sets[2]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_respect_the_clips(name):
    m = mix(name)
    q = generate.serve_queue(m, 102400, 99)
    p, o = lengths(q)
    assert len(q) == m["requests"]
    assert p.min() >= m["prompt"]["min"] and p.max() <= m["prompt"]["max"]
    assert o.min() >= 1 and o.max() <= m["output"]["max"]
    assert (p + o).max() <= m["max_total"] == 1024
    if name == "reason":
        assert o.min() >= m["output"]["min"]
    firsts = [int(x.prompt[0]) for x in q]
    assert len(set(firsts)) == len(firsts)     # no shared prefix block


@pytest.mark.parametrize("name", MIXES)
def test_each_block_holds_one_length_per_stratum(name):
    m = mix(name)
    p, _ = lengths(generate.serve_queue(m, 102400, 7))
    block = m["block"]
    strata = np.sort(p).reshape(block, -1)
    for j in range(len(p) // block):
        got = np.sort(p[j * block:(j + 1) * block])
        assert all(strata[i].min() <= got[i] <= strata[i].max()
                   for i in range(block))


def test_lognormal_quantiles_median_and_clip():
    v = generate.lognormal_quantiles(101, 384, 0.6, 64, 896)
    assert np.median(v) == 384
    assert v.min() >= 64 and v.max() <= 896


def test_image_pool_is_seeded():
    import jax

    m = mix("train32")
    cfg = {"image_size": 8, "in_channels": 3, "num_classes": 10}
    m = {**m, "batch": 2}
    a = generate.image_pool(m, cfg, jax.random.PRNGKey(1))
    b = generate.image_pool(m, cfg, jax.random.PRNGKey(1))
    c = generate.image_pool(m, cfg, jax.random.PRNGKey(2))
    assert a[0].shape == (m["pool"], 2, 8, 8, 3)
    assert np.array_equal(a[0], b[0]) and not np.array_equal(a[0], c[0])
    assert int(a[1].max()) < 10
