"""The plain references against the program at CPU test size: the serve
engine's token-serial prefill and paged decode against the deepseek-7b
reference's full forward, and ``optimize()`` of VGG-16-BN against the
same function run by XLA alone."""
from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from conftest import BENCH, TINY

serve = harness.load_module(BENCH / "drivers" / "serve.py")
image = harness.load_module(BENCH / "drivers" / "image.py")
lm_ref = harness.load_module(BENCH / "configs" / "deepseek-7b.py")
vgg = harness.load_module(BENCH / "configs" / "vgg16-bn.py")


def tiny(name: str, **over) -> dict:
    cfg = json.loads((TINY / "configs" / f"{name}.json").read_text())
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def lm_case():
    cfg = tiny("deepseek-7b", torch_dtype="float32")
    w = jax.jit(functools.partial(lm_ref.init, cfg))(jax.random.PRNGKey(3))
    return cfg, w


def test_prefill_and_decode_logits_match_the_full_forward(lm_case):
    """Token by token through the paged cache, as the engine's mixed step
    runs a prompt and then its decode, in brainslug mode (the Pallas
    paged-decode kernel, rmsnorm and SwiGLU kernels)."""
    from repro.configs.base import RuntimeConfig
    from repro.models import lm

    cfg, w = lm_case
    mc = serve.program_config(cfg)
    params = serve.program_params(w)
    bs, n = 16, 40
    rt = RuntimeConfig(mode="brainslug", kv_layout="paged", kv_block_size=bs)
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], n)
    cache = lm.init_decode_cache(mc, 1, 64, dtype=jnp.float32,
                                 kv_layout="paged", kv_num_blocks=4,
                                 kv_block_size=bs)
    table = jnp.asarray([[2, 0, 3, 1]], jnp.int32)
    step = jax.jit(lambda p, c, t: lm.decode_step(p, c, t, mc, rt,
                                                  block_tables=table))
    got = []
    for t in tokens:
        logits, cache = step(params, cache, jnp.asarray([[t]], jnp.int32))
        got.append(np.asarray(logits[0, 0]))
    want = np.asarray(lm_ref.logits(w, jnp.asarray(tokens), cfg))
    np.testing.assert_allclose(np.stack(got), want, rtol=2e-4, atol=2e-4)


def test_engine_greedy_tokens_are_the_reference_argmax(lm_case):
    """Served through the engine (8-token prefill chunks over 4 slots),
    every greedy token is the float32 reference's best at its position."""
    from repro.launch.engine import Request

    cfg, w = lm_case
    engine = serve.build_engine(cfg, w, seed=0)
    rng = np.random.default_rng(1)
    reqs = [Request(request_id=i, max_new_tokens=6,
                    prompt=rng.integers(0, cfg["vocab_size"], (p,)))
            for i, p in enumerate((5, 19, 11, 30, 8))]
    done = engine.run(reqs)
    for r, c in zip(reqs, done):
        gap, k = serve.sequence_gap(lm_ref, w, cfg, np.asarray(r.prompt),
                                    c.tokens, cfg["serve"]["max_len"])
        assert k == 6 and gap <= 1e-4


def test_fp8_control_reads_wider_than_the_program(lm_case):
    """The control (the reference with float8 linear layers) puts other
    tokens first than float32 does, on the same sequence."""
    cfg, w = lm_case
    seq = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg["vocab_size"], 96))
    ref = np.asarray(lm_ref.logits(w, seq, cfg))
    low = np.asarray(lm_ref.logits(w, seq, cfg, "fp8"))
    assert np.abs(low - ref).max() > 1e-3
    assert (low.argmax(-1) != ref.argmax(-1)).any()


def test_optimize_matches_xla_forward_and_gradient():
    cfg = tiny("vgg16-bn")
    params = jax.jit(functools.partial(vgg.init, cfg))(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    y = jnp.asarray([1, 7])
    forward = vgg.make_forward(cfg)
    from repro import api

    net = api.optimize(forward, x, params,
                       config=api.OptimizeConfig(mode="brainslug",
                                                 differentiable=True))
    kinds = {s.layout for s in net.report().stacks}
    assert kinds == {"nhwc", "rows"}
    np.testing.assert_allclose(jax.jit(net)(x, params), forward(x, params),
                               rtol=1e-5, atol=1e-5)

    def grad(f):
        return jax.jit(jax.grad(lambda p: vgg.cross_entropy(f(x, p), y)))(
            params)

    g, g_ref = grad(net), grad(forward)
    for k in g_ref:
        np.testing.assert_allclose(g[k], g_ref[k], rtol=1e-4, atol=1e-6,
                                   err_msg=k)


def test_train_comparison_numbers():
    prog = {"losses": [2.0, 1.5], "mom1": {"a": 1.0, "b": 2.0, "z": 1e-9},
            "change": {"a": 0.1, "b": 0.2, "z": 0.0}}
    ref = {"losses": [2.0, 1.5 * (1 + 1e-3)],
           "mom1": {"a": 1.1, "b": 2.0, "z": 1e-9},
           "change": {"a": 0.1, "b": 0.1, "z": 5.0}}
    got, left_out = image.compare_train(prog, ref)
    assert left_out == ["z"]            # under a thousandth of the median
    assert got["loss_gap"] == pytest.approx(1e-3 / (1 + 1e-3))
    # the gap of norms over the larger of the leaf's and the median leaf's
    assert got["grad_norm_gap"] == pytest.approx(0.1 / 1.55)
    assert got["change_norm_gap"] == pytest.approx(0.1 / 0.1)


def test_queue_sample_holds_the_longest_finished_request():
    q = [serve.generate.QueuedRequest(i, np.zeros(3, np.int32), n)
         for i, n in enumerate((5, 40, 7, 9, 3))]
    done = {i: dataclasses.make_dataclass("C", ["status"])("ok")
            for i in (0, 2, 3, 4)}
    pick = serve.sample_finished(q, done, seed=4, min_tokens=12)
    assert pick[0] == 3 and 1 not in pick
    assert sum(q[i].max_new for i in pick) >= 12


def test_paged_decode_work_rebuilds_each_ticks_lanes():
    """Request A (prompt 10, 3 tokens, first token at tick 5) prefills in
    chunks of 8 at ticks 4 and 5 and decodes at 6 and 7; B (prompt 3, 2
    tokens, first token at tick 6) prefills at 6 and decodes at 7."""
    cfg = {"serve": {"prefill_chunk": 8}, "hidden_size": 8,
           "num_attention_heads": 2, "num_key_value_heads": 1,
           "num_hidden_layers": 3}
    q = [serve.generate.QueuedRequest(0, np.zeros(10, np.int32), 3),
         serve.generate.QueuedRequest(1, np.zeros(3, np.int32), 2)]
    calls, work = serve.paged_decode_work(cfg, q, {0: 5, 1: 6},
                                          range(4, 8))
    # evaluations per tick: 8, 2, 3, 1; one kernel call per layer each
    assert calls == 14 * 3
    # attended positions per layer: 36 + 19 + (11 + 1 + 2 + 3) + (12 + 4)
    positions = 36 + 19 + 17 + 16
    lanes = 8 + 2 + 4 + 2
    hd = 4
    assert work.bytes == 3 * (2 * positions * 1 * hd * 2
                              + 2 * lanes * 2 * hd * 2)
