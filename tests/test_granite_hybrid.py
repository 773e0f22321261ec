"""granite-4.0-h-small at CPU size: a scanned period of distinct layers
(Mamba-2 + MoE x5, NoPE attention + MoE, Mamba-2 + MoE x4), muP
multipliers, a held share of the experts, and the serve engine's two kinds
of cache (paged KV for the attention layer, dense state for the Mamba
layers).  Weights are seeded random, float32."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import RuntimeConfig
from repro.launch import engine as engine_mod
from repro.launch.engine import Engine, Request
from repro.layers import base, moe
from repro.models import lm

ARCH = "granite-4.0-h-small"
PERIOD = ("mamba_moe",) * 5 + ("attn_moe",) + ("mamba_moe",) * 4


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config(ARCH).reduced()
    params, _ = lm.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_layer_plan_takes_the_period_from_the_pattern():
    cfg = get_config(ARCH)
    plan = lm.layer_plan(cfg)
    assert plan.superblock == PERIOD and plan.n_super == 4 and not plan.tail
    assert lm.layer_plan(cfg.reduced()).n_super == 1
    with pytest.raises(ValueError, match="layer_pattern"):
        lm.layer_plan(dataclasses.replace(cfg, n_layers=15))
    # zamba2 keeps its weight-shared attention plan
    zamba = lm.layer_plan(get_config("zamba2-7b"))
    assert zamba.superblock == ("mamba",) * 13 + ("shared_attn",)
    assert zamba.n_super == 5 and zamba.tail == ("mamba",) * 11


def test_every_other_config_keeps_the_defaults():
    for arch in ARCH_IDS:
        if arch == ARCH:
            continue
        c = get_config(arch)
        assert (c.layer_pattern, c.embedding_multiplier,
                c.residual_multiplier, c.attention_multiplier,
                c.logits_scaling, c.use_rope, c.norm_eps, c.expert_share,
                c.ssm_conv_bias) == \
            ((), None, 1.0, None, 1.0, True, 1e-6, None, False), arch


def test_published_sizes_and_share():
    cfg = get_config(ARCH)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == \
        (4096, 32, 8, 128)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state) == (128, 64, 128)
    assert (cfg.n_experts, cfg.top_k, cfg.d_ff, cfg.shared_expert_ff) == \
        (72, 10, 768, 1536)
    assert cfg.held_experts == (0, 9)
    params = jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0),
                        dataclasses.replace(cfg, n_layers=10))[0])
    sub = params["blocks"]["sub0"]
    assert sub["moe"]["router"].shape == (1, 4096, 72)
    assert sub["moe"]["wg"].shape == (1, 9, 4096, 768)
    assert sub["mixer"]["conv_bias"].shape == (1, 8192 + 2 * 128)
    assert params["blocks"]["sub5"]["attn"]["wk"].shape == (1, 4096, 1024)


def _share_params(full: dict, first: int, count: int) -> dict:
    out = {k: v for k, v in full.items() if k != "shared"}
    for k in ("wg", "wu", "wd"):
        out[k] = full[k][first:first + count]
    return out


def _plain_moe_layer(p: dict, x, top_k: int):
    """The uncut layer written out: softmax over each token's top-k router
    logits, each chosen expert's gated MLP times its gate, plus the shared
    expert."""
    xt = x.reshape(-1, x.shape[-1])
    top, idx = jax.lax.top_k(xt @ p["router"], top_k)
    gates = jax.nn.softmax(top, axis=-1)
    out = jnp.zeros_like(xt)
    for e in range(p["wg"].shape[0]):
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        y = (jax.nn.silu(xt @ p["wg"][e]) * (xt @ p["wu"][e])) @ p["wd"][e]
        out = out + g[:, None] * y
    s = p["shared"]
    out = out + (jax.nn.silu(xt @ s["wg"]) * (xt @ s["wu"])) @ s["wd"]
    return out.reshape(x.shape)


@pytest.mark.parametrize("dropless", [True, False])
def test_expert_shares_sum_to_the_uncut_layer(dropless):
    """Four chips holding two experts each: their partial outputs, with the
    shared expert counted once, add up to the whole layer."""
    cfg = dataclasses.replace(get_config(ARCH).reduced(), n_experts=8,
                              top_k=3, expert_share=None,
                              capacity_factor=8 / 3)
    full, _ = base.split(moe.init(jax.random.PRNGKey(1), cfg))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, cfg.d_model))
    rt = RuntimeConfig(mode="xla")
    total = moe.shared(full, x, cfg, rt)
    held = 0
    for first in range(0, 8, 2):
        share = dataclasses.replace(cfg, expert_share=(first, 2))
        part, aux = moe.apply(_share_params(full, first, 2), x, share, rt,
                              dropless=dropless)
        total = total + part
        held = held + aux["held"]
    uncut, aux = moe.apply(full, x, cfg, rt, dropless=dropless)
    uncut = uncut + moe.shared(full, x, cfg, rt)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(_plain_moe_layer(full, x, 3)),
                               rtol=1e-4, atol=1e-4)
    # every token's top-k assignments land on exactly one share
    assert np.all(np.asarray(held) == 3)
    np.testing.assert_array_equal(np.asarray(aux["held"]), 3)


def test_absent_experts_add_nothing():
    """A share whose experts no token picks returns only zeros."""
    cfg = dataclasses.replace(get_config(ARCH).reduced(), n_experts=8,
                              top_k=1, expert_share=(6, 2))
    p, _ = base.split(moe.init(jax.random.PRNGKey(3), cfg))
    p["router"] = p["router"].at[:, 6:].set(-1e3)   # never the top choice
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (1, 4,
                                                          cfg.d_model)))
    y, aux = moe.apply(p, x, cfg, RuntimeConfig(mode="xla"), dropless=True)
    assert float(jnp.max(jnp.abs(y))) == 0.0
    assert int(jnp.sum(aux["held"])) == 0


SWITCHES = {"use_rope": True, "embedding_multiplier": None,
            "residual_multiplier": 1.0, "attention_multiplier": None,
            "logits_scaling": 1.0}


@pytest.mark.parametrize("field", sorted(SWITCHES))
def test_turning_off_nope_or_a_multiplier_moves_the_logits(tiny, field):
    cfg, params = tiny
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        0, cfg.vocab_size, (2, 12)), jnp.int32)
    rt = RuntimeConfig(mode="xla")
    want, _ = lm.forward(params, {"tokens": tokens}, cfg, rt)
    off = dataclasses.replace(cfg, **{field: SWITCHES[field]})
    got, _ = lm.forward(params, {"tokens": tokens}, off, rt)
    scale = float(jnp.std(want))
    assert float(jnp.max(jnp.abs(got - want))) > 1e-2 * scale


def _reference_logits(params, cfg, tokens):
    return lm.forward(params, {"tokens": jnp.asarray(tokens)[None]}, cfg,
                      RuntimeConfig(mode="xla"))[0][0]


@pytest.mark.parametrize("mode", ["xla", "brainslug"])
def test_paged_decode_through_both_caches_matches_the_full_forward(tiny,
                                                                   mode):
    """Slots of different prompt lengths prefill in chunks beside slots that
    decode, through the engine's jitted mixed step (paged KV for the
    attention layer, dense state for the Mamba layers); every consumed
    position's logits match the full forward.  Tolerance 2e-4 of the
    logits' scale: float32 throughout, the only differences are the order
    of the sums (chunked SSD scan against the recurrence, flash against
    dense attention)."""
    cfg, params = tiny
    rt = RuntimeConfig(mode=mode, kv_layout="paged", kv_block_size=4)
    rng = np.random.default_rng(6)
    lens = [13, 5, 9]
    seqs = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]
    b, mb, chunk = len(seqs), 4, 3
    cache = lm.init_decode_cache(cfg, b, mb * 4, dtype=jnp.float32,
                                 kv_layout="paged", kv_num_blocks=b * mb,
                                 kv_block_size=4)
    tables = jnp.asarray(np.arange(b * mb).reshape(b, mb)[:, ::-1].copy(),
                         jnp.int32)
    step = jax.jit(lambda c, tok, act: lm.decode_step(
        params, c, tok, cfg, rt, act, block_tables=tables,
        count_experts=True))
    pos = [0] * b
    got = [[] for _ in seqs]
    held = 0
    while any(p < n for p, n in zip(pos, lens)):
        # each tick a lane consumes up to `chunk` tokens (prefill) one
        # evaluation at a time; a short lane rides idle once it is done
        for _ in range(chunk):
            tok = np.zeros((b, 1), np.int32)
            act = np.zeros((b,), bool)
            for i, s in enumerate(seqs):
                if pos[i] < lens[i]:
                    tok[i, 0], act[i] = s[pos[i]], True
            if not act.any():
                break
            logits, cache, h = step(cache, jnp.asarray(tok),
                                    jnp.asarray(act))
            held += int(h)
            for i in range(b):
                if act[i]:
                    got[i].append(np.asarray(logits[i, 0]))
                    pos[i] += 1
    for s, g in zip(seqs, got):
        want = np.asarray(_reference_logits(params, cfg, s))
        scale = float(np.abs(want).max())
        np.testing.assert_allclose(np.stack(g), want, rtol=0,
                                   atol=2e-4 * scale)
    assert 0 < held <= sum(lens) * cfg.top_k * cfg.n_layers


def test_engine_serves_the_hybrid_with_both_caches(tiny):
    """The same constructor, scheduler and step as every paged model:
    greedy tokens are the full forward's argmax, prefix sharing stays off
    for the recurrent state, and the counters land in the report."""
    cfg, params = tiny
    rt = RuntimeConfig(mode="brainslug", kv_layout="paged", kv_block_size=4)
    eng = Engine(cfg, params, rt, slots=2, max_len=32, prefill_chunk=4,
                 verify_mode="strict")
    assert not eng.prefix_sharing
    rng = np.random.default_rng(7)
    reqs = [Request(request_id=i, prompt=rng.integers(
        0, cfg.vocab_size, (n,)).astype(np.int32), max_new_tokens=6)
        for i, n in enumerate([7, 3, 10])]
    done = eng.run(reqs)
    for r, c in zip(reqs, done):
        seq = np.concatenate([r.prompt, c.tokens[:-1]])
        want = np.asarray(_reference_logits(params, cfg, seq))
        pos = np.arange(len(r.prompt) - 1, len(seq))
        np.testing.assert_array_equal(c.tokens, want[pos].argmax(-1))
    rep = eng.report()
    d = rep["dispatch"]
    assert rep["decode_path"] == "pallas-paged-decode"
    consumed = d["prefill_tokens"] + d["decode_slot_steps"]
    assert consumed == sum(len(r.prompt) + 5 for r in reqs)
    assert d["ssm_state_updates"] == 9 * consumed
    assert d["moe_routed_assignments"] == consumed * cfg.top_k * 10
    assert 0 < d["moe_held_assignments"] < d["moe_routed_assignments"]


def test_only_models_with_experts_compile_the_held_count():
    rt = RuntimeConfig(mode="xla", kv_layout="paged", kv_block_size=4)
    for arch, n_out in (("deepseek-7b", 2), (ARCH, 3)):
        cfg = get_config(arch).reduced()
        params = jax.eval_shape(
            lambda c=cfg: lm.init(jax.random.PRNGKey(0), c)[0])
        cache = jax.eval_shape(lambda c=cfg: lm.init_decode_cache(
            c, 2, 8, dtype=jnp.float32, kv_layout="paged", kv_num_blocks=4,
            kv_block_size=4))
        i32 = jax.ShapeDtypeStruct((2,), jnp.int32)
        out = jax.eval_shape(
            engine_mod._jitted_mixed_step(cfg, rt), params, cache,
            jax.ShapeDtypeStruct((2, 2), jnp.int32),
            jax.ShapeDtypeStruct((2, 4), jnp.int32), i32, i32, i32,
            jax.ShapeDtypeStruct((2,), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        assert len(out) == n_out, arch


def test_norm_eps_reaches_every_norm(tiny):
    """The config's eps is the one every norm uses: at a huge eps the
    normalised activations shrink and the logits move."""
    cfg, params = tiny
    tokens = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    rt = RuntimeConfig(mode="xla")
    a, _ = lm.forward(params, {"tokens": tokens}, cfg, rt)
    b, _ = lm.forward(params, {"tokens": tokens},
                      dataclasses.replace(cfg, norm_eps=10.0), rt)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-2 * float(jnp.std(a))
