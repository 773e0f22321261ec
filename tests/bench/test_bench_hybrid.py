"""The granite-4.0-h-small cell at CPU test size: its plain reference
against the program (the full forward, and the serve engine's prefill and
decode through paged KV and dense Mamba state), whole ``serve_hybrid``
runs past the chip check with each planted fault reading ``correct``
false, the FLOP counts behind ``mfu.serve`` there, device time by named
scope on an HLO and a trace recorded on a TPU v5e, and a program that
does not know the architecture failing before any device work."""
from __future__ import annotations

import dataclasses
import functools
import gzip
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import reduce_trace
import run
import scopes
from conftest import BENCH, TINY

hybrid = harness.load_module(BENCH / "drivers" / "serve_hybrid.py")
ref = harness.load_module(BENCH / "configs" / "granite-4.0-h-small.py")
DATA = pathlib.Path(__file__).resolve().parent / "data"
WORKLOAD = "granite-h-small-reason4k"
V5E = harness.peaks_for("TPU v5 lite")


@pytest.fixture(scope="module")
def case():
    cfg = json.loads((TINY / "configs" / "granite-4.0-h-small.json")
                     .read_text())
    w = jax.jit(functools.partial(ref.init, cfg))(jax.random.PRNGKey(3))
    return cfg, w


def test_reference_matches_the_program_forward(case):
    """Float32 on both sides: the program's chunked SSD scan and fused
    stacks against the token-by-token recurrence; tolerance 1e-4 of the
    logits' scale (the order of the sums).  The forward's expert capacity
    is set to hold every token, as serving's is."""
    from repro.configs.base import RuntimeConfig
    from repro.models import lm

    cfg, w = case
    mc = hybrid.program_config(cfg)
    mc = dataclasses.replace(mc, capacity_factor=mc.n_experts / mc.top_k)
    params = hybrid.program_params(w, cfg)
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], 48)
    want = np.asarray(ref.logits(w, jnp.asarray(tokens), cfg))
    for mode in ("xla", "brainslug"):
        got, _ = lm.forward(params, {"tokens": jnp.asarray(tokens)[None]},
                            mc, RuntimeConfig(mode=mode))
        np.testing.assert_allclose(np.asarray(got[0]), want, rtol=0,
                                   atol=1e-4 * np.abs(want).max())


def test_engine_greedy_tokens_are_the_reference_argmax(case):
    """Served through the engine (8-token prefill chunks over 4 slots,
    paged KV for the attention layer, dense state for the Mamba layers):
    every greedy token is the float32 reference's best at its position."""
    from repro.launch.engine import Request

    cfg, w = case
    engine = hybrid.build_engine(hybrid.program_config(cfg), cfg,
                                 hybrid.program_params(w, cfg), seed=0)
    rng = np.random.default_rng(1)
    reqs = [Request(request_id=i, max_new_tokens=12,
                    prompt=rng.integers(0, cfg["vocab_size"], (p,)))
            for i, p in enumerate((5, 19, 11, 30, 8))]
    done = engine.run(reqs)
    for r, c in zip(reqs, done):
        gap, k = hybrid.serve.sequence_gap(
            ref, w, cfg, np.asarray(r.prompt), c.tokens,
            cfg["serve"]["max_len"])
        assert k == 12 and gap <= 1e-5


def test_served_gaps_widest_is_the_serve_drivers_gap(case):
    """The per-token gaps the hybrid driver checks (widest and mean) are
    those of ``serve.served_logit_gap`` over the same sample: their
    widest is its number, for the program's tokens and the control's."""
    import types

    from repro.launch.engine import Request

    cfg, w = case
    engine = hybrid.build_engine(hybrid.program_config(cfg), cfg,
                                 hybrid.program_params(w, cfg), seed=0)
    rng = np.random.default_rng(5)
    queue = [types.SimpleNamespace(
        prompt=rng.integers(0, cfg["vocab_size"], (p,)).astype(np.int32),
        max_new=n) for p, n in ((7, 9), (12, 6), (5, 11))]
    done = {c.request_id: c for c in engine.run([
        Request(request_id=i, prompt=q.prompt, max_new_tokens=q.max_new)
        for i, q in enumerate(queue)])}
    cell = types.SimpleNamespace(cfg=cfg, model=ref, seed=3,
                                 traffic={"sample_tokens": 15})
    for quant in (None, "fp8"):
        gaps = hybrid.served_gaps(cell, w, queue, done, quant)
        widest, n = hybrid.serve.served_logit_gap(cell, w, queue, done,
                                                  quant)
        assert len(gaps) == n and gaps.max() == pytest.approx(widest)
        assert (gaps >= 0).all()


def test_fp8_control_reads_wider_than_the_program(case):
    cfg, w = case
    seq = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg["vocab_size"], 96))
    exact = np.asarray(ref.logits(w, seq, cfg))
    low = np.asarray(ref.logits(w, seq, cfg, "fp8"))
    assert (low.argmax(-1) != exact.argmax(-1)).any()


def test_the_reference_computes_only_the_held_experts(case):
    """Experts outside the held share leave the reference untouched, and
    zeroing a held one moves it."""
    cfg, w = case
    seq = jnp.asarray(np.random.default_rng(4).integers(
        0, cfg["vocab_size"], 24))
    base = np.asarray(ref.logits(w, seq, cfg))
    shifted = dict(cfg, first_held_expert=cfg["router_experts"]
                   - cfg["num_local_experts"])
    other = np.asarray(ref.logits(w, seq, shifted))
    assert np.abs(other - base).max() > 0
    zero = jax.tree_util.tree_map(lambda a: a, w)
    zero["layers"] = [dict(lw, wd=lw["wd"].at[:, 0].set(0))
                      for lw in w["layers"]]
    assert np.abs(np.asarray(ref.logits(zero, seq, cfg)) - base).max() > 0


def test_period_of_the_layer_types():
    kinds = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert ref.period({"layer_types": kinds * 4,
                       "num_hidden_layers": 40}) == 10
    assert ref.period({"layer_types": kinds, "num_hidden_layers": 10}) == 10
    assert ref.period({"layer_types": ["mamba"] * 6,
                       "num_hidden_layers": 6}) == 1


def test_published_flop_counts():
    """Hand count at the cell's sizes: a Mamba mixer multiplies
    4096 x (2 x 8192 + 2 x 128 + 128) + 8192 x 4096 weights per token, the
    attention projections 4096 x (4096 + 2 x 1024) + 4096 x 4096, every
    layer's router 4096 x 72 and shared expert 3 x 4096 x 1536, the tied
    head 4096 x 100352."""
    cfg = json.loads((BENCH / "configs" / "granite-4.0-h-small.json")
                     .read_text())
    mamba = 4096 * (2 * 8192 + 2 * 128 + 128) + 8192 * 4096
    attn = 4096 * (4096 + 2 * 1024) + 4096 * 4096
    per_layer = 4096 * 72 + 3 * 4096 * 1536
    want = 2 * (9 * mamba + attn + 10 * per_layer + 4096 * 100352)
    assert hybrid.dense_flops_per_token(cfg) == want
    assert hybrid.expert_flops_per_assignment(cfg) == 2 * 3 * 4096 * 768


def test_mfu_reader_adds_the_held_expert_work():
    """``mfu.serve`` over the hybrid driver's FLOPs per processed token:
    the dense work of every token plus one expert's per held
    assignment."""
    reader = harness.load_module(BENCH / "metrics" / "mfu.serve.py")
    cfg = json.loads((BENCH / "configs" / "granite-4.0-h-small.json")
                     .read_text())
    per_token = hybrid.flops_per_token(cfg, 100, 40)
    rec = harness.Record(facts={"window_s": 2.0, "processed_tokens": 100,
                                "flops_per_token": per_token})
    want = 100.0 * (100 * hybrid.dense_flops_per_token(cfg)
                    + 40 * hybrid.expert_flops_per_assignment(cfg)) \
        / 2.0 / V5E["bf16_flops_per_s"]
    assert reader.read(rec, V5E) == pytest.approx(want)
    assert hybrid.flops_per_token(cfg, 0, 0) == \
        hybrid.dense_flops_per_token(cfg)


def test_unknown_architecture_fails_before_device_work(case, monkeypatch):
    """A program that does not know the architecture (as the parent of
    this cell does not) raises ``BenchError`` from the configuration
    alone."""
    import repro.configs

    cfg, _ = case

    def unknown(arch):
        raise KeyError(arch)
    monkeypatch.setattr(repro.configs, "get_config", unknown)
    with pytest.raises(harness.BenchError, match="cannot serve"):
        hybrid.program_config(cfg)


# -- whole runs -----------------------------------------------------------------

def measure(tiny_bench, trace: int = 0, seconds: float = 2.0) -> dict:
    manifest = json.loads((tiny_bench.parent / "BENCHMARK.json").read_text())
    args = run.parse(["--workload", WORKLOAD, "--seed", "3000000031",
                      "--seconds", str(seconds), "--trace", str(trace)])
    line = run.measure(manifest, args, find_devices=lambda n: jax.devices(),
                       bench=tiny_bench, peaks=V5E)
    return json.loads(line)


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_sound_run_is_correct_and_reports_the_hybrid_metrics(tiny_bench):
    out = measure(tiny_bench, trace=1)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    metrics = out["metrics"]
    assert 0 < metrics["mfu.serve"]["value"] < 100
    assert {"itl_p50_ms.serve", "tick_host_ms.serve"} <= set(metrics)
    assert list(out)[-1] == "checks"


def _fault(kind):
    real_config, real_params = hybrid.program_config, hybrid.program_params

    def config(cfg):
        mc = real_config(cfg)
        if kind == "rope":
            return dataclasses.replace(mc, use_rope=True)
        if kind == "residual":
            return dataclasses.replace(mc, residual_multiplier=1.0)
        return mc

    def params(w, cfg):
        p = real_params(w, cfg)
        if kind == "expert0":
            for sub in p["blocks"].values():
                sub["moe"] = dict(sub["moe"],
                                  wd=sub["moe"]["wd"].at[:, 0].set(0))
        return p
    return config, params


@pytest.mark.parametrize("kind", ["rope", "residual", "expert0"])
def test_planted_fault_is_not_correct(tiny_bench, monkeypatch, kind):
    """RoPE turned on, the residual multiplier left at 1, one held
    expert's output zeroed (what a zero gate gives): each in the program
    alone, against the sound reference."""
    config, params = _fault(kind)
    monkeypatch.setattr(hybrid, "program_config", config)
    monkeypatch.setattr(hybrid, "program_params", params)
    out = measure(tiny_bench)
    assert out["correct"] is False, out["checks"]


# -- device time by scope, on a recording from the chip --------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The compiled mixed step of a granite engine at CPU-test widths
    (``reduced()``, bfloat16, 4 slots, paged) and 16 streamed events of
    its trace, both recorded on one TPU v5e."""
    xplane = tmp_path_factory.mktemp("trace") / "granite_tiny.xplane.pb"
    xplane.write_bytes(gzip.decompress(
        (DATA / "granite_tiny.xplane.pb.gz").read_bytes()))
    hlo = gzip.decompress((DATA / "granite_tiny.hlo.txt.gz").read_bytes())
    return reduce_trace.load(xplane), hlo.decode()


def test_each_scope_holds_device_time(recorded):
    trace, hlo = recorded
    module, names = scopes.op_names(hlo)
    assert module.startswith("jit_mixed_step")
    parts = {s: scopes.scope_seconds(trace, hlo, s) for s in
             ("moe", "mamba", "attn",
              "shared")}
    assert all(v and v > 0 for v in parts.values()), parts
    busy = trace.busy_s
    assert sum(parts.values()) <= busy * (1 + 1e-9)


def test_a_matmul_fused_with_the_residual_scale_keeps_its_scope(recorded):
    """The Mamba out-projection fused with the residual multiplier: its
    root is the scale's convert, outside the mixer's scope, and the
    fusion's own metadata is the projection's."""
    _, hlo = recorded
    _, names = scopes.op_names(hlo)
    name = "%multiply_convert_fusion.39"
    line = next(x for x in hlo.splitlines() if f"{name} = " in x)
    assert "mamba/dot_general" in line
    assert scopes.in_scope(names[name], "mamba")


def test_per_evaluation_reading_and_a_program_without_scopes(recorded):
    trace, hlo = recorded
    rec = harness.Record(trace=trace, facts={"step_hlo": hlo,
                                             "traced_evaluations": 4})
    moe = scopes.ms_per_evaluation(rec, "moe")
    assert moe == pytest.approx(
        1e3 * scopes.scope_seconds(trace, hlo, "moe") / 4)
    assert scopes.ms_per_evaluation(rec, "no_such_scope") is None
    assert scopes.ms_per_evaluation(harness.Record(), "moe") is None


def test_op_names_of_a_small_program():
    text = "\n".join([
        "HloModule jit_f, entry_computation_layout={()->f32[]}",
        "",
        "%fused_computation (p: f32[4]) -> f32[4] {",
        "  %p = f32[4]{0} parameter(0)",
        '  ROOT %m = f32[4]{0} multiply(%p, %p), '
        'metadata={op_name="jit(f)/moe/router/mul"}',
        "}",
        "",
        "ENTRY %main (a: f32[4]) -> f32[4] {",
        "  %a = f32[4]{0} parameter(0)",
        "  %fusion = f32[4]{0} fusion(%a), kind=kLoop, "
        "calls=%fused_computation",
        '  ROOT %fusion.1 = f32[4]{0} fusion(%fusion), kind=kLoop, '
        'calls=%fused_computation, metadata={op_name="jit(f)/mul"}',
        "}"])
    module, names = scopes.op_names(text)
    assert module == "jit_f"
    assert names["%fusion"] == "jit(f)/moe/router/mul"   # root's
    assert names["%fusion.1"] == "jit(f)/mul"                     # own
    assert scopes.in_scope(names["%fusion"], "moe")
    assert not scopes.in_scope(names["%fusion"], "mamba")


def test_a_prefetch_takes_its_users_scope():
    """``copy-start`` and ``copy-done`` carry no metadata: the chain takes
    the scope of the instruction that reads what it copied."""
    text = "\n".join([
        "HloModule jit_f, entry_computation_layout={()->f32[]}",
        "",
        "ENTRY %main (w: bf16[8,128]) -> bf16[8,128] {",
        "  %w = bf16[8,128]{1,0} parameter(0)",
        "  %copy-start = (bf16[8,128]{1,0:S(1)}, bf16[8,128]{1,0}, u32[]) "
        "copy-start(%w)",
        "  %copy-done = bf16[8,128]{1,0:S(1)} copy-done(%copy-start)",
        '  ROOT %dot = bf16[8,128]{1,0} multiply(%copy-done, %copy-done), '
        'metadata={op_name="jit(f)/mamba/dot_general"}',
        "}"])
    _, names = scopes.op_names(text)
    assert names["%copy-done"] == names["%copy-start"] == \
        "jit(f)/mamba/dot_general"
    assert "%w" in names      # a parameter read only by the chain
