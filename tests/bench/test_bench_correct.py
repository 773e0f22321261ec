"""Whole runs at CPU test size, past the harness's look for a chip: a
sound run is ``correct``, and a run whose timed path is broken underneath
is not — once for each fault a cell can have.  A step that returns its
state unchanged, and half of each batch left out (the mean taken over
the rest), in the training cell; a token altered where the engine
produces it, in the serve cells; an answer altered where the optimized
forward produces it, in the inference cell.  (Every cell runs on one
chip: no exchange between chips exists to leave out.)"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import pytest

import harness
import run
from conftest import BENCH

image = harness.load_module(BENCH / "drivers" / "image.py")
V5E = harness.peaks_for("TPU v5 lite")


def measure(tiny_bench, workload: str, trace: int = 0,
            seconds: float = 2.0) -> dict:
    manifest = json.loads((tiny_bench.parent / "BENCHMARK.json").read_text())
    args = run.parse(["--workload", workload, "--seed", "3000000029",
                      "--seconds", str(seconds), "--trace", str(trace)])
    line = run.measure(manifest, args, find_devices=lambda n: jax.devices(),
                       bench=tiny_bench, peaks=V5E)
    return json.loads(line)


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


@pytest.mark.parametrize("workload", ["vgg16-train", "vgg16-infer",
                                      "deepseek-7b-reason"])
def test_sound_run_is_correct(tiny_bench, workload):
    out = measure(tiny_bench, workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert "setup_s" in out["metrics"]


def test_requests_finished_after_the_window_are_checked(tiny_bench):
    """A window too short for any request to finish: the engine serves
    on, untimed, and the check covers the requests finished then."""
    out = measure(tiny_bench, "deepseek-7b-reason", seconds=0.05)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_traced_run_reports_per_layer_metrics(tiny_bench):
    out = measure(tiny_bench, "deepseek-7b-reason", trace=1)
    assert out["correct"], out["checks"]
    assert {"itl_p50_ms.serve", "mfu.serve"} <= set(out["metrics"])
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def _broken_train_step(kind):
    real = image.program_step

    def broken(net, cell):
        if kind == "unchanged":
            step = real(net, cell)

            def frozen(p, m, x, y):
                _, _, loss = step(p, m, x, y)
                return p, m, loss
            return frozen
        half = cell.traffic["batch"] // 2
        return image.make_train_step(
            lambda p, x, y: cell.model.cross_entropy(
                net(x, p)[:half], y[:half]), cell.cfg["optimizer"])
    return broken


@pytest.mark.parametrize("kind", ["unchanged", "half_batch"])
def test_broken_training_step_is_not_correct(tiny_bench, monkeypatch, kind):
    monkeypatch.setattr(image, "program_step", _broken_train_step(kind))
    out = measure(tiny_bench, "vgg16-train")
    assert out["correct"] is False, out["checks"]


def test_altered_answer_is_not_correct(tiny_bench, monkeypatch):
    real = image.program_step

    def broken(net, cell):
        f = real(net, cell)
        return lambda x, p: f(x, p).at[1, 0].add(1.0)
    monkeypatch.setattr(image, "program_step", broken)
    out = measure(tiny_bench, "vgg16-infer")
    assert out["correct"] is False, out["checks"]


def test_altered_token_is_not_correct(tiny_bench, monkeypatch):
    from repro.launch import engine as engine_mod

    def broken(cfg, rt):
        raw = engine_mod._mixed_step_fn(cfg, rt)

        def step(*args):
            nxt, cache = raw(*args)
            tidx = args[-3]
            nxt = jnp.where(tidx == 2, (nxt + 1) % cfg.vocab_size, nxt)
            return nxt, cache
        return jax.jit(step, donate_argnums=(1,))
    monkeypatch.setattr(engine_mod, "_jitted_mixed_step", broken)
    out = measure(tiny_bench, "deepseek-7b-reason")
    assert out["correct"] is False, out["checks"]
