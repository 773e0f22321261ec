"""The per-layer metrics read from the program's spans: the tick's host
time from a traced window (``spans.tick_host_ms``), and the set-up split
of ``optimize()`` from the span registry — on hand-built traces, and in
tiny traced runs of both cells."""
from __future__ import annotations

import json

import jax
import pytest

import harness
import reduce_trace as rt
import run
import spans
from conftest import BENCH

V5E = harness.peaks_for("TPU v5 lite")


def _summary(events, window=(100, 1000)) -> rt.TraceSummary:
    return rt.TraceSummary(window=window, device_ops={},
                           host=[rt.Event(n, s, e) for n, s, e in events])


def test_tick_host_time_adds_the_admit_and_leaves_out_the_sync():
    t = _summary([
        # starts before the window: partial, left out
        ("engine.admit", 90, 95), ("engine.tick", 96, 200),
        ("engine.sync", 120, 180),
        # 200 long, 100 of it waiting, after a 10-long admission
        ("engine.admit", 210, 220), ("engine.tick", 230, 430),
        ("engine.sync", 300, 400), ("engine.upload", 240, 250),
        # 200 long, 140 of it waiting, after a 5-long admission
        ("engine.admit", 440, 445), ("engine.tick", 450, 650),
        ("engine.sync", 460, 600),
        # runs past the window: partial, left out
        ("engine.admit", 700, 710), ("engine.tick", 720, 1100),
        ("engine.sync", 730, 1050),
    ])
    assert spans.tick_host_seconds(t) == pytest.approx([110e-9, 65e-9])
    assert spans.tick_host_ms(t) == pytest.approx(87.5e-6)


def test_a_tick_whose_admission_is_not_in_the_trace_is_left_out():
    t = _summary([("engine.tick", 150, 300), ("engine.sync", 160, 200),
                  ("engine.admit", 310, 320), ("engine.tick", 330, 400)])
    assert spans.tick_host_seconds(t) == pytest.approx([80e-9])


def test_no_tick_spans_read_nothing():
    assert spans.tick_host_ms(None) is None
    assert spans.tick_host_ms(_summary([])) is None
    assert spans.tick_host_ms(_summary([("jit_step", 200, 300)])) is None


def test_registry_seconds_reads_one_optimize_call(monkeypatch):
    from repro import obs

    reg = obs.SpanStats(obs.SPAN_NAMES)
    monkeypatch.setattr(obs, "SPANS", reg)
    assert spans.registry_seconds("trace.probe") is None
    reg.counts["optimize.trace"] = 1
    assert spans.registry_seconds("trace.probe") is None     # no probe ran
    reg.counts["trace.probe"], reg.seconds["trace.probe"] = 3, 1.5
    assert spans.registry_seconds("trace.probe") == 1.5
    assert spans.registry_seconds("not.a.span") is None
    reg.counts["optimize.trace"] = 2            # whose set-up would it be?
    assert spans.registry_seconds("trace.probe") is None


def _traced(tiny_bench, workload: str) -> dict:
    manifest = json.loads((tiny_bench.parent / "BENCHMARK.json").read_text())
    args = run.parse(["--workload", workload, "--seed", "3000000031",
                      "--seconds", "1", "--trace", "1"])
    return json.loads(run.measure(manifest, args,
                                  find_devices=lambda n: jax.devices(),
                                  bench=tiny_bench, peaks=V5E))


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_traced_serve_run_reports_the_tick_host_time(tiny_bench):
    out = _traced(tiny_bench, "deepseek-7b-reason")
    assert out["correct"], out["checks"]
    m = out["metrics"]["tick_host_ms.serve"]
    assert m["unit"] == "ms" and 0 < m["value"] < 1e3 * out["device"][
        "window_s"]


def test_traced_train_run_reports_the_setup_split(tiny_bench, monkeypatch):
    from repro import obs

    # the metrics describe the process's one optimize() call: the
    # registry starts empty, as in a benchmark process
    monkeypatch.setattr(obs.SPANS, "counts",
                        dict.fromkeys(obs.SPAN_NAMES, 0))
    monkeypatch.setattr(obs.SPANS, "seconds",
                        dict.fromkeys(obs.SPAN_NAMES, 0.0))
    out = _traced(tiny_bench, "vgg16-train")
    assert out["correct"], out["checks"]
    probe = out["metrics"]["probe_s.paper"]["value"]
    compile_s = out["metrics"]["optimize_compile_s.paper"]["value"]
    assert probe > 0 and compile_s > 0
    assert probe + compile_s <= out["metrics"]["optimize_s.paper"]["value"]
    assert out["metrics"]["probe_s.paper"]["unit"] == "s"


def test_the_readers_are_found_by_name():
    for name in ("tick_host_ms.serve", "probe_s.paper",
                 "optimize_compile_s.paper"):
        assert callable(harness.load_module(
            BENCH / "metrics" / f"{name}.py").read)
