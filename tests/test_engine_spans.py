"""The serve engine's tick split into ``engine.*`` spans (``repro.obs``):
one tick span per mixed step, each holding exactly one sync, the verify
span only where the engine verifies, no span open while the consumer
holds an event, and ``Engine.report()["host_spans"]`` as the registry's
delta over the run."""
from __future__ import annotations

import importlib
import pathlib
import sys
import time

import jax
import numpy as np
import pytest

from repro.launch import engine as engine_mod
from repro.launch.engine import Request
from repro.launch.serve import ServeConfig, Server
from repro.obs import SPANS

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
PAUSE = "consumer.pause"


def _reduce_trace():
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    return importlib.import_module("reduce_trace")


@pytest.fixture(scope="module")
def server():
    return Server(ServeConfig(arch="deepseek-7b", batch=2, prompt_len=6,
                              new_tokens=4, max_len=24))


def _queue(vocab: int, n: int = 4) -> list[Request]:
    rng = np.random.default_rng(1)
    return [Request(request_id=i, max_new_tokens=3 + i % 2,
                    prompt=rng.integers(1, vocab, 5 + i).tolist())
            for i in range(n)]


def _engine(server, verify_mode="strict"):
    return server.engine(slots=2, prefill_chunk=4, kv_layout="paged",
                         kv_block_size=4, verify_mode=verify_mode)


@pytest.mark.parametrize("verify_mode", ["strict", "off"])
def test_one_tick_span_per_mixed_step(server, verify_mode):
    e = _engine(server, verify_mode)
    reqs = _queue(server.cfg.vocab_size)
    e.run(reqs)                                   # compile outside the count
    spans0, stats0 = SPANS.snapshot(), engine_mod.STATS.snapshot()
    out = e.run(reqs)
    d, steps = SPANS.delta(spans0), engine_mod.STATS.delta(stats0)
    assert all(c.status == "ok" for c in out)
    ticks = steps["mixed_step"]
    assert ticks > 0 and d["engine.tick"]["count"] == ticks
    for name in ("engine.prepare", "engine.upload", "engine.dispatch",
                 "engine.sync", "engine.commit"):
        assert d[name]["count"] == ticks, name
    # one admission per scheduler iteration: each tick's, and the last
    # one that finds every slot empty
    assert d["engine.admit"]["count"] == ticks + 1
    want_verify = ticks if verify_mode == "strict" else 0
    assert d["engine.verify"]["count"] == want_verify
    inner = sum(d[k]["seconds"] for k in (
        "engine.prepare", "engine.verify", "engine.upload",
        "engine.dispatch", "engine.sync", "engine.commit"))
    assert inner <= d["engine.tick"]["seconds"]


def test_report_host_spans_is_the_runs_delta(server):
    e = _engine(server)
    reqs = _queue(server.cfg.vocab_size)
    assert e.report()["host_spans"] == {}
    before = SPANS.snapshot()
    e.run(reqs)
    d = SPANS.delta(before)
    host = e.report()["host_spans"]
    assert host == {k: v for k, v in d.items() if k.startswith("engine.")}
    assert host["engine.tick"]["count"] == e.last_dispatch["mixed_step"]


def test_ticks_hold_one_sync_and_no_span_spans_a_consumer_pause(
        server, tmp_path):
    """Under a profiler: every tick span holds exactly one sync span, and
    no engine span overlaps the consumer's own time between events."""
    rt = _reduce_trace()
    e = _engine(server)
    reqs = _queue(server.cfg.vocab_size)
    e.run(reqs)
    with rt.traced(tmp_path / "trace") as tr:
        for _ in e.stream(reqs):
            with jax.profiler.TraceAnnotation(PAUSE):
                time.sleep(0.003)
    host = tr.result.host
    ticks = [ev for ev in host if ev.name == "engine.tick"]
    syncs = [ev for ev in host if ev.name == "engine.sync"]
    pauses = [ev for ev in host if ev.name == PAUSE]
    assert ticks and pauses
    assert len(ticks) == e.last_dispatch["mixed_step"]
    for t in ticks:
        inside = [s for s in syncs if t.start <= s.start and s.end <= t.end]
        assert len(inside) == 1
    engine_spans = [ev for ev in host if ev.name.startswith("engine.")]
    for p in pauses:
        assert not any(ev.start < p.end and p.start < ev.end
                       for ev in engine_spans)
