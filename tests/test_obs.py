"""The one registry of counters and spans (``repro.obs``): declared names,
the snapshot/delta protocol, nesting, the spans' landing on a profiler's
host plane, and the set-up split ``optimize()`` records with them."""
from __future__ import annotations

import importlib
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, obs
from repro.obs import SPANS, DispatchStats, SpanStats

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"


def _reduce_trace():
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    return importlib.import_module("reduce_trace")


def test_every_counter_shares_the_one_class():
    from repro.core import autotune, registry
    from repro.kernels.attention import ops as attn_ops
    from repro.kernels.fused_stack import ops as fused_ops
    from repro.launch import engine, serve

    for mod in (fused_ops, attn_ops, autotune, registry, engine, serve):
        assert type(mod.STATS) is DispatchStats, mod.__name__


def test_span_names_are_declared_and_unknown_ones_refused():
    reg = SpanStats(("a", "b"))
    assert reg.snapshot() == {"a": (0, 0.0), "b": (0, 0.0)}
    with pytest.raises(KeyError, match="unknown span 'c'"):
        reg.span("c")
    with pytest.raises(KeyError):
        obs.span("engine.nothing")
    assert len(set(obs.SPAN_NAMES)) == len(obs.SPAN_NAMES)
    assert set(SPANS.snapshot()) == set(obs.SPAN_NAMES)


def test_snapshot_and_delta_count_and_seconds():
    reg = SpanStats(("a", "b"))
    with reg.span("a"):
        time.sleep(0.01)
    before = reg.snapshot()
    for _ in range(3):
        with reg.span("a"):
            time.sleep(0.005)
    d = reg.delta(before)
    assert d["a"]["count"] == 3 and d["b"] == {"count": 0, "seconds": 0.0}
    assert 0.015 <= d["a"]["seconds"] < 0.5
    assert reg.snapshot()["a"][0] == 4
    # a span left by an exception is still timed, and the error propagates
    with pytest.raises(ValueError):
        with reg.span("b"):
            raise ValueError("inside")
    assert reg.delta(before)["b"]["count"] == 1
    reg.reset()
    assert reg.snapshot() == {"a": (0, 0.0), "b": (0, 0.0)}


def test_nested_spans_each_time_their_own_body():
    reg = SpanStats(("outer", "inner"))
    with reg.span("outer"):
        time.sleep(0.005)
        for _ in range(2):
            with reg.span("inner"):
                time.sleep(0.01)
    d = reg.delta({})
    assert d["inner"]["count"] == 2 and d["outer"]["count"] == 1
    assert d["inner"]["seconds"] >= 0.02
    assert d["outer"]["seconds"] >= d["inner"]["seconds"] + 0.005


def test_spans_land_on_the_profilers_host_plane(tmp_path):
    """Under a live CPU profiler, each span is a host event of its name
    on the trace's clock, nested as it was opened."""
    rt = _reduce_trace()
    f = jax.jit(lambda x: x * 2 + 1)
    x = jnp.ones((8,))
    f(x).block_until_ready()
    with rt.traced(tmp_path / "trace") as tr:
        with obs.span("engine.tick"):
            with obs.span("engine.sync"):
                f(x).block_until_ready()
                time.sleep(0.002)
    host = {e.name: e for e in tr.result.host}
    tick, sync = host["engine.tick"], host["engine.sync"]
    assert tick.start <= sync.start and sync.end <= tick.end
    assert sync.seconds >= 0.002
    lo, hi = tr.result.window
    assert lo <= tick.start and tick.end <= hi


def test_a_span_costs_microseconds_with_no_profiler():
    n = 2000
    reg = SpanStats(("a",))
    t = time.perf_counter()
    for _ in range(n):
        with reg.span("a"):
            pass
    per = (time.perf_counter() - t) / n
    assert per < 50e-6, per


# -- optimize() -------------------------------------------------------------

def _mlp(x, p):
    h = jax.nn.relu(x @ p["w1"] + p["b1"])          # a probed call
    h = jax.nn.gelu(h @ p["w2"], approximate=True)  # a probed chain
    return jax.nn.softmax(h, axis=-1)


@pytest.fixture(scope="module")
def optimized():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    p = {"w1": jnp.asarray(rng.standard_normal((16, 32)), jnp.float32),
         "b1": jnp.zeros((32,), jnp.float32),
         "w2": jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)}
    before = SPANS.snapshot()
    t = time.perf_counter()
    net = api.optimize(_mlp, x, p)
    wall = time.perf_counter() - t
    return net, SPANS.delta(before), wall


TOP = ("optimize.trace", "optimize.registry", "optimize.verify",
       "optimize.compile", "optimize.floor")


def test_optimize_records_its_phases(optimized):
    net, delta, wall = optimized
    assert delta["optimize.trace"]["count"] == 1
    assert delta["optimize.compile"]["count"] == 1
    assert delta["trace.probe"]["count"] >= 1
    assert delta["trace.chain_probe"]["count"] >= 1
    assert delta["trace.probe"]["seconds"] \
        + delta["trace.chain_probe"]["seconds"] \
        <= delta["optimize.trace"]["seconds"]
    assert sum(delta[k]["seconds"] for k in TOP) <= wall
    assert not any(k.startswith("engine.") and v["count"]
                   for k, v in delta.items())


def test_optimize_keeps_its_split_and_explains_it(optimized):
    net, delta, _ = optimized
    assert net.setup_spans == {k: v for k, v in delta.items()
                               if v["count"]}
    text = net.explain()
    assert "optimize() set-up:" in text
    for name in net.setup_spans:
        assert name in text
