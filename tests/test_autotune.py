"""Autotuner tests: the never-slower guardrail (measured variant
selection hard-floored at the baseline), the crash-safe decision cache
(warm hits skip every micro-benchmark; corrupt/truncated/stale entries
are quarantined and silently re-measured), and the report() surface."""
from __future__ import annotations

import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import autotune, codegen

TOL = dict(rtol=1e-5, atol=1e-5)


@pytest.fixture(autouse=True)
def _clear_caches():
    codegen.clear_cache()
    autotune.clear_memory_cache()
    autotune.STATS.reset()
    yield
    codegen.clear_cache()
    autotune.clear_memory_cache()
    autotune.STATS.reset()


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _norm_chain_fn():
    def fn(x, w):
        h = x @ w
        h = h + x
        y = h * jax.lax.rsqrt(
            jnp.mean(jnp.square(h), axis=-1, keepdims=True) + 1e-6)
        return jnp.tanh(y) * y
    return fn


def _args(rng):
    x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 16)) * 0.25, jnp.float32)
    return x, w


def _cfg(tmp_path, **kw):
    kw.setdefault("mode", "brainslug")
    kw.setdefault("autotune", True)
    kw.setdefault("autotune_cache_dir", str(tmp_path / "atcache"))
    return api.OptimizeConfig(**kw)


# ---------------------------------------------------------------------------
# decisions, parity, and the report surface
# ---------------------------------------------------------------------------

class TestDecisions:
    def test_autotuned_net_matches_reference(self, rng, tmp_path):
        fn = _norm_chain_fn()
        x, w = _args(rng)
        net = api.optimize(fn, x, w, config=_cfg(tmp_path))
        np.testing.assert_allclose(np.asarray(net(x, w)),
                                   np.asarray(fn(x, w)), **TOL)

    def test_report_surfaces_decisions(self, rng, tmp_path):
        fn = _norm_chain_fn()
        x, w = _args(rng)
        net = api.optimize(fn, x, w, config=_cfg(tmp_path))
        rep = net.report()
        assert rep.autotune                      # decisions are visible
        kinds = {a.kind for a in rep.autotune}
        assert "stack" in kinds and "function" in kinds
        for a in rep.autotune:
            assert a.source == "measured"
            assert a.chosen in {v for v, _, _ in a.measured_ms} \
                or a.failures
            assert a.baseline in ("barrier", "ref", "raw")
        # the committed variant text shows up in explain()
        text = net.explain()
        assert "autotune" in text

    def test_floor_is_timed_as_a_setup_span(self, rng, tmp_path):
        fn = _norm_chain_fn()
        x, w = _args(rng)
        net = api.optimize(fn, x, w, config=_cfg(tmp_path))
        assert net.setup_spans["optimize.floor"]["count"] == 1
        assert "optimize.floor" in net.explain()

    def test_variant_never_slower_than_baseline(self, rng, tmp_path):
        """The hard floor: whatever was committed measured no slower than
        the baseline in every phase (modulo the declared slack)."""
        fn = _norm_chain_fn()
        x, w = _args(rng)
        net = api.optimize(fn, x, w, config=_cfg(tmp_path))
        for a in net.report().autotune:
            times = {}
            for variant, phase, ms in a.measured_ms:
                times.setdefault(variant, {})[phase] = ms
            if a.chosen not in times or a.baseline not in times:
                continue
            for phase, base_ms in times[a.baseline].items():
                assert times[a.chosen][phase] \
                    <= base_ms * autotune.FLOOR_SLACK

    def test_autotune_off_is_static_dispatch(self, rng, tmp_path):
        """The escape hatch: autotune=False (default) must not measure,
        not touch the cache dir, and keep the static planner's choices."""
        fn = _norm_chain_fn()
        x, w = _args(rng)
        before = autotune.STATS.snapshot()
        net = api.optimize(fn, x, w,
                           config=_cfg(tmp_path, autotune=False))
        delta = autotune.STATS.delta(before)
        assert all(v == 0 for v in delta.values())
        assert net.autotune_decisions == {}
        assert net.report().autotune == ()
        assert not os.path.exists(str(tmp_path / "atcache"))

    def test_kernel_dispatch_is_tuned(self, rng, tmp_path):
        """A registry-matched kernel (rmsnorm before matmul) gets a
        measured PALLAS-vs-REF decision; the committed backend is what
        the dispatch record reports."""
        x = jnp.asarray(rng.standard_normal((6, 16)), jnp.float32)
        g = jnp.asarray(1.0 + 0.1 * rng.standard_normal(16), jnp.float32)
        w = jnp.asarray(rng.standard_normal((16, 16)) * 0.25, jnp.float32)

        def fn(x, g, w):
            var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
            y = x * jax.lax.rsqrt(var + 1e-6) * g
            return y @ w

        net = api.optimize(fn, x, g, w, config=_cfg(tmp_path))
        kernel_decisions = [a for a in net.report().autotune
                            if a.kind == "kernel"]
        assert len(kernel_decisions) == 1
        (d,) = kernel_decisions
        assert d.requested == "pallas" and d.baseline == "ref"
        (dispatch,) = net.kernel_dispatches.values()
        assert dispatch.backend.value == d.chosen
        if d.chosen == "ref":                    # measured fallback
            assert "autotune" in dispatch.reason
        np.testing.assert_allclose(np.asarray(net(x, g, w)),
                                   np.asarray(fn(x, g, w)),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# warm cache: zero micro-benchmark runs (acceptance criterion)
# ---------------------------------------------------------------------------

class TestWarmCache:
    def test_second_optimize_skips_all_measurement(self, rng, tmp_path):
        fn = _norm_chain_fn()
        x, w = _args(rng)
        cfg = _cfg(tmp_path)
        api.optimize(fn, x, w, config=cfg)

        autotune.clear_memory_cache()            # force the disk path
        before = autotune.STATS.snapshot()
        net2 = api.optimize(fn, x, w, config=cfg)
        delta = autotune.STATS.delta(before)
        assert delta["measure_runs"] == 0
        assert delta["cache_miss"] == 0
        assert delta["cache_hit_disk"] == len(net2.autotune_decisions)
        assert all(d.source == "cache-disk"
                   for d in net2.autotune_decisions.values())

        before = autotune.STATS.snapshot()       # third run: memory memo
        net3 = api.optimize(fn, x, w, config=cfg)
        delta = autotune.STATS.delta(before)
        assert delta["measure_runs"] == 0
        assert delta["cache_hit_mem"] == len(net3.autotune_decisions)

    def test_new_shapes_measure_fresh(self, rng, tmp_path):
        fn = _norm_chain_fn()
        x, w = _args(rng)
        cfg = _cfg(tmp_path)
        api.optimize(fn, x, w, config=cfg)
        before = autotune.STATS.snapshot()
        x2 = jnp.asarray(rng.standard_normal((16, 16)), jnp.float32)
        api.optimize(fn, x2, w, config=cfg)      # different traced shape
        delta = autotune.STATS.delta(before)
        assert delta["cache_miss"] > 0
        assert delta["measure_runs"] > 0


# ---------------------------------------------------------------------------
# cache robustness: corruption never raises (acceptance criterion)
# ---------------------------------------------------------------------------

def _cache_files(tmp_path):
    return sorted(glob.glob(str(tmp_path / "atcache" / "*.json")))


class TestCacheRobustness:
    def _seed_cache(self, rng, tmp_path):
        fn = _norm_chain_fn()
        x, w = _args(rng)
        cfg = _cfg(tmp_path)
        api.optimize(fn, x, w, config=cfg)
        files = _cache_files(tmp_path)
        assert files
        return fn, (x, w), cfg, files

    def _assert_recovers(self, fn, args, cfg, n_bad):
        autotune.clear_memory_cache()
        before = autotune.STATS.snapshot()
        net = api.optimize(fn, *args, config=cfg)     # must not raise
        delta = autotune.STATS.delta(before)
        assert delta["cache_quarantined"] == n_bad
        assert delta["measure_runs"] > 0              # re-measured
        rep = net.report()
        assert any("quarantined" in e
                   for a in rep.autotune for e in a.events)
        np.testing.assert_allclose(np.asarray(net(*args)),
                                   np.asarray(fn(*args)), **TOL)
        return net

    def test_corrupt_json_quarantined(self, rng, tmp_path):
        fn, args, cfg, files = self._seed_cache(rng, tmp_path)
        for p in files:
            with open(p, "w") as fh:
                fh.write('{"schema": 1, "trunc')
        self._assert_recovers(fn, args, cfg, len(files))
        assert glob.glob(str(tmp_path / "atcache" / "*.quarantined"))

    def test_truncated_entry_fails_checksum(self, rng, tmp_path):
        fn, args, cfg, files = self._seed_cache(rng, tmp_path)
        blob = json.load(open(files[0]))
        blob["payload"]["measured_ms"] = blob["payload"][
            "measured_ms"][:1]                   # valid JSON, bad checksum
        json.dump(blob, open(files[0], "w"))
        self._assert_recovers(fn, args, cfg, 1)

    def test_stale_schema_quarantined(self, rng, tmp_path):
        fn, args, cfg, files = self._seed_cache(rng, tmp_path)
        blob = json.load(open(files[0]))
        blob["schema"] = autotune.SCHEMA_VERSION + 1
        json.dump(blob, open(files[0], "w"))
        self._assert_recovers(fn, args, cfg, 1)

    def test_stale_version_quarantined(self, rng, tmp_path):
        fn, args, cfg, files = self._seed_cache(rng, tmp_path)
        blob = json.load(open(files[0]))
        blob["versions"]["repro"] = "0.0.0-ancient"
        json.dump(blob, open(files[0], "w"))
        self._assert_recovers(fn, args, cfg, 1)

    def test_tampered_decision_payload_quarantined(self, rng, tmp_path):
        """A mis-dispatch attempt: rewriting the committed variant inside
        the payload breaks the checksum, so the poisoned entry can never
        steer dispatch."""
        fn, args, cfg, files = self._seed_cache(rng, tmp_path)
        blob = json.load(open(files[0]))
        blob["payload"]["variant"] = "definitely-not-a-variant"
        json.dump(blob, open(files[0], "w"))
        self._assert_recovers(fn, args, cfg, 1)

    def test_unwritable_cache_dir_never_raises(self, rng, tmp_path):
        fn = _norm_chain_fn()
        x, w = _args(rng)
        bad = tmp_path / "file-not-dir"
        bad.write_text("i am a file, not a directory")
        cfg = _cfg(tmp_path, autotune_cache_dir=str(bad))
        net = api.optimize(fn, x, w, config=cfg)  # store fails silently
        assert net.autotune_decisions
        np.testing.assert_allclose(np.asarray(net(x, w)),
                                   np.asarray(fn(x, w)), **TOL)


# ---------------------------------------------------------------------------
# measurement harness + pick_callable (benchmark-facing floor)
# ---------------------------------------------------------------------------

class TestHarness:
    def test_measure_ms_failure_is_reported_not_raised(self):
        def boom(x):
            raise RuntimeError("lowering exploded")
        ms, why = autotune.measure_ms(boom, (jnp.zeros(4),), use_jit=False)
        assert ms is None
        assert "lowering exploded" in why

    def test_timeout_disqualifies_candidate(self, tmp_path):
        def slow(x):
            time.sleep(0.05)
            return x + 1.0

        def fast(x):
            return x + 1.0

        decision, chosen = autotune.pick_callable(
            "timeout-test", {"fast": fast, "slow": slow},
            (jnp.zeros(4),), baseline="fast", requested="slow",
            cache_dir=str(tmp_path), timeout_ms=5.0)
        assert decision.variant == "fast"
        assert decision.guardrail_tripped
        assert any("timeout" in why for _, why in decision.failures)

    def test_pick_callable_floors_slow_requested(self, tmp_path):
        calls = {"n": 0}

        def slow(x):
            time.sleep(0.01)
            return x * 2.0

        def fast(x):
            calls["n"] += 1
            return x * 2.0

        decision, chosen = autotune.pick_callable(
            "floor-test", {"base": fast, "fused": slow},
            (jnp.zeros(8),), baseline="base", requested="fused",
            cache_dir=str(tmp_path))
        assert decision.variant == "base"
        assert decision.guardrail_tripped
        assert chosen is fast

    def test_pick_callable_warm_cache(self, tmp_path):
        def a(x):
            return x + 1.0

        def b(x):
            return x + 1.0

        args = (jnp.zeros(8),)
        autotune.pick_callable("warm", {"a": a, "b": b}, args,
                               baseline="a", cache_dir=str(tmp_path))
        autotune.clear_memory_cache()
        before = autotune.STATS.snapshot()
        decision, _ = autotune.pick_callable(
            "warm", {"a": a, "b": b}, args, baseline="a",
            cache_dir=str(tmp_path))
        delta = autotune.STATS.delta(before)
        assert delta["measure_runs"] == 0
        assert decision.source == "cache-disk"

    def test_config_validates_autotune_fields(self):
        with pytest.raises(ValueError, match="autotune_repeats"):
            api.OptimizeConfig(autotune_repeats=0)
        with pytest.raises(ValueError, match="autotune_warmup"):
            api.OptimizeConfig(autotune_warmup=-1)
