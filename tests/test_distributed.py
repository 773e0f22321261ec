"""Distributed-layer tests: sharding rules + repair, fault tolerance,
gradient compression, collective parsing.  Pure-logic parts run on 1 device;
multi-device lowering is exercised by test_multidevice.py (subprocess)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import compression, fault_tolerance as ft
from repro.distributed import sharding as shd
from repro.launch import dryrun


class _FakeMesh:
    """Just enough of Mesh for spec logic (axis name -> size)."""

    def __init__(self, sizes: dict):
        self.shape = dict(sizes)
        self.axis_names = tuple(sizes)


MESH = _FakeMesh({"data": 16, "model": 16})
MESH_POD = _FakeMesh({"pod": 2, "data": 16, "model": 16})


class TestShardingRules:
    def test_default_rules(self):
        r = shd.ShardingRules()
        assert shd.spec_for_axes(("fsdp", "heads"), r, MESH) == \
            P("data", "model")
        assert shd.spec_for_axes(("vocab", None), r, MESH) == \
            P("model", None)
        assert shd.spec_for_axes(("layers", "fsdp", "ffn"), r, MESH) == \
            P(None, "data", "model")

    def test_conflict_dropped_first_wins(self):
        r = shd.ShardingRules()
        # both dims want "model": second goes replicated
        assert shd.spec_for_axes(("heads", "ffn"), r, MESH) == \
            P("model", None)

    def test_pod_extends_fsdp_when_asked(self):
        r = shd.ShardingRules(fsdp_over_pod=True)
        assert shd.spec_for_axes(("fsdp",), r, MESH_POD) == \
            P(("pod", "data"))
        r2 = shd.ShardingRules()
        assert shd.spec_for_axes(("fsdp",), r2, MESH_POD) == P("data")

    def test_unknown_axis_raises(self):
        with pytest.raises(ValueError, match="unknown logical axis"):
            shd.spec_for_axes(("bogus",), shd.ShardingRules(), MESH)


class TestRepairSpec:
    def test_tuple_prefix_kept(self):
        # 32 % (2*16) == 0 -> keep both; 16 % 2 == 0 but 16 % 32 != 0 -> pod only
        spec = shd.repair_spec((32,), P(("pod", "data")), MESH_POD)
        assert spec == P(("pod", "data"))
        spec = shd.repair_spec((16,), P(("pod", "data")), MESH_POD)
        assert spec == P("pod")

    def test_known_awkward_dims(self):
        # the assigned-arch offenders: vocab 50280/49155/504, 40 experts
        assert shd.repair_spec((50280, 2560), P("model", None), MESH) == \
            P(None, None)
        assert shd.repair_spec((40, 1536, 512), P("data", None, "model"),
                               MESH) == P(None, None, "model")
        assert shd.repair_spec((49152, 64), P("model", None), MESH) == \
            P("model", None)

    def test_rank_mismatch_tolerated(self):
        assert shd.repair_spec((32, 8, 8), P("data"), MESH) == \
            P("data", None, None)


class TestMeshPlanning:
    def test_elastic_shrink_example(self):
        # 256-chip pod loses 3 hosts (12 chips): still a valid grid
        plan = ft.plan_mesh(244, model_parallel=16)
        assert plan.n_devices >= 224           # <9% idle
        assert plan.shape[1] in (16, 8, 4, 2, 1)

    def test_multi_pod_plan(self):
        plan = ft.plan_mesh(512, model_parallel=16, pods=2)
        assert plan.shape == (2, 16, 16)
        assert plan.axis_names == ("pod", "data", "model")

    def test_straggler_watchdog(self):
        wd = ft.StragglerWatchdog(warmup_steps=2, threshold=1.5)
        import time
        for _ in range(4):
            wd.start()
            wd.stop()
        wd.start()
        time.sleep(0.05)
        assert wd.stop() is True
        assert wd.slow_steps == 1

    def test_failure_injector(self):
        hook = ft.failure_injector({3})
        hook(1)
        hook(2)
        with pytest.raises(ft.SimulatedFailure):
            hook(3)
        hook(3)        # fires once


class TestCompression:
    def test_error_feedback_converges(self):
        """Repeatedly compressing a CONSTANT gradient with error feedback
        must transmit the true mean: accumulated payloads -> n * g."""
        rng = np.random.default_rng(1)
        g = {"w": jnp.asarray(rng.standard_normal((300,)), jnp.float32)}
        err = compression.init_error_state(g)
        total = jnp.zeros_like(g["w"])
        n = 50
        for _ in range(n):
            out, err = compression.compress_decompress(g, err)
            total = total + out["w"]
        np.testing.assert_allclose(np.asarray(total / n),
                                   np.asarray(g["w"]), atol=2e-3)

    def test_compressed_bytes_ratio(self):
        g = {"w": jnp.zeros((1 << 20,), jnp.bfloat16)}
        raw = (1 << 20) * 2
        comp = compression.compressed_bytes(g)
        assert comp < raw * 0.52 + 1024        # ~2x cut vs bf16


class TestCollectiveParsing:
    HLO = """
  ENTRY main {
    %ag = f32[128,256] all-gather(f32[8,256] %p0), replica_groups={}
    %ar.1 = bf16[1024]{0} all-reduce(bf16[1024]{0} %x), to_apply=%add
    %rs = f32[16,16] reduce-scatter(f32[256,16] %y), dimensions={0}
    %a2a = f32[4,8]{1,0} all-to-all(f32[4,8] %z), dimensions={0}
    %cp = u8[100]{0} collective-permute(u8[100]{0} %w)
    %start = f32[64]{0} all-reduce-start(f32[64]{0} %v), to_apply=%add
    %done = f32[64]{0} all-reduce-done(f32[64]{0} %start)
    %not = f32[9] add(f32[9] %a, f32[9] %b)
  }
    """

    def test_parse_collective_bytes(self):
        out = dryrun.parse_collective_bytes(self.HLO)
        b = out["bytes"]
        assert b["all-gather"] == 128 * 256 * 4
        assert b["all-reduce"] == 1024 * 2 + 64 * 4      # start counted once
        assert b["reduce-scatter"] == 16 * 16 * 4
        assert b["all-to-all"] == 4 * 8 * 4
        assert b["collective-permute"] == 100
        assert out["counts"]["all-reduce"] == 2

    def test_parse_ignores_done_and_plain_ops(self):
        out = dryrun.parse_collective_bytes("%x = f32[8] add(f32[8] %a)")
        assert sum(out["bytes"].values()) == 0


class TestCacheSpec:
    def _kv(self, g, s=32768):
        import jax.numpy as jnp
        return jax.ShapeDtypeStruct((4, 8, g, s, 128), jnp.bfloat16)

    def test_gqa_cache_sequence_sharded(self):
        """8 kv heads don't divide the 16-way model axis -> shard S."""
        spec = shd.cache_spec({"k": self._kv(8)}, MESH)["k"]
        assert spec == P(None, "data", None, "model", None)

    def test_mha_cache_head_sharded(self):
        """32 kv heads divide the model axis -> keep head sharding."""
        spec = shd.cache_spec({"k": self._kv(32)}, MESH)["k"]
        assert spec == P(None, "data", "model", None, None)

    def test_ssm_state_head_sharded(self):
        import jax.numpy as jnp
        state = jax.ShapeDtypeStruct((4, 8, 80, 128, 64), jnp.float32)
        spec = shd.cache_spec({"s": state}, MESH)["s"]
        assert spec == P(None, "data", "model", None, None)

    def test_lengths_batch_sharded(self):
        import jax.numpy as jnp
        ln = jax.ShapeDtypeStruct((4, 8), jnp.int32)
        assert shd.cache_spec({"l": ln}, MESH)["l"] == P(None, "data")
