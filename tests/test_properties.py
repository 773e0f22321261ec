"""Randomized property tests (hypothesis) for the collapser, the op IR,
the distributed helpers and the LR schedule.  The deterministic unit tests
of the same modules live in their own files and run without hypothesis;
this file is skipped when hypothesis is not installed."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")

from hypothesis import given, strategies as st  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import collapse, ir, resource  # noqa: E402
from repro.distributed import compression, fault_tolerance as ft  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.optim import schedule  # noqa: E402


class _FakeMesh:
    """Just enough of Mesh for spec logic (axis name -> size)."""

    def __init__(self, sizes: dict):
        self.shape = dict(sizes)
        self.axis_names = tuple(sizes)


MESH = _FakeMesh({"data": 16, "model": 16})


# ---------------------------------------------------------------------------
# Random nhwc-program generator (element-wise + pooling chains).
# ---------------------------------------------------------------------------

@st.composite
def nhwc_programs(draw):
    n_ops = draw(st.integers(1, 12))
    ops = []
    v = "x"
    for i in range(n_ops):
        kind = draw(st.sampled_from(["relu", "pool", "affine"]))
        if kind == "relu":
            ops.append(ir.OpNode(ir.OpKind.EW_UNARY, f"op{i}", (v,),
                                 f"v{i}", fn="relu"))
        elif kind == "affine":
            ops.append(ir.OpNode(ir.OpKind.AFFINE, f"op{i}", (v,), f"v{i}",
                                 params=(f"s{i}", f"b{i}")))
        else:
            k = draw(st.sampled_from([2, 3]))
            s = draw(st.sampled_from([1, 2]))
            ops.append(ir.OpNode(
                ir.OpKind.POOL2D, f"op{i}", (v,), f"v{i}",
                fn=draw(st.sampled_from(["max", "avg"])),
                attrs={"window": (k, k), "stride": (s, s),
                       "padding": (k // 2, k // 2)}))
        v = f"v{i}"
    return ir.StackProgram(name="rand", inputs=("x",), outputs=(v,),
                           ops=tuple(ops), layout="nhwc")


class TestBuildSteps:
    @given(prog=nhwc_programs())
    def test_step_invariants(self, prog):
        steps = collapse.build_steps(prog)
        # 1. every op appears exactly once, in order
        flat = [op for s in steps for op in s.ops]
        assert flat == list(prog.ops)
        # 2. at most one non-element-wise op per step (paper rule)
        for s in steps:
            non_ew = [op for op in s.ops if not op.is_elementwise]
            assert len(non_ew) <= 1
        # 3. steps are maximal: two consecutive steps cannot merge without
        #    violating rule 2
        for a, b in zip(steps, steps[1:]):
            merged_non_ew = [op for op in a.ops + b.ops
                             if not op.is_elementwise]
            assert len(merged_non_ew) >= 2


class TestCollapseInvariants:
    @given(prog=nhwc_programs(), budget_kb=st.sampled_from([4, 16, 64, 1024]))
    def test_sequences_partition_and_fit(self, prog, budget_kb):
        device = resource.DeviceSpec(name="t", vmem_bytes=budget_kb * 1024,
                                     vmem_budget_fraction=1.0)
        shape = (1, 32, 32, 8)
        try:
            plan = collapse.collapse(prog, {"x": shape}, device, itemsize=4)
        except resource.ResourceError:
            return  # single step legitimately too big for a tiny budget
        # 1. sequences partition the steps in order
        flat = [op for seq in plan.sequences for op in seq.ops]
        assert flat == list(prog.ops)
        # 2. each sequence's working set fits the budget
        for seq in plan.sequences:
            fps = resource.sequence_footprint(
                [s.ops for s in seq.steps], seq.tile_out_h, seq.tile_out_w,
                shape[-1], 4, device)
            assert resource.sequence_bytes(fps) <= device.resource_limit

    @given(prog=nhwc_programs())
    def test_max_steps_knob(self, prog):
        plan = collapse.collapse(prog, {"x": (1, 32, 32, 8)},
                                 resource.TPU_V5E, itemsize=4,
                                 max_steps_per_sequence=1)
        for seq in plan.sequences:
            assert len(seq.steps) == 1


class TestShapes:
    @given(extent=st.integers(1, 64), k=st.integers(1, 5),
           s=st.integers(1, 4), p=st.integers(0, 3))
    def test_pool_extent_roundtrip(self, extent, k, s, p):
        """pool_in_extent is the least input size producing that output."""
        out = ir.pool_out_extent(extent, k, s, p)
        if out < 1:
            return
        need = ir.pool_in_extent(out, k, s)
        # an input of size `need` (already padded) yields exactly `out`
        assert ir.pool_out_extent(need, k, s, 0) == out


class TestRepairSpec:
    @given(dim=st.integers(1, 4096))
    def test_repaired_extent_divides(self, dim):
        spec = shd.repair_spec((dim, 64), P("model", None), MESH)
        entry = spec[0]
        if entry is not None:
            assert dim % MESH.shape[entry] == 0
        elif dim % 16 == 0:
            pytest.fail("dropped a divisible dim")


class TestMeshPlanning:
    @given(n=st.integers(1, 4096), mp=st.sampled_from([1, 2, 4, 8, 16]))
    def test_plan_mesh_properties(self, n, mp):
        plan = ft.plan_mesh(n, model_parallel=mp)
        assert plan.n_devices <= n
        assert len(plan.shape) == 2
        data, model = plan.shape
        assert model <= mp
        assert data * model <= n


class TestCompression:
    @given(scale=st.floats(1e-3, 1e3))
    def test_quantize_error_bound(self, scale):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((512,)) * scale, jnp.float32)
        q, s = compression.quantize(x)
        back = compression.dequantize(q, s, x.shape, jnp.float32)
        # max error is half an int8 bucket of the block max
        bound = float(jnp.max(jnp.abs(x))) / 127.0
        assert float(jnp.max(jnp.abs(back - x))) <= bound + 1e-6


class TestSchedule:
    @given(warm=st.integers(1, 50), total=st.integers(60, 500))
    def test_schedule_properties(self, warm, total):
        sched = schedule.warmup_cosine(1e-3, warm, total)
        steps = jnp.asarray([0, warm, total, total * 2])
        vals = [float(sched(s)) for s in steps]
        assert vals[0] == 0.0
        assert vals[1] == pytest.approx(1e-3, rel=1e-4)
        assert vals[2] == pytest.approx(1e-4, rel=1e-3)   # final_fraction
        assert vals[3] == pytest.approx(1e-4, rel=1e-3)   # clamped
        # monotone decay after warmup
        post = [float(sched(jnp.asarray(s)))
                for s in range(warm, total, max((total - warm) // 7, 1))]
        assert all(a >= b - 1e-12 for a, b in zip(post, post[1:]))
