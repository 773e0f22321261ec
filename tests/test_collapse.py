"""Unit tests for the Collapser (paper Listing 1) and the
resource model — the invariants the paper's algorithm must satisfy (the
randomized properties are in ``test_properties.py``)."""
from __future__ import annotations

import dataclasses

import pytest

from repro.core import collapse, ir, resource
from repro.models import cnn


class TestBuildSteps:
    def test_pure_elementwise_is_one_step(self):
        ops = tuple(ir.OpNode(ir.OpKind.EW_UNARY, f"r{i}",
                              ("x" if i == 0 else f"v{i-1}",), f"v{i}",
                              fn="relu") for i in range(5))
        prog = ir.StackProgram(name="t", inputs=("x",), outputs=("v4",),
                               ops=ops, layout="rows")
        assert len(collapse.build_steps(prog)) == 1


class TestCollapseInvariants:
    def test_smaller_budget_no_fewer_sequences(self):
        graph, _ = cnn.block_net(8, channels=32)
        prog = ir.StackProgram(name="s", inputs=("x",),
                               outputs=(graph.ops[-1].output,),
                               ops=graph.ops, layout="nhwc")
        shapes = {"x": (1, 32, 32, 32)}
        seqs = []
        for kb in (1024, 64, 16):
            device = resource.DeviceSpec(name="t", vmem_bytes=kb * 1024,
                                         vmem_budget_fraction=1.0)
            plan = collapse.collapse(prog, shapes, device, itemsize=4)
            seqs.append(len(plan.sequences))
        assert seqs[0] <= seqs[1] <= seqs[2]

    def test_fig10_artifact_receptive_field_growth(self):
        """Stacked 3x3 s1 pools grow the tile working set (the paper's
        cache-overflow artifact): deeper stacks need more sequences on a
        fixed small budget."""
        def n_seq(blocks):
            graph, _ = cnn.block_net(blocks, channels=32)
            prog = ir.StackProgram(name="s", inputs=("x",),
                                   outputs=(graph.ops[-1].output,),
                                   ops=graph.ops, layout="nhwc")
            plan = collapse.collapse(
                prog, {"x": (1, 32, 32, 32)}, resource.TINY_DEVICE,
                itemsize=4)
            return len(plan.sequences)
        assert n_seq(12) > n_seq(2)

    def test_subprogram_boundary_values(self):
        graph, _ = cnn.block_net(10, channels=16)
        prog = ir.StackProgram(name="s", inputs=("x",),
                               outputs=(graph.ops[-1].output,),
                               ops=graph.ops, layout="nhwc")
        plan = collapse.collapse(prog, {"x": (1, 16, 16, 16)},
                                 resource.TINY_DEVICE, itemsize=4)
        assert len(plan.sequences) >= 2
        # chaining the subprograms must reconstruct the full program
        prev_outs = set(prog.inputs)
        for i in range(len(plan.sequences)):
            sub = plan.subprogram(i)
            assert set(sub.inputs) <= prev_outs
            prev_outs |= set(sub.outputs)
        assert set(prog.outputs) <= prev_outs


class TestRowsResource:
    def test_max_live_values(self):
        prog = ir.StackProgram(
            name="t", inputs=("x", "res"), outputs=("y", "h"), layout="rows",
            ops=(
                ir.OpNode(ir.OpKind.EW_BINARY, "add", ("x", "res"), "h",
                          fn="add"),
                ir.OpNode(ir.OpKind.ROW_NORM, "norm", ("h",), "y",
                          params=("scale",), attrs={}),
            ))
        # live peak: at the add, {x, res, h} coexist = 3; afterwards {h, y}
        assert resource.max_live_values(prog) == 3

    def test_pick_row_tile_fits(self):
        prog = ir.StackProgram(
            name="t", inputs=("x",), outputs=("y",), layout="rows",
            ops=(ir.OpNode(ir.OpKind.EW_UNARY, "r", ("x",), "y",
                           fn="relu"),))
        rows = resource.pick_row_tile(prog, 4096, 2, resource.TPU_V5E)
        assert rows % resource.TPU_V5E.sublane == 0
        assert resource.rows_tile_bytes(
            resource.max_live_values(prog), rows, 4096, 2,
            resource.TPU_V5E) <= resource.TPU_V5E.resource_limit

    def test_rows_overflow_raises(self):
        prog = ir.StackProgram(
            name="t", inputs=("x",), outputs=("y",), layout="rows",
            ops=(ir.OpNode(ir.OpKind.EW_UNARY, "r", ("x",), "y",
                           fn="relu"),))
        tiny = dataclasses.replace(resource.TINY_DEVICE, vmem_bytes=1024)
        with pytest.raises(resource.ResourceError):
            resource.pick_row_tile(prog, 1 << 20, 4, tiny)
