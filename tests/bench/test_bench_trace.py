"""The reduction from a trace to per-layer numbers, on a small trace
recorded on one TPU v5e: a jitted program with a Pallas paged-decode
kernel in a loop (``kernels/attention/decode.py``), a Pallas rows
fused-stack kernel (``kernels/fused_stack/rows.py``) and an XLA matmul,
run twice inside the ``bench.window`` span.  ``v5e_tiny.hlo.txt`` is that
program's compiled text."""
from __future__ import annotations

import pytest

import reduce_trace as rt
from conftest import REPO

DATA = REPO / "tests" / "bench" / "data"


@pytest.fixture(scope="module")
def trace():
    t = rt.load(DATA / "v5e_tiny.xplane.pb")
    t.attach([(DATA / "v5e_tiny.hlo.txt").read_text()])
    return t


def test_union_of_busy_intervals():
    # [0,10) and [5,20) overlap; [30,40) is clipped by the window at 35
    iv = [(0, 10), (5, 20), (30, 40), (50, 60)]
    assert rt.union_seconds(iv, 0, 35) == pytest.approx(25e-9)
    assert rt.union_seconds(iv, 12, 18) == pytest.approx(6e-9)
    assert rt.idle_gaps(iv, 0, 55) == [(20, 30), (40, 50)]
    assert rt.idle_gaps([], 3, 7) == [(3, 7)]


def test_window_and_idle_share(trace):
    assert trace.window_s == pytest.approx(3.549649e-3)
    assert 0 < trace.busy_s < trace.window_s
    # two runs of about 0.33 ms each in a 3.5 ms window
    assert trace.busy_s == pytest.approx(2 * 0.327e-3, rel=0.02)
    idle = 1 - trace.busy_s / trace.window_s
    assert 0.8 < idle < 0.85


def test_kernel_events_are_attributed_to_their_source_file(trace):
    # 3 loop iterations x 2 runs of the decode kernel, 1 x 2 of the rows
    assert trace.kernel_count("kernels/attention/decode.py") == 6
    assert trace.kernel_count("kernels/fused_stack/rows.py") == 2
    assert trace.kernel_count("kernels/fused_stack/nhwc.py") == 0
    decode = trace.kernel_seconds("kernels/attention/decode.py")
    assert decode == pytest.approx(6 * 98.8e-6, rel=0.01)
    # every op ran inside the program the XLA Modules line names
    assert {e.module for e in trace.device_ops[0]} == {"jit_f"}


def test_breakdown_leaves_out_loops_and_names_kernels_by_file(trace):
    b = trace.breakdown()
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "pallas kernels/attention/decode.py"
    assert "pallas kernels/fused_stack/rows.py" in names
    assert "while" not in names
    total = sum(v for _, v in b["device_ops"])
    assert total <= trace.busy_s * 1.0001
    assert b["idle_gaps"] and all(n.startswith("host: ")
                                  for n, _ in b["idle_gaps"])
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


@pytest.mark.parametrize("files, want", [
    ({"kernels/attention/decode.py", "kernels/attention/ops.py"},
     "kernels/attention/decode.py"),
    ({"kernels/fused_stack/nhwc.py", "kernels/fused_stack/ops.py",
      "core/codegen.py"}, "kernels/fused_stack/nhwc.py"),
    ({"kernels/fused_stack/nhwc.py", "kernels/fused_stack/nhwc_bwd.py"},
     "kernels/fused_stack/nhwc_bwd.py"),
    ({"kernels/fused_stack/rows.py", "kernels/fused_stack/nhwc.py"}, None),
    ({"core/ir.py"}, None),
])
def test_defining_file(files, want):
    assert rt.defining_file(files) == want


def test_compile_counter_sees_a_compile_and_not_a_cached_call():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.ones((3,))
    with rt.CompileCounter() as c:
        f(x).block_until_ready()
    assert c.count >= 1
    with rt.CompileCounter() as c:
        f(x).block_until_ready()
    assert c.count == 0


def test_generated_backward_kernels_are_told_apart():
    """Three kernel calls of the compiled VGG-16 training step: an NHWC
    forward (its body names ``nhwc.py``), and an NHWC and a rows generated
    backward, whose bodies name no kernel file: told apart by function
    name and the rank of their results."""
    module, calls = rt.kernel_sources(
        (DATA / "vgg16_kernels.hlo.txt").read_text())
    assert module == "jit_step"
    assert calls == {
        "%jvp__.17": "kernels/fused_stack/nhwc.py",
        "%transpose_jvp___.30": "kernels/fused_stack/nhwc_bwd.py",
        "%transpose_jvp___.31": "kernels/fused_stack/rows_bwd.py",
    }
