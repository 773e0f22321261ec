"""BrainSlug stack integration for LM blocks.

Each block's non-matmul chain is declared once as a
:class:`~repro.core.ir.StackProgram` and executed through the BrainSlug
dispatcher.  The mode knob (``RuntimeConfig.mode``) selects the schedule:

* ``brainslug`` — dedicated Pallas kernels where the Code Generator
  recognizes an idiom (residual+rmsnorm, swiglu), generic fused-stack kernel
  otherwise (paper: device-specific pre-processor templates, step 4),
* ``xla``       — fused jnp closure,
* ``barrier``   — per-op materialization (paper's framework baseline).
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.core import ir
from repro.kernels.fused_stack import ops as fused_ops
from repro.kernels.rmsnorm import ops as rms_ops
from repro.kernels.swiglu import ops as swiglu_ops


@functools.lru_cache(maxsize=None)
def addnorm_program(norm: str, eps: float, has_bias: bool) -> ir.StackProgram:
    """h = x + res;  y = norm(h) * scale (+ bias)."""
    params = ("scale", "bias") if has_bias else ("scale",)
    return ir.StackProgram(
        name=f"addnorm_{norm}", inputs=("x", "res"), outputs=("y", "h"),
        layout="rows",
        ops=(
            ir.OpNode(ir.OpKind.EW_BINARY, "add", ("x", "res"), "h",
                      fn="add"),
            ir.OpNode(ir.OpKind.ROW_NORM, "norm", ("h",), "y",
                      params=params, attrs={"norm": norm, "eps": eps}),
        ))


@functools.lru_cache(maxsize=None)
def norm_program(norm: str, eps: float, has_bias: bool) -> ir.StackProgram:
    params = ("scale", "bias") if has_bias else ("scale",)
    return ir.StackProgram(
        name=f"norm_{norm}", inputs=("x",), outputs=("y",), layout="rows",
        ops=(ir.OpNode(ir.OpKind.ROW_NORM, "norm", ("x",), "y",
                       params=params, attrs={"norm": norm, "eps": eps}),))


@functools.lru_cache(maxsize=None)
def glu_program(act: str) -> ir.StackProgram:
    """y = act(gate) * up."""
    return ir.StackProgram(
        name=f"glu_{act}", inputs=("gate", "up"), outputs=("y",),
        layout="rows",
        ops=(
            ir.OpNode(ir.OpKind.EW_UNARY, "act", ("gate",), "a", fn=act),
            ir.OpNode(ir.OpKind.EW_BINARY, "mul", ("a", "up"), "y",
                      fn="mul"),
        ))


@functools.lru_cache(maxsize=None)
def act_program(act: str) -> ir.StackProgram:
    return ir.StackProgram(
        name=f"act_{act}", inputs=("x",), outputs=("y",), layout="rows",
        ops=(ir.OpNode(ir.OpKind.EW_UNARY, "act", ("x",), "y", fn=act),))


# ---------------------------------------------------------------------------
# Dispatchers.  In 'brainslug' mode the recognized idioms go to their
# dedicated kernels; everything else goes through the generic fused kernel.
# ---------------------------------------------------------------------------

def add_norm(x: jnp.ndarray, residual: jnp.ndarray, scale: jnp.ndarray,
             bias: jnp.ndarray | None, *, norm: str = "rms",
             eps: float = 1e-6, mode: str = "xla"):
    """Fused residual add + norm.  Returns (normed, new_residual)."""
    if mode == "brainslug" and norm == "rms" and bias is None:
        y, h = rms_ops.rmsnorm(x, scale, residual, eps, 256)
        return y, h
    prog = addnorm_program(norm, eps, bias is not None)
    params = {"scale": scale}
    if bias is not None:
        params["bias"] = bias
    out = fused_ops.fused_stack_apply(
        prog, {"x": x, "res": residual}, params, mode=mode)
    return out["y"], out["h"]


def apply_norm(x: jnp.ndarray, scale: jnp.ndarray,
               bias: jnp.ndarray | None = None, *, norm: str = "rms",
               eps: float = 1e-6, mode: str = "xla") -> jnp.ndarray:
    if mode == "brainslug" and norm == "rms" and bias is None:
        y, _ = rms_ops.rmsnorm(x, scale, None, eps, 256)
        return y
    prog = norm_program(norm, eps, bias is not None)
    params = {"scale": scale}
    if bias is not None:
        params["bias"] = bias
    return fused_ops.fused_stack_apply(prog, {"x": x}, params,
                                       mode=mode)["y"]


def glu(gate: jnp.ndarray, up: jnp.ndarray, *, act: str = "silu",
        mode: str = "xla") -> jnp.ndarray:
    if mode == "brainslug" and act in ("silu", "gelu", "squared_relu"):
        return swiglu_ops.swiglu(gate, up, act, 256)
    return fused_ops.fused_stack_apply(
        glu_program(act), {"gate": gate, "up": up}, {}, mode=mode)["y"]


def activation(x: jnp.ndarray, *, act: str, mode: str = "xla"
               ) -> jnp.ndarray:
    return fused_ops.fused_stack_apply(
        act_program(act), {"x": x}, {}, mode=mode)["y"]
