"""Per-op VJP rules for the BrainSlug IR — the backward half of the stack.

The forward half of the system executes a :class:`~repro.core.ir.StackProgram`
three ways from one semantics object (:func:`~repro.core.ir.apply_op`).  This
module is the analogous single source of *derivative* semantics: an explicit
VJP rule per optimizable ``OpKind``, written in plain jnp so the same rules
run

* on full arrays (the oracle path — tested against ``jax.vjp`` of the
  interpreter), and
* inside the generated depth-first backward kernel
  (:mod:`repro.kernels.fused_stack.rows_bwd`), traced over VMEM tiles.

Both kernel layouts are covered: the rows op set (elementwise, affine, row
norms, row softmax, residual adds) and POOL2D for nhwc pooling chains.  The
pool rules are written over a *pre-padded patch* (out-of-image positions
hold the pool's neutral element, exactly what the nhwc kernels feed them)
so the same code runs on the halo-grown VMEM tile inside
:mod:`repro.kernels.fused_stack.nhwc_bwd` and on padded full images in the
oracle path.

Max-pool tie convention: the **first** maximal element in row-major window
order takes the whole cotangent — the jax/XLA ``select_and_scatter``
convention, oracle-matched against ``jax.vjp`` of
``lax.reduce_window(max)`` (ties are not split).  Avg-pool cotangents are
scattered uniformly at ``g / (kh * kw)`` (count-include-pad, matching the
forward's divisor).

Conventions
-----------
``op_vjp`` consumes the *recomputed forward environment* (every value of the
program, as produced by running the ops in order) — the depth-first backward
recomputes the forward on the resident tile rather than saving intermediates
to HBM, so the rules can assume all primal values are at hand.

Parameter cotangents are reduced over all leading (row/batch) axes down to
the parameter's own shape and cast to the parameter's dtype, matching what
``jax.vjp`` would return.
"""
from __future__ import annotations

import math
from typing import Callable, Mapping

import jax
import jax.numpy as jnp

from repro.core import ir

Array = jnp.ndarray

# ---------------------------------------------------------------------------
# Unary derivative table: fn name -> d/dx evaluated as dfn(x, y) where y=f(x)
# (rules may use whichever of x/y is cheaper — e.g. sigmoid uses y).
# ---------------------------------------------------------------------------

_INV_SQRT_2PI = 1.0 / math.sqrt(2.0 * math.pi)
_SQRT_2_OVER_PI = math.sqrt(2.0 / math.pi)


def _d_gelu_tanh(x: Array, y: Array) -> Array:
    del y
    u = _SQRT_2_OVER_PI * (x + 0.044715 * x * x * x)
    t = jnp.tanh(u)
    du = _SQRT_2_OVER_PI * (1.0 + 3.0 * 0.044715 * x * x)
    return 0.5 * (1.0 + t) + 0.5 * x * (1.0 - t * t) * du


def _d_gelu_exact(x: Array, y: Array) -> Array:
    del y
    phi = jnp.exp(-0.5 * x * x) * _INV_SQRT_2PI
    cdf = 0.5 * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))
    return cdf + x * phi


def _d_silu(x: Array, y: Array) -> Array:
    del y
    s = jax.nn.sigmoid(x)
    return s * (1.0 + x * (1.0 - s))


_UNARY_DERIVS: dict[str, Callable[[Array, Array], Array]] = {
    "relu": lambda x, y: (x > 0).astype(x.dtype),
    "relu6": lambda x, y: ((x > 0) & (x < 6)).astype(x.dtype),
    "squared_relu": lambda x, y: 2.0 * jnp.maximum(x, 0.0),
    "gelu": _d_gelu_tanh,
    "gelu_exact": _d_gelu_exact,
    "silu": _d_silu,
    "sigmoid": lambda x, y: y * (1.0 - y),
    "tanh": lambda x, y: 1.0 - y * y,
    "exp": lambda x, y: y,
    "abs": lambda x, y: jnp.sign(x),
    "square": lambda x, y: 2.0 * x,
    "identity": lambda x, y: jnp.ones_like(x),
    "neg": lambda x, y: -jnp.ones_like(x),
    "softplus": lambda x, y: jax.nn.sigmoid(x),
}

#: OpKinds this module can differentiate (== what the generated backward
#: kernels support — rows and nhwc layouts).
DIFFERENTIABLE_KINDS = frozenset({
    ir.OpKind.EW_UNARY, ir.OpKind.EW_BINARY, ir.OpKind.AFFINE,
    ir.OpKind.ROW_NORM, ir.OpKind.ROW_SOFTMAX, ir.OpKind.POOL2D,
})

#: Binary fns :func:`_binary_vjp` has a rule for.  Declared up front so the
#: static verifier (repro.core.verify) can prove a differentiable plan has
#: every VJP rule *before* runtime rather than hitting the
#: NotImplementedError mid-backward.
BINARY_VJP_FNS = frozenset({"add", "sub", "mul", "div", "max", "min"})


def supports(program: ir.StackProgram) -> bool:
    """True when every op of ``program`` has a VJP rule here (i.e. the
    generated backward kernel can take the program end to end)."""
    return all(op.kind in DIFFERENTIABLE_KINDS and
               (op.kind != ir.OpKind.EW_UNARY or op.fn in _UNARY_DERIVS) and
               (op.kind != ir.OpKind.EW_BINARY or op.fn in BINARY_VJP_FNS)
               for op in program.ops)


def with_ref_vjp(fwd_fn: Callable, ref_fn: Callable) -> Callable:
    """Wrap a non-differentiable kernel forward with a reference backward.

    Registry kernel entries (``repro.core.registry``) declare where their
    VJP comes from: the kernel package's existing ``jax.custom_vjp``
    (attention / rmsnorm / swiglu / vocab-CE all carry one — forward runs
    the pallas kernel, backward recomputes through the jnp ref twin), or —
    for an entry whose pallas path has no custom rule yet — this wrapper:
    forward runs ``fwd_fn``, backward is ``jax.vjp`` of ``ref_fn`` over
    the same operands.  Both fns take positional arrays and return one
    array; the schedules differ, the math must not.
    """
    @jax.custom_vjp
    def run(*args):
        return fwd_fn(*args)

    def _fwd(*args):
        return fwd_fn(*args), args

    def _bwd(args, g):
        _, vjp = jax.vjp(ref_fn, *args)
        return vjp(g)

    run.defvjp(_fwd, _bwd)
    return run


# ---------------------------------------------------------------------------
# Helpers.
# ---------------------------------------------------------------------------

def _reduce_to(grad: Array, target: Array,
               row_mask: Array | None = None) -> Array:
    """Sum-reduce ``grad`` down to ``target``'s shape (undo broadcasting),
    casting to the target dtype — the cotangent contract of ``jax.vjp``.

    ``row_mask`` (shape ``(rows, 1)``, kernel path only) zeroes the
    contribution of zero-padded tile rows *before* the reduction: their
    cotangent is already zero, but a padded-row primal can be NaN/inf (e.g.
    ``div`` recomputed on all-zero rows), and ``0 * nan`` would otherwise
    poison the parameter-gradient grid sum."""
    if row_mask is not None:
        grad = jnp.where(row_mask, grad, 0)
    shape = jnp.shape(target)
    extra = grad.ndim - len(shape)
    if extra > 0:
        grad = jnp.sum(grad, axis=tuple(range(extra)))
    keep = tuple(i for i, d in enumerate(shape)
                 if d == 1 and grad.shape[i] != 1)
    if keep:
        grad = jnp.sum(grad, axis=keep, keepdims=True)
    return grad.astype(target.dtype)


def _balanced_max_mask(a: Array, b: Array, bigger: bool) -> Array:
    """Sub-gradient split of max/min matching jax.lax semantics: the winning
    operand takes the cotangent, exact ties split it evenly."""
    win = (a > b) if bigger else (a < b)
    tie = a == b
    return jnp.where(win, 1.0, jnp.where(tie, 0.5, 0.0))


# ---------------------------------------------------------------------------
# Per-op rules.
# ---------------------------------------------------------------------------

def op_vjp(op: ir.OpNode, env: Mapping[str, Array],
           params: Mapping[str, Array], g: Array,
           row_mask: Array | None = None
           ) -> tuple[dict[str, Array], dict[str, Array]]:
    """Cotangents of one op: ``g`` is the cotangent of ``op.output``;
    returns (input-value cotangents, parameter cotangents), both keyed by
    name and *not yet accumulated* — callers sum across consumers."""
    ins = [env[v] for v in op.inputs]
    ps = [params[p] for p in op.params]

    if op.kind == ir.OpKind.EW_UNARY:
        x = ins[0]
        y = env[op.output]
        dx = g * _UNARY_DERIVS[op.fn](x, y)
        return {op.inputs[0]: dx.astype(x.dtype)}, {}

    if op.kind == ir.OpKind.EW_BINARY:
        a = ins[0]
        b = ps[0] if ps else ins[1]
        da, db = _binary_vjp(op.fn, a, b, env[op.output], g)
        # The validity mask guards *reduced* value operands (nhwc broadcast
        # side inputs, whichever slot they sit in): out-of-image tile
        # positions recompute garbage primals, and 0 * inf or 0/0 would
        # poison the reduction.
        y_shape = jnp.shape(env[op.output])

        def _vmask(operand):
            return row_mask if jnp.shape(operand) != y_shape else None

        din = {op.inputs[0]: _reduce_to(da, a, _vmask(a))}
        dparams: dict[str, Array] = {}
        if ps:
            dparams[op.params[0]] = _reduce_to(db, b, row_mask)
        else:
            # a value consumed twice (x + x) accumulates both cotangents
            key = op.inputs[1]
            if key in din:
                din[key] = din[key] + _reduce_to(db, b, _vmask(b))
            else:
                din[key] = _reduce_to(db, b, _vmask(b))
        return din, dparams

    if op.kind == ir.OpKind.AFFINE:
        x = ins[0]
        scale, bias = ps
        return ({op.inputs[0]: _reduce_to(g * scale, x)},
                {op.params[0]: _reduce_to(g * x, scale, row_mask),
                 op.params[1]: _reduce_to(g, bias, row_mask)})

    if op.kind == ir.OpKind.ROW_NORM:
        return _row_norm_vjp(op, ins[0], ps, g, row_mask)

    if op.kind == ir.OpKind.ROW_SOFTMAX:
        y = env[op.output]
        dot = jnp.sum(g * y, axis=-1, keepdims=True)
        return {op.inputs[0]: (y * (g - dot)).astype(ins[0].dtype)}, {}

    if op.kind == ir.OpKind.POOL2D:
        # Full-array oracle path: pad with the neutral element (what the
        # forward's reduce_window padding computes with), run the shared
        # patch rule, crop the padding back off.
        x = ins[0]
        ph, pw = op.attrs["padding"]
        xp = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)),
                     constant_values=pool_neutral(x.dtype, op.fn))
        dxp = pool2d_patch_vjp(op, xp, env[op.output], g)
        if ph or pw:
            h, w = x.shape[-3], x.shape[-2]
            dxp = dxp[..., ph: ph + h, pw: pw + w, :]
        return {op.inputs[0]: dxp}, {}

    raise NotImplementedError(
        f"no VJP rule for op kind {op.kind} (op {op.name!r})")


def _binary_vjp(fn: str, a: Array, b: Array, y: Array, g: Array
                ) -> tuple[Array, Array]:
    if fn == "add":
        return g, g
    if fn == "sub":
        return g, -g
    if fn == "mul":
        return g * b, g * a
    if fn == "div":
        return g / b, -g * a / (b * b)
    if fn == "max":
        m = _balanced_max_mask(a, b, bigger=True)
        return g * m, g * (1.0 - m)
    if fn == "min":
        m = _balanced_max_mask(a, b, bigger=False)
        return g * m, g * (1.0 - m)
    raise NotImplementedError(f"no VJP rule for binary fn {fn!r}")


def pool_neutral(dtype, fn: str):
    """The pool's padding value: what an out-of-image position must hold so
    the windowed reduction reproduces the layer's own padding semantics."""
    if fn == "max":
        return (jnp.finfo(dtype).min if jnp.issubdtype(dtype, jnp.floating)
                else jnp.iinfo(dtype).min)
    return jnp.zeros((), dtype)


def _offset_scatter(c: Array, di: int, dj: int, in_h: int, in_w: int,
                    sh: int, sw: int) -> Array:
    """Place the window-offset-``(di, dj)`` cotangent contributions ``c``
    (shape ``(..., oh, ow, C)``) at input positions ``(di + i*sh, dj + j*sw)``
    of an ``(..., in_h, in_w, C)`` array — interior dilation by the stride
    plus an edge offset, expressed as one ``lax.pad`` (maps onto cheap
    VPU-friendly ops, no scatter)."""
    oh, ow = c.shape[-3], c.shape[-2]
    cfg = [(0, 0, 0)] * (c.ndim - 3)
    cfg.append((di, in_h - di - ((oh - 1) * sh + 1), sh - 1))
    cfg.append((dj, in_w - dj - ((ow - 1) * sw + 1), sw - 1))
    cfg.append((0, 0, 0))
    return jax.lax.pad(c, jnp.zeros((), c.dtype), cfg)


def pool2d_patch_vjp(op: ir.OpNode, x: Array, y: Array, g: Array) -> Array:
    """VJP of one POOL2D op over a *pre-padded* patch.

    ``x`` is the pool's input with padding already applied — out-of-image
    positions hold :func:`pool_neutral` — with spatial axes at ``(-3, -2)``;
    ``y``/``g`` are the pool output and its cotangent at the matching output
    extent, here on padded full images ``(N, Hp, Wp, C)`` (the oracle
    path).  The generated nhwc backward kernel applies the same rule to its
    VMEM tile through strided refs
    (``repro.kernels.fused_stack.nhwc_bwd._pool_patch_vjp``).

    Max ties follow the jax/XLA ``select_and_scatter`` convention: the first
    maximal element in row-major window order takes the whole cotangent.
    The neutral element never wins against real data, so halo padding gets
    zero gradient by construction.
    """
    kh, kw = op.attrs["window"]
    sh, sw = op.attrs["stride"]
    in_h, in_w = x.shape[-3], x.shape[-2]
    oh, ow = g.shape[-3], g.shape[-2]
    dx = jnp.zeros(x.shape, x.dtype)
    if op.fn == "avg":
        c = (g / float(kh * kw)).astype(x.dtype)
        for di in range(kh):
            for dj in range(kw):
                dx = dx + _offset_scatter(c, di, dj, in_h, in_w, sh, sw)
        return dx
    # max: route g to the first window position that attains the max.
    taken = jnp.zeros(g.shape, bool)
    for di in range(kh):
        for dj in range(kw):
            part = x[..., di: di + (oh - 1) * sh + 1: sh,
                     dj: dj + (ow - 1) * sw + 1: sw, :]
            sel = (part == y) & ~taken
            taken = taken | sel
            c = jnp.where(sel, g, 0).astype(x.dtype)
            dx = dx + _offset_scatter(c, di, dj, in_h, in_w, sh, sw)
    return dx


def _row_norm_vjp(op: ir.OpNode, x: Array, ps: list[Array], g: Array,
                  row_mask: Array | None = None
                  ) -> tuple[dict[str, Array], dict[str, Array]]:
    """rms / layer norm backward, recomputing the f32 statistics exactly as
    the forward does (same eps, same cast points)."""
    eps = op.attrs.get("eps", 1e-6)
    kind = op.attrs.get("norm", "rms")
    xf = x.astype(jnp.float32)
    if kind == "rms":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        r = jax.lax.rsqrt(var + eps)
        xhat_f = xf * r                                  # pre-cast normalized
    elif kind == "layer":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        r = jax.lax.rsqrt(var + eps)
        xhat_f = (xf - mu) * r
    else:
        raise ValueError(f"unknown norm kind {kind!r}")
    xhat = xhat_f.astype(x.dtype)                        # forward's y pre-scale

    dparams: dict[str, Array] = {}
    if ps:
        scale = ps[0]
        dparams[op.params[0]] = _reduce_to(g * xhat, scale, row_mask)
        if len(ps) > 1:
            dparams[op.params[1]] = _reduce_to(g, ps[1], row_mask)
        gy = (g * scale).astype(jnp.float32)             # cot of normalized y
    else:
        gy = g.astype(jnp.float32)

    if kind == "rms":
        # y = x * r(x):  dx = r*gy - x * r^3 * mean(gy*x)
        dxf = r * gy - xf * (r ** 3) * jnp.mean(gy * xf, axis=-1,
                                                keepdims=True)
    else:
        # standard layernorm backward in terms of xhat
        m1 = jnp.mean(gy, axis=-1, keepdims=True)
        m2 = jnp.mean(gy * xhat_f, axis=-1, keepdims=True)
        dxf = r * (gy - m1 - xhat_f * m2)
    return {op.inputs[0]: dxf.astype(x.dtype)}, dparams


# ---------------------------------------------------------------------------
# Whole-program reverse sweep.
# ---------------------------------------------------------------------------

def program_vjp(program: ir.StackProgram,
                env: Mapping[str, Array],
                params: Mapping[str, Array],
                gouts: Mapping[str, Array],
                row_mask: Array | None = None
                ) -> tuple[dict[str, Array], dict[str, Array]]:
    """Reverse-mode sweep over a whole program.

    ``env`` must contain every value of the program (inputs + all op
    outputs) — i.e. the recomputed forward; ``gouts`` the cotangent of each
    program output.  Returns cotangents for ``program.inputs`` and
    ``program.param_names``.  Pure jnp: traceable inside a Pallas kernel
    body over tiles, or runnable on full arrays as the oracle.
    """
    cot: dict[str, Array] = {}
    for v, g in gouts.items():
        cot[v] = g

    dparams: dict[str, Array] = {}
    for op in reversed(program.ops):
        g = cot.pop(op.output, None)
        if g is None:                       # output never used downstream
            continue
        din, dp = op_vjp(op, env, params, g, row_mask)
        for v, d in din.items():
            cot[v] = cot[v] + d if v in cot else d
        for p, d in dp.items():
            dparams[p] = dparams[p] + d if p in dparams else d

    dins: dict[str, Array] = {}
    for v in program.inputs:
        d = cot.get(v)
        dins[v] = jnp.zeros_like(env[v]) if d is None else d
    for p in program.param_names:
        if p not in dparams:
            dparams[p] = jnp.zeros_like(params[p])
    return dins, dparams
