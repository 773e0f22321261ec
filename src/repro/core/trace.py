"""Transparent frontend — lift plain JAX callables into the BrainSlug IR.

The paper's front-ends parse *unmodified* framework networks ("zero cost to
the user", Listing 3: ``brainslug.optimize(model)`` on a stock torchvision
net).  This module is the JAX analogue: :func:`trace` takes an arbitrary JAX
callable plus example inputs, stages it to a jaxpr (``jax.make_jaxpr``), and
lifts recognized primitives into :class:`~repro.core.ir.OpNode`s so the
analyzer/collapser/codegen pipeline can rewrite it.  Everything it does *not*
recognize is wrapped conservatively as an OPAQUE op closing over the
primitive bind — tracing never rejects a function, it just optimizes less of
it.

Recognition runs at three levels, from cheap to thorough:

1. **Call-boundary probing** — ``jax.nn`` activations reach the jaxpr as
   ``custom_jvp_call`` / ``pjit`` sub-jaxprs (relu, relu6, silu, softplus,
   ...).  A 1-in/1-out same-shape call is evaluated on a fixed probe vector
   and matched *behaviorally* against the IR's unary table, so the match is
   robust to how a given jax version implements the function.
2. **Elementwise-chain probing** — compositions inlined into the jaxpr
   (``gelu``'s tanh polynomial, ``x * sigmoid(x)``, the max/integer-pow
   spellings of relu / relu6 / squared_relu) are found as maximal
   single-source elementwise chains and probed the same way.
3. **Structural pattern rules** — dataflow idioms with reductions:
   ``reduce_window`` max/avg -> POOL2D, feature-wise ``mul``+``add`` on
   per-channel constants -> AFFINE, the mean-of-square/rsqrt subgraph ->
   ROW_NORM (rms and layer variants), softmax-over-trailing-axis ->
   ROW_SOFTMAX, ``dot_general`` -> MATMUL, ``conv_general_dilated`` ->
   CONV2D, and the six binary arithmetic primitives -> EW_BINARY.

A layout constraint that fails (reduction over a non-trailing axis,
asymmetric conv padding, non-NHWC dimension numbers, ...) simply drops the
op to OPAQUE — correctness first, capture second.  The per-op coverage is
reported by ``repro.api`` (``report()`` / ``explain()``).
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import core as jcore
from jax.extend import core as jex

from repro.core import ir

__all__ = ["trace", "TraceResult"]


# ---------------------------------------------------------------------------
# Flattening: jaxpr -> a flat list of Atoms over integer value ids.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Const:
    """A literal / captured-constant operand."""

    val: Any

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(np.shape(self.val))

    @property
    def size(self) -> int:
        return int(np.size(self.val))


@dataclasses.dataclass
class _Atom:
    """One flattened primitive application (or a recognized virtual op)."""

    prim: Any                     # jax Primitive, or None for virtual atoms
    operands: list                # int ids or _Const
    out_ids: list[int]
    params: dict
    virtual: str | None = None    # 'unary' | 'row_softmax' for probe matches
    fn_name: str | None = None    # unary table name for virtual='unary'


#: call-like primitives we inline (name -> params key holding the jaxpr).
_CALL_JAXPR_KEYS = {
    "pjit": "jaxpr",
    "jit": "jaxpr",
    "custom_jvp_call": "call_jaxpr",
    "custom_jvp_call_jaxpr": "fun_jaxpr",
    "custom_vjp_call": "call_jaxpr",
    "custom_vjp_call_jaxpr": "fun_jaxpr",
    "closed_call": "call_jaxpr",
    "core_call": "call_jaxpr",
    "xla_call": "call_jaxpr",
    "remat": "jaxpr",
    "remat2": "jaxpr",
    "checkpoint": "jaxpr",
}


#: call primitives carrying a user-defined derivative rule.  These are
#: never inlined (flattening would silently drop the custom backward) and
#: are only replaced by a table activation when a *gradient* probe agrees
#: too — a straight-through estimator whose forward is relu must stay put.
_CUSTOM_GRAD_CALLS = frozenset({
    "custom_jvp_call", "custom_jvp_call_jaxpr",
    "custom_vjp_call", "custom_vjp_call_jaxpr",
})

#: primitives that change autodiff semantics without changing the forward
#: values.  A behavioral probe only sees forward values, so a call whose
#: body contains one of these anywhere must also pass a gradient probe
#: before it may be replaced — jit(stop_gradient(relu(x))) matches relu's
#: forward exactly but has a zero backward.
_GRAD_FENCE_PRIMS = frozenset({"stop_gradient"}) | _CUSTOM_GRAD_CALLS


def _has_grad_fence(jaxpr: jex.Jaxpr) -> bool:
    """Does ``jaxpr`` (recursively) contain a gradient fence / custom rule?"""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _GRAD_FENCE_PRIMS:
            return True
        for v in eqn.params.values():
            subs = v if isinstance(v, (tuple, list)) else (v,)
            for s in subs:
                if isinstance(s, jex.ClosedJaxpr):
                    s = s.jaxpr
                if isinstance(s, jex.Jaxpr) and _has_grad_fence(s):
                    return True
    return False


def _inner_closed_jaxpr(eqn) -> jex.ClosedJaxpr | None:
    key = _CALL_JAXPR_KEYS.get(eqn.primitive.name)
    if key is None:
        return None
    sub = eqn.params.get(key)
    if sub is None:
        return None
    if isinstance(sub, jex.Jaxpr):
        sub = jex.ClosedJaxpr(sub, ())
    if not isinstance(sub, jex.ClosedJaxpr):
        return None
    if len(sub.jaxpr.invars) != len(eqn.invars):
        return None                       # unknown arg convention: keep opaque
    return sub


class _FlattenCtx:
    def __init__(self) -> None:
        self.atoms: list[_Atom] = []
        self.avals: dict[int, Any] = {}
        self._counter = itertools.count()

    def fresh(self, aval) -> int:
        i = next(self._counter)
        self.avals[i] = aval
        return i


def _flatten(closed: jex.ClosedJaxpr, operands: list, ctx: _FlattenCtx
             ) -> list:
    """Inline ``closed`` into ``ctx.atoms``; returns the output operands."""
    env: dict[Any, Any] = {}
    jaxpr = closed.jaxpr
    for v, o in zip(jaxpr.invars, operands):
        env[v] = o
    for v, c in zip(jaxpr.constvars, closed.consts):
        env[v] = _Const(c)

    def read(v):
        if isinstance(v, jex.Literal):
            return _Const(v.val)
        return env[v]

    for eqn in jaxpr.eqns:
        sub = _inner_closed_jaxpr(eqn)
        ins = [read(v) for v in eqn.invars]
        if sub is not None:
            hit = _probe_call(sub, ins, eqn, ctx)
            if hit is not None:
                virtual, fn_name, src = hit
                out_id = ctx.fresh(eqn.outvars[0].aval)
                ctx.atoms.append(_Atom(None, [src], [out_id], {},
                                       virtual=virtual, fn_name=fn_name))
                env[eqn.outvars[0]] = out_id
                continue
            if eqn.primitive.name not in _CUSTOM_GRAD_CALLS:
                outs = _flatten(sub, ins, ctx)
                for v, o in zip(eqn.outvars, outs):
                    if not isinstance(v, jcore.DropVar):
                        env[v] = o
                continue
            # unmatched custom-derivative call: fall through to a regular
            # atom (the OPAQUE fragment binds the original primitive, so
            # the user's custom backward survives)
        out_ids = []
        for ov in eqn.outvars:
            oid = ctx.fresh(ov.aval)
            out_ids.append(oid)
            if not isinstance(ov, jcore.DropVar):
                env[ov] = oid
        ctx.atoms.append(_Atom(eqn.primitive, ins, out_ids,
                               dict(eqn.params)))
    return [read(v) for v in jaxpr.outvars]


# ---------------------------------------------------------------------------
# Behavioral probing.
# ---------------------------------------------------------------------------

#: Probe support: negatives/positives, the relu/relu6 breakpoints (0 and 6),
#: large-|x| tails that separate softplus/gelu variants from their
#: asymptotes, and far-out points (±60, ±1000) so a function that merely
#: coincides with a table activation on a narrow range is not rewritten.
_PROBE_BASE = np.array(
    [-1000.0, -60.0, -20.0, -8.0, -4.0, -2.5, -1.5, -1.0, -0.6, -0.3,
     -0.1, 0.0, 0.05, 0.2, 0.5, 1.0, 1.7, 2.5, 3.3, 4.0, 5.5, 6.0, 6.2,
     7.0, 8.0, 20.0, 60.0, 1000.0],
    dtype=np.float64)

_UNARY_CANDIDATES: tuple[tuple[str, Callable], ...] = tuple(
    ir._UNARY_FNS.items())


def _probe_batches(aval) -> list[jnp.ndarray]:
    """Probe arrays of ``aval``'s exact shape that jointly cover the whole
    probe support.  A sub-jaxpr is baked to one shape, so a tensor smaller
    than the support gets several batches — truncating instead would lose
    the discriminating points (e.g. the x > 6 region that separates relu
    from relu6) and misidentify activations on small tensors."""
    n = max(int(math.prod(aval.shape)), 1)
    k = -(-_PROBE_BASE.size // n)
    flat = np.resize(_PROBE_BASE, k * n)          # cyclic tile / pad
    shape = tuple(aval.shape) or ()
    return [jnp.asarray(flat[i * n:(i + 1) * n].reshape(shape),
                        dtype=aval.dtype) for i in range(k)]


def _probe_tol(aval) -> float:
    return 2e-2 if np.dtype(aval.dtype).itemsize < 4 else 1e-5


def _match_unary_values(xs: list, ys: list, aval) -> str | None:
    """Which named unary fn (if any) maps probe batches ``xs`` to ``ys``."""
    tol = _probe_tol(aval)
    ref = np.concatenate([np.asarray(y, np.float64).reshape(-1)
                          for y in ys])
    if np.any(np.isnan(ref)):
        return None
    # infinities are compared positionally by allclose (exp overflows at
    # the far probe points — a candidate must overflow in the same places)
    x_flat = jnp.concatenate([jnp.reshape(x, (-1,)) for x in xs])
    for name, fn in _UNARY_CANDIDATES:
        try:
            cand = np.asarray(fn(x_flat), np.float64)
        except Exception:                         # pragma: no cover - defensive
            continue
        if cand.shape == ref.shape and np.allclose(ref, cand, rtol=tol,
                                                   atol=tol):
            return name
    return None


def _probe_call(sub: jex.ClosedJaxpr, ins: list, eqn, ctx: _FlattenCtx
                ) -> tuple[str, str | None, int] | None:
    """Try to recognize a whole sub-jaxpr call as one IR op.

    Matches 1-in/1-out same-shape float calls against the unary table and
    against trailing-axis softmax.  Returns (virtual kind, fn name, src id)
    or None to inline the call instead.
    """
    if len(eqn.outvars) != 1 or isinstance(eqn.outvars[0], jcore.DropVar):
        return None
    ids = [o for o in ins if isinstance(o, int)]
    if len(ids) != 1:
        return None
    if any(isinstance(o, _Const) and o.size != 1 for o in ins):
        return None
    src = ids[0]
    aval_in = ctx.avals[src]
    aval_out = eqn.outvars[0].aval
    if (tuple(aval_in.shape) != tuple(aval_out.shape)
            or aval_in.dtype != aval_out.dtype
            or len(aval_in.shape) < 1            # 0-d: keep opaque
            or math.prod(aval_in.shape) == 0     # empty: nothing to probe
            or not jnp.issubdtype(aval_in.dtype, jnp.floating)):
        return None
    # don't eagerly execute huge or effectful sub-jaxprs on fabricated data
    if getattr(sub.jaxpr, "effects", None) or len(sub.jaxpr.eqns) > 64:
        return None
    # a fence (stop_gradient / custom derivative) anywhere inside the call
    # is invisible to the forward probe — require the gradient probe too,
    # whatever the outer call primitive is (jit/pjit included)
    needs_grad_check = (eqn.primitive.name in _CUSTOM_GRAD_CALLS
                        or _has_grad_fence(sub.jaxpr))
    probes = _probe_batches(aval_in)

    def f(x):
        args = [x if isinstance(o, int) else jnp.asarray(o.val) for o in ins]
        return jcore.eval_jaxpr(sub.jaxpr, sub.consts, *args)[0]

    try:
        ys = [f(p) for p in probes]
    except Exception:
        return None
    name = _match_unary_values(probes, ys, aval_in)
    if name is not None and name != "identity":
        if (needs_grad_check
                and not _grad_probe_matches(eqn, ins, aval_in,
                                            ir._UNARY_FNS[name])):
            return None            # forward matches, backward differs
        return ("unary", name, src)
    if len(aval_in.shape) >= 2:
        tol = _probe_tol(aval_in)
        try:
            ok = all(
                np.allclose(np.asarray(y, np.float64),
                            np.asarray(jax.nn.softmax(p, axis=-1),
                                       np.float64), rtol=tol, atol=tol)
                for p, y in zip(probes, ys))
        except Exception:                         # pragma: no cover - defensive
            return None
        if ok:
            if (needs_grad_check
                    and not _grad_probe_matches(
                        eqn, ins, aval_in,
                        lambda v: jax.nn.softmax(v, axis=-1))):
                return None        # e.g. jit(stop_gradient(softmax(x)))
            return ("row_softmax", None, src)
    return None


def _grad_probe_matches(eqn, ins: list, aval, cand: Callable) -> bool:
    """Does the call's (possibly fenced / custom) backward agree with the
    candidate replacement's?  Probed at kink-shifted points — the table
    derivative at an exact kink (relu at 0) is convention, not semantics."""
    try:
        subfuns, bind_params = eqn.primitive.get_bind_params(
            dict(eqn.params))
    except Exception:                             # pragma: no cover
        return False

    def h(x):
        args = [x if isinstance(o, int) else jnp.asarray(o.val) for o in ins]
        out = eqn.primitive.bind(*subfuns, *args, **bind_params)
        return out[0] if eqn.primitive.multiple_results else out

    tol = max(_probe_tol(aval), 1e-4)            # d/dx amplifies probe noise
    for probe in _probe_batches(aval):
        probe = probe + jnp.asarray(0.0137, probe.dtype)   # step off kinks
        try:
            y1, vjp1 = jax.vjp(h, probe)
            # non-uniform cotangent: at ones, row-normalizing backwards
            # (softmax: J^T . 1 = 0) are degenerate and a zeroed fence
            # would be indistinguishable from the candidate
            ct = (jnp.linspace(0.5, 1.5, y1.size, dtype=jnp.float32)
                  .reshape(y1.shape).astype(y1.dtype))
            g1 = vjp1(ct)[0]
            y2, vjp2 = jax.vjp(cand, probe)
            g2 = vjp2(ct)[0]
        except Exception:
            return False
        if not np.allclose(np.asarray(g1, np.float64),
                           np.asarray(g2, np.float64), rtol=tol, atol=tol):
            return False
    return True


def _eval_atom(atom: _Atom, args: list):
    """Re-execute one atom on concrete arrays (probe path)."""
    if atom.virtual == "unary":
        return ir._UNARY_FNS[atom.fn_name](args[0])
    if atom.virtual == "row_softmax":
        return jax.nn.softmax(args[0], axis=-1)
    subfuns, bind_params = atom.prim.get_bind_params(dict(atom.params))
    out = atom.prim.bind(*subfuns, *args, **bind_params)
    return out[0] if atom.prim.multiple_results else out


# ---------------------------------------------------------------------------
# Recognition tables.
# ---------------------------------------------------------------------------

#: Primitives through which "y is an elementwise function of single source x"
#: propagates.  Comparisons/select are included so numerically careful
#: compositions (softplus-style) stay probeable.  ``stop_gradient`` is
#: deliberately absent: a probe only checks forward values, and replacing a
#: chain that fences gradients with a table activation would silently change
#: the backward.
_CHAIN_PRIMS = frozenset({
    "add", "sub", "mul", "div", "max", "min", "neg", "abs", "exp", "log",
    "tanh", "logistic", "integer_pow", "square", "rsqrt", "sqrt", "log1p",
    "expm1", "sign", "floor", "ceil", "round", "erf", "erfc", "pow",
    "exp2", "log2", "cbrt", "clamp", "ne", "eq", "lt", "le", "gt", "ge",
    "and", "or", "xor", "not", "select_n", "is_finite",
    "convert_element_type",
})

#: shape-compatible single-input atoms structural walkers may hop across
#: (keepdims re-expansion, dtype normalization).  ``stop_gradient`` is
#: deliberately absent — same rule as _CHAIN_PRIMS: a structural match
#: only checks forward dataflow, so hopping a user gradient fence would
#: rewrite e.g. ``x * stop_gradient(rsqrt(mean(x^2)+eps))`` into a fully
#: differentiable ROW_NORM.  The one sound exception is softmax's internal
#: row-max fence (ROW_SOFTMAX reproduces it), which _try_softmax opts into
#: explicitly via ``hop_stop_gradient``.
_HOP_PRIMS = frozenset({"broadcast_in_dim", "convert_element_type"})

_COMMUTATIVE = frozenset({"add", "mul", "max", "min"})

_SINGLE_UNARY = {          # one-primitive EW_UNARY lifts
    "logistic": "sigmoid", "tanh": "tanh", "exp": "exp", "abs": "abs",
    "neg": "neg", "square": "square",
}

_BINARY_PRIMS = frozenset({"add", "sub", "mul", "div", "max", "min"})


def _is_param_like(shape: Sequence[int]) -> bool:
    """Shapes the generated kernels accept as (1, C)-broadcast parameters."""
    shape = tuple(shape)
    return len(shape) <= 1 or all(d == 1 for d in shape[:-1])


def _liftable(shape: Sequence[int]) -> bool:
    """Shapes stacks can tile: rank >= 1 and non-empty (0-d values and
    zero-size arrays stay opaque)."""
    shape = tuple(shape)
    return len(shape) >= 1 and 0 not in shape


@dataclasses.dataclass(frozen=True)
class _Alias:
    """A value id that is a pure broadcast/view of a parameter or constant."""

    pname: str
    src_shape: tuple[int, ...]
    tgt_shape: tuple[int, ...]
    dtype: Any


# ---------------------------------------------------------------------------
# Trace result.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TraceResult:
    """A plain JAX callable lifted into the BrainSlug graph IR.

    ``graph`` is a standard :class:`~repro.core.ir.NetGraph`; the first
    flattened input leaf is the graph input (name ``arg0``), every leaf is
    additionally available as a runtime parameter ``arg{i}``, and captured
    constants/literals are bound in ``const_params``.
    """

    graph: ir.NetGraph
    shapes: dict[str, tuple[int, ...]]        # value name -> shape
    dtypes: dict[str, Any]                    # value name -> dtype
    param_shapes: dict[str, tuple[int, ...]]  # param name -> shape
    const_params: dict[str, jnp.ndarray]      # captured consts/literals
    n_leaves: int
    leaf_avals: tuple                         # (shape, dtype) per input leaf
    in_tree: Any
    out_tree: Any
    out_refs: tuple                           # ('env'|'leaf'|'const', ref)
    input_name: str
    n_atoms: int


# ---------------------------------------------------------------------------
# The builder: atoms -> OpNodes.
# ---------------------------------------------------------------------------

class _Builder:
    def __init__(self, ctx: _FlattenCtx, leaf_ids: list[int],
                 out_operands: list) -> None:
        self.atoms = ctx.atoms
        self.avals = ctx.avals
        self.leaf_index = {lid: i for i, lid in enumerate(leaf_ids)}

        # dataflow maps (-1 marks "escapes as a traced output")
        self.producer: dict[int, int] = {}
        for i, a in enumerate(self.atoms):
            for o in a.out_ids:
                self.producer[o] = i
        self.consumers: dict[int, list[int]] = {}
        for i, a in enumerate(self.atoms):
            for o in a.operands:
                if isinstance(o, int):
                    self.consumers.setdefault(o, []).append(i)
        for o in out_operands:
            if isinstance(o, int):
                self.consumers.setdefault(o, []).append(-1)

        # builder state
        self.val_name: dict[int, str] = {}
        self.alias: dict[int, _Alias] = {}
        self.redirect: dict[int, Any] = {}
        self.const_params: dict[str, jnp.ndarray] = {}
        self.param_shapes: dict[str, tuple[int, ...]] = {}
        self.shapes: dict[str, tuple[int, ...]] = {}
        self.dtypes: dict[str, Any] = {}
        self.ops: list[ir.OpNode] = []
        self.claimed: set[int] = set()
        self.emitted: set[int] = set()
        self._failed_probes: set[int] = set()
        self._names = itertools.count()
        self._ew_src: dict[int, int] = {}
        self._const_names: dict[int, str] = {}    # id(val) -> param name

        if leaf_ids:
            lid = leaf_ids[0]
            self.val_name[lid] = "arg0"
            self.shapes["arg0"] = tuple(self.avals[lid].shape)
            self.dtypes["arg0"] = self.avals[lid].dtype
        for lid, i in self.leaf_index.items():
            self.param_shapes[f"arg{i}"] = tuple(self.avals[lid].shape)

        self._register_views()
        self._compute_ew_sources()

    def _register_views(self) -> None:
        """Pre-pass: broadcast/convert atoms over params and constants are
        pure *views* — register them as aliases/redirects up front so every
        matcher sees them regardless of atom order (a BatchNorm bias
        broadcast appears after the scale multiply it rides along with)."""
        for i, a in enumerate(self.atoms):
            if a.virtual is not None:
                continue
            nm = a.prim.name
            if nm == "broadcast_in_dim" and self._try_broadcast(i):
                self.claimed.add(i)
            elif nm == "convert_element_type" and self._try_convert(i):
                self.claimed.add(i)
            elif nm == "copy" and len(a.out_ids) == 1:
                self.redirect[a.out_ids[0]] = self.resolve(a.operands[0])
                self.claimed.add(i)

    # -- naming helpers ----------------------------------------------------

    def _fresh_value(self, hint: str = "v") -> str:
        return f"{hint}{next(self._names)}"

    def _op_name(self, hint: str) -> str:
        return f"{hint}_{next(self._names)}"

    # -- operand resolution ------------------------------------------------

    def resolve(self, o):
        while isinstance(o, int) and o in self.redirect:
            o = self.redirect[o]
        return o

    def _shape_of(self, o) -> tuple[int, ...]:
        o = self.resolve(o)
        if isinstance(o, _Const):
            return o.shape
        return tuple(self.avals[o].shape)

    def _dtype_of(self, o):
        o = self.resolve(o)
        if isinstance(o, _Const):
            return jnp.asarray(o.val).dtype
        return self.avals[o].dtype

    def _const_param(self, val) -> str:
        """Param name for a captured constant.  Cached by the value's
        identity: a constvar shared by several consumers (and speculative
        as_param calls inside match attempts that later fail) must reuse
        one entry, not mint a fresh array copy each time."""
        name = self._const_names.get(id(val))
        if name is not None:
            return name
        name = f"c{next(self._names)}"
        arr = jnp.asarray(val)
        self.const_params[name] = arr
        self.param_shapes[name] = tuple(arr.shape)
        self._const_names[id(val)] = name
        return name

    def as_value(self, o) -> str | None:
        """Name of ``o`` in the runtime env, or None (no bind emitted)."""
        o = self.resolve(o)
        if isinstance(o, int) and o in self.val_name:
            return self.val_name[o]
        return None

    def valueable(self, o) -> bool:
        o = self.resolve(o)
        return isinstance(o, int) and (o in self.val_name
                                       or o in self.leaf_index)

    def ensure_value(self, o) -> str:
        """Env-value name for ``o``, emitting a bind op if needed."""
        o = self.resolve(o)
        if isinstance(o, int) and o in self.val_name:
            return self.val_name[o]
        if isinstance(o, int) and o in self.leaf_index:
            pname = f"arg{self.leaf_index[o]}"
            vname = self._emit_bind(pname, tuple(self.avals[o].shape),
                                    self.avals[o].dtype)
            self.val_name[o] = vname
            return vname
        if isinstance(o, int) and o in self.alias:
            al = self.alias[o]
            vname = self._emit_bind(al.pname, al.tgt_shape, al.dtype)
            self.val_name[o] = vname
            return vname
        if isinstance(o, _Const):
            pname = self._const_param(o.val)
            arr = self.const_params[pname]
            return self._emit_bind(pname, tuple(arr.shape), arr.dtype)
        raise AssertionError(f"cannot materialize operand {o!r}")

    def _emit_bind(self, pname: str, shape: tuple[int, ...], dtype) -> str:
        vname = self._fresh_value()

        def bind_fn(p, _shape=tuple(shape), _dtype=dtype):
            return jnp.broadcast_to(jnp.asarray(p), _shape).astype(_dtype)

        self._append(ir.OpNode(
            ir.OpKind.OPAQUE, self._op_name("bind"), (), vname,
            params=(pname,),
            attrs={"fn": bind_fn, "out_shape": tuple(shape),
                   "synthetic": True}), vname, shape, dtype)
        return vname

    def as_param(self, o) -> str | None:
        """Param name for ``o`` if it can ride as a kernel parameter."""
        o = self.resolve(o)
        if isinstance(o, _Const):
            if not _is_param_like(o.shape):
                return None
            return self._const_param(o.val)
        if isinstance(o, int) and o in self.leaf_index:
            if not _is_param_like(self.avals[o].shape):
                return None
            return f"arg{self.leaf_index[o]}"
        if isinstance(o, int) and o in self.alias:
            al = self.alias[o]
            if _is_param_like(al.src_shape) and _is_param_like(al.tgt_shape):
                return al.pname
        return None

    def _append(self, op: ir.OpNode, out_name: str,
                shape: tuple[int, ...], dtype=None) -> None:
        self.ops.append(op)
        self.shapes[out_name] = tuple(shape)
        if dtype is not None:
            self.dtypes[out_name] = dtype

    def _emit_for(self, out_id: int, op: ir.OpNode) -> None:
        self.ops.append(op)
        self.val_name[out_id] = op.output
        self.shapes[op.output] = tuple(self.avals[out_id].shape)
        self.dtypes[op.output] = self.avals[out_id].dtype

    # -- elementwise-chain machinery ---------------------------------------

    def _compute_ew_sources(self) -> None:
        for a in self.atoms:
            if len(a.out_ids) != 1:
                continue
            if a.virtual is None:
                if a.prim.name not in _CHAIN_PRIMS:
                    continue
            elif a.virtual != "unary":
                continue
            src = None
            ok = True
            for o in a.operands:
                if isinstance(o, _Const):
                    if o.size != 1:
                        ok = False
                        break
                    continue
                s = self._ew_src.get(o, o)
                if src is None:
                    src = s
                elif s != src:
                    ok = False
                    break
            if not ok or src is None:
                continue
            out = a.out_ids[0]
            if (tuple(self.avals[out].shape) != tuple(self.avals[src].shape)
                    or src in self.alias):
                continue
            self._ew_src[out] = src

    def _chain_endpoint(self, idx: int, src: int) -> int:
        cur = self.atoms[idx].out_ids[0]
        while True:
            cons = self.consumers.get(cur, [])
            if len(cons) != 1 or cons[0] == -1:
                break
            j = cons[0]
            a = self.atoms[j]
            if (j in self.claimed or len(a.out_ids) != 1
                    or self._ew_src.get(a.out_ids[0]) != src):
                break
            cur = a.out_ids[0]
        return self.producer[cur]

    def _chain_slice(self, end_idx: int, src: int) -> list[int] | None:
        """Atoms of the chain ending at ``end_idx``, or None if invalid."""
        seen: set[int] = set()
        work = [end_idx]
        while work:
            i = work.pop()
            if i in seen:
                continue
            seen.add(i)
            if i in self.claimed:
                return None
            for o in self.atoms[i].operands:
                if isinstance(o, _Const) or o == src:
                    continue
                if self._ew_src.get(o) != src:
                    return None
                work.append(self.producer[o])
        idxs = sorted(seen)
        end_out = self.atoms[end_idx].out_ids[0]
        for i in idxs:
            out = self.atoms[i].out_ids[0]
            if out == end_out:
                continue
            if any(c == -1 or c not in seen
                   for c in self.consumers.get(out, [])):
                return None
        return idxs

    def _try_chain_probe(self, idx: int) -> bool:
        a = self.atoms[idx]
        if len(a.out_ids) != 1 or a.out_ids[0] not in self._ew_src:
            return False
        src = self._ew_src[a.out_ids[0]]
        if not self.valueable(src):
            return False
        aval = self.avals[src]
        # 0-d / empty chains stay opaque: the rows kernels tile (rows, F)
        if (not jnp.issubdtype(aval.dtype, jnp.floating)
                or not _liftable(aval.shape)):
            return False
        end = self._chain_endpoint(idx, src)
        if end in self._failed_probes:
            return False
        idxs = self._chain_slice(end, src)
        if idxs is None:
            self._failed_probes.add(end)
            return False
        if idxs[0] != idx:
            # an earlier atom of this chain was already emitted another way
            return False
        end_out = self.atoms[end].out_ids[0]
        if self.avals[end_out].dtype != aval.dtype:
            self._failed_probes.add(end)
            return False
        probes = _probe_batches(aval)
        ys = []
        try:
            for probe in probes:
                env = {src: probe}
                for i in idxs:
                    atom = self.atoms[i]
                    args = [env[o] if isinstance(o, int)
                            else jnp.asarray(o.val)
                            for o in atom.operands]
                    env[atom.out_ids[0]] = _eval_atom(atom, args)
                ys.append(env[end_out])
        except Exception:
            self._failed_probes.add(end)
            return False
        name = _match_unary_values(probes, ys, aval)
        if name is None or (name == "identity" and len(idxs) == 1):
            self._failed_probes.add(end)
            return False
        x = self.ensure_value(src)
        self.claimed.update(idxs)
        self._emit_for(end_out, ir.OpNode(
            ir.OpKind.EW_UNARY, self._op_name(name), (x,),
            self._fresh_value(), fn=name))
        return True

    # -- structural walkers ------------------------------------------------

    def _producer_of(self, o, from_idx: int, claim: list[int], *,
                     hop_stop_gradient: bool = False
                     ) -> tuple[_Atom, int] | None:
        """(atom, idx) producing ``o``, hopping over broadcast/convert
        atoms (collected into ``claim``).  Every traversed value must be
        consumed exactly once, by the node we came from.
        ``hop_stop_gradient`` additionally hops gradient fences — only
        sound when the matched IR op reproduces the fence itself
        (softmax's row-max); every other matcher must leave a fenced
        subgraph un-lifted so the user's backward survives."""
        hops = (_HOP_PRIMS | {"stop_gradient"} if hop_stop_gradient
                else _HOP_PRIMS)
        o = self.resolve(o)
        while isinstance(o, int):
            i = self.producer.get(o)
            if i is None or i in self.claimed or i in self.emitted:
                return None
            if self.consumers.get(o, []) != [from_idx]:
                return None
            a = self.atoms[i]
            if (a.virtual is None and a.prim.name in hops
                    and len(a.out_ids) == 1):
                claim.append(i)
                from_idx = i
                o = self.resolve(a.operands[0])
                continue
            return a, i
        return None

    def _walk(self, o, from_idx: int, claim: list[int], prim_name: str
              ) -> tuple[_Atom, int] | None:
        got = self._producer_of(o, from_idx, claim)
        if got is None:
            return None
        a, i = got
        if a.virtual is not None or a.prim.name != prim_name:
            return None
        return a, i

    def _scalar_const(self, o) -> float | None:
        o = self.resolve(o)
        if isinstance(o, _Const) and o.size == 1:
            try:
                return float(np.asarray(o.val).reshape(()))
            except (TypeError, ValueError):
                return None
        return None

    def _try_affine(self, ri: int) -> bool:
        root = self.atoms[ri]
        out = root.out_ids[0]
        if not _liftable(self.avals[out].shape):
            return False
        for u, b in (tuple(root.operands), tuple(root.operands)[::-1]):
            if not isinstance(u, int):
                continue
            claim: list[int] = []
            got = self._walk(u, ri, claim, "mul")
            if got is None:
                continue
            m, mi = got
            for xo, so in (tuple(m.operands), tuple(m.operands)[::-1]):
                if not self.valueable(xo):
                    continue
                if self._shape_of(xo) != tuple(self.avals[out].shape):
                    continue
                s = self.as_param(so)
                bp = self.as_param(b)
                if s is None or bp is None:
                    continue
                x = self.ensure_value(xo)
                self.claimed.update(claim + [mi, ri])
                self._emit_for(out, ir.OpNode(
                    ir.OpKind.AFFINE, self._op_name("affine"), (x,),
                    self._fresh_value(), params=(s, bp)))
                return True
        return False

    def _pool_geometry(self, a: _Atom) -> tuple | None:
        """(window, stride, padding) if the reduce_window is a plain NHWC
        spatial pool; None otherwise (layout constraint failed)."""
        p = a.params
        wd = tuple(p.get("window_dimensions", ()))
        ws = tuple(p.get("window_strides", ()))
        pad = tuple(tuple(q) for q in p.get("padding", ()))
        if len(wd) != 4 or len(ws) != 4 or len(pad) != 4:
            return None
        if wd[0] != 1 or wd[3] != 1 or ws[0] != 1 or ws[3] != 1:
            return None
        if tuple(p.get("base_dilation", (1,) * 4)) != (1, 1, 1, 1):
            return None
        if tuple(p.get("window_dilation", (1,) * 4)) != (1, 1, 1, 1):
            return None
        if pad[0] != (0, 0) or pad[3] != (0, 0):
            return None
        if pad[1][0] != pad[1][1] or pad[2][0] != pad[2][1]:
            return None
        return ((wd[1], wd[2]), (ws[1], ws[2]), (pad[1][0], pad[2][0]))

    def _emit_pool(self, out_id: int, x, fn: str, geom) -> None:
        window, stride, padding = geom
        xv = self.ensure_value(x)
        self._emit_for(out_id, ir.OpNode(
            ir.OpKind.POOL2D, self._op_name(f"{fn}pool"), (xv,),
            self._fresh_value(), fn=fn,
            attrs={"window": window, "stride": stride, "padding": padding}))

    def _try_avgpool(self, ri: int) -> bool:
        root = self.atoms[ri]
        u, d = root.operands
        n = self._scalar_const(d)
        if n is None or not isinstance(u, int):
            return False
        claim: list[int] = []
        got = self._walk(u, ri, claim, "reduce_window_sum")
        if got is None:
            return False
        rw, rwi = got
        if (not self.valueable(rw.operands[0])
                or not _liftable(self._shape_of(rw.operands[0]))):
            return False
        geom = self._pool_geometry(rw)
        if geom is None or geom[0][0] * geom[0][1] != n:
            return False
        self.claimed.update(claim + [rwi, ri])
        self._emit_pool(root.out_ids[0], rw.operands[0], "avg", geom)
        return True

    def _try_softmax(self, ri: int) -> bool:
        root = self.atoms[ri]
        g, i_o = root.operands
        if not isinstance(g, int) or not isinstance(i_o, int):
            return False
        out = root.out_ids[0]
        if not _liftable(self.avals[out].shape):
            return False
        ndim = len(self.avals[out].shape)
        gi = self.producer.get(g)
        if gi is None or gi in self.claimed or gi in self.emitted:
            return False
        ga = self.atoms[gi]
        if ga.virtual is not None or ga.prim.name != "exp":
            return False
        claim: list[int] = []
        got = self._walk(i_o, ri, claim, "reduce_sum")
        if got is None:
            return False
        s, si = got
        if tuple(s.params.get("axes", ())) != (ndim - 1,):
            return False
        if self.resolve(s.operands[0]) != g:
            return False
        # the exponentials feed exactly the row-sum and the division
        if sorted(self.consumers.get(g, [])) != sorted([ri, si]):
            return False
        claim2: list[int] = []
        got = self._walk(ga.operands[0], gi, claim2, "sub")
        if got is None:
            return False
        sub, subi = got
        a, m = sub.operands
        if not self.valueable(a):
            return False
        # the row-max walk is the one place a stop_gradient hop is sound:
        # jax.nn.softmax fences its max, and ROW_SOFTMAX reproduces that
        claim3: list[int] = []
        got = self._producer_of(m, subi, claim3, hop_stop_gradient=True)
        if got is not None:
            cur, curi = got
            # optional `max(-inf, rowmax)` guard jax.nn.softmax inserts
            if (cur.virtual is None and cur.prim.name == "max"
                    and any(self._scalar_const(o) == -np.inf
                            for o in cur.operands)):
                claim3.append(curi)
                vo = [o for o in cur.operands
                      if self._scalar_const(o) != -np.inf][0]
                got = self._producer_of(vo, curi, claim3,
                                        hop_stop_gradient=True)
        if got is None:
            return False
        cur, curi = got
        if (cur.virtual is not None or cur.prim.name != "reduce_max"
                or tuple(cur.params.get("axes", ())) != (ndim - 1,)):
            return False
        if self.resolve(cur.operands[0]) != self.resolve(a):
            return False
        xv = self.ensure_value(a)
        self.claimed.update(claim + claim2 + claim3
                            + [gi, si, subi, curi, ri])
        self._emit_for(out, ir.OpNode(
            ir.OpKind.ROW_SOFTMAX, self._op_name("softmax"), (xv,),
            self._fresh_value()))
        return True

    def _mean_terminal(self, o, from_idx: int, claim: list[int],
                       features: int) -> tuple[Any, int] | None:
        """(terminal operand, reduce_sum idx) of a trailing-axis mean."""
        for prim, want in (("div", float(features)),
                           ("mul", 1.0 / features)):
            local: list[int] = []
            got = self._walk(o, from_idx, local, prim)
            if got is None:
                continue
            d, di = got
            # div is not commutative: only sum/n is a mean, n/sum is a
            # reciprocal — the scalar must be the second operand there
            orders = ((tuple(d.operands),) if prim == "div"
                      else (tuple(d.operands), tuple(d.operands)[::-1]))
            so = None
            for p, q in orders:
                n = self._scalar_const(q)
                if n is None or isinstance(p, _Const):
                    continue
                if not np.isclose(n, want, rtol=1e-6):
                    continue
                so = p
                break
            if so is None:
                continue
            local.append(di)
            got = self._walk(so, di, local, "reduce_sum")
            if got is None:
                continue
            rs, rsi = got
            t = self.resolve(rs.operands[0])
            ndim = len(self._shape_of(t))
            if tuple(rs.params.get("axes", ())) != (ndim - 1,):
                continue
            local.append(rsi)
            claim.extend(local)
            return t, rsi
        return None

    def _square_terminal(self, o, from_idx: int,
                         claim: list[int]) -> Any | None:
        local: list[int] = []
        got = self._walk(o, from_idx, local, "square")
        if got is not None:
            claim.extend(local + [got[1]])
            return self.resolve(got[0].operands[0])
        local = []
        got = self._walk(o, from_idx, local, "integer_pow")
        if got is not None and got[0].params.get("y") == 2:
            claim.extend(local + [got[1]])
            return self.resolve(got[0].operands[0])
        local = []
        got = self._walk(o, from_idx, local, "mul")
        if got is not None:
            a, b = (self.resolve(q) for q in got[0].operands)
            if a == b and isinstance(a, int):
                claim.extend(local + [got[1]])
                return a
        return None

    def _rsqrt_var_chain(self, h, ri: int, claim: list[int],
                         features: int) -> tuple[Any, float] | None:
        """Walk ``h`` = rsqrt(mean(square(t)) + eps); returns (t, eps)."""
        got = self._walk(h, ri, claim, "rsqrt")
        if got is None:
            return None
        r, ri2 = got
        claim.append(ri2)
        got = self._walk(r.operands[0], ri2, claim, "add")
        if got is None:
            return None
        ad, adi = got
        claim.append(adi)
        for v, e in (tuple(ad.operands), tuple(ad.operands)[::-1]):
            eps = self._scalar_const(e)
            if eps is None or isinstance(v, _Const):
                continue
            sub_claim: list[int] = []
            got_m = self._mean_terminal(v, adi, sub_claim, features)
            if got_m is None:
                continue
            q, rsi = got_m
            t = self._square_terminal(q, rsi, sub_claim)
            if t is None:
                continue
            claim.extend(sub_claim)
            return t, eps
        return None

    def _try_row_norm(self, ri: int) -> bool:
        root = self.atoms[ri]
        out = root.out_ids[0]
        shape = tuple(self.avals[out].shape)
        if not _liftable(shape):
            return False
        features = shape[-1]
        for f_o, h_o in (tuple(root.operands), tuple(root.operands)[::-1]):
            if not isinstance(f_o, int) or not isinstance(h_o, int):
                continue
            claim: list[int] = []
            got = self._rsqrt_var_chain(h_o, ri, claim, features)
            if got is None:
                continue
            t, eps = got
            # rms: mul(x, rsqrt(mean(x^2) + eps))
            if t == self.resolve(f_o) and self.valueable(t):
                if self._shape_of(t) != shape:
                    continue
                xv = self.ensure_value(t)
                self.claimed.update(claim + [ri])
                self._emit_for(out, ir.OpNode(
                    ir.OpKind.ROW_NORM, self._op_name("rmsnorm"), (xv,),
                    self._fresh_value(),
                    attrs={"norm": "rms", "eps": eps}))
                return True
            # layer: f = sub(a, mean(a)); mul(f, rsqrt(mean(f^2) + eps))
            if t != self.resolve(f_o):
                continue
            fi = self.producer.get(self.resolve(f_o))
            if fi is None or fi in self.claimed or fi in self.emitted:
                continue
            fa = self.atoms[fi]
            if fa.virtual is not None or fa.prim.name != "sub":
                continue
            a_o, mu_o = fa.operands
            if not self.valueable(a_o) or self._shape_of(a_o) != shape:
                continue
            mu_claim: list[int] = []
            got_mu = self._mean_terminal(mu_o, fi, mu_claim, features)
            if got_mu is None or got_mu[0] != self.resolve(a_o):
                continue
            # f feeds exactly the square and the root mul
            f_cons = set(self.consumers.get(self.resolve(f_o), []))
            if not f_cons.issubset(set(claim) | {ri}):
                continue
            xv = self.ensure_value(a_o)
            self.claimed.update(claim + mu_claim + [fi, ri])
            self._emit_for(out, ir.OpNode(
                ir.OpKind.ROW_NORM, self._op_name("layernorm"), (xv,),
                self._fresh_value(),
                attrs={"norm": "layer", "eps": eps}))
            return True
        return False

    # -- single-atom rules -------------------------------------------------

    def _try_single(self, ri: int) -> bool:
        a = self.atoms[ri]
        if a.virtual == "unary":
            x = self.ensure_value(a.operands[0])
            self._emit_for(a.out_ids[0], ir.OpNode(
                ir.OpKind.EW_UNARY, self._op_name(a.fn_name), (x,),
                self._fresh_value(), fn=a.fn_name))
            return True
        if a.virtual == "row_softmax":
            x = self.ensure_value(a.operands[0])
            self._emit_for(a.out_ids[0], ir.OpNode(
                ir.OpKind.ROW_SOFTMAX, self._op_name("softmax"), (x,),
                self._fresh_value()))
            return True
        name = a.prim.name
        if name in _BINARY_PRIMS and len(a.out_ids) == 1:
            if self._try_binary(ri):
                return True
        if name in _SINGLE_UNARY and len(a.out_ids) == 1:
            x_o = self.resolve(a.operands[0])
            if self.valueable(x_o) and _liftable(self._shape_of(x_o)):
                x = self.ensure_value(x_o)
                fn = _SINGLE_UNARY[name]
                self._emit_for(a.out_ids[0], ir.OpNode(
                    ir.OpKind.EW_UNARY, self._op_name(fn), (x,),
                    self._fresh_value(), fn=fn))
                return True
        if name == "integer_pow" and a.params.get("y") == 2:
            x_o = self.resolve(a.operands[0])
            if self.valueable(x_o) and _liftable(self._shape_of(x_o)):
                x = self.ensure_value(x_o)
                self._emit_for(a.out_ids[0], ir.OpNode(
                    ir.OpKind.EW_UNARY, self._op_name("square"), (x,),
                    self._fresh_value(), fn="square"))
                return True
        if name == "reduce_window_max":
            geom = self._pool_geometry(a)
            if (geom is not None and self.valueable(a.operands[0])
                    and _liftable(self._shape_of(a.operands[0]))):
                self._emit_pool(a.out_ids[0], a.operands[0], "max", geom)
                return True
        if name == "dot_general":
            return self._try_matmul(ri)
        if name == "conv_general_dilated":
            return self._try_conv(ri)
        return False

    def _try_binary(self, ri: int) -> bool:
        a = self.atoms[ri]
        fn = a.prim.name
        x_o, y_o = (self.resolve(o) for o in a.operands)
        out = a.out_ids[0]
        out_shape = tuple(self.avals[out].shape)
        if not _liftable(out_shape):           # 0-d/empty: keep opaque
            return False
        # value (op) value — identical shapes keep rows tiling uniform
        if (self.valueable(x_o) and self.valueable(y_o)
                and self._shape_of(x_o) == self._shape_of(y_o) == out_shape):
            vx, vy = self.ensure_value(x_o), self.ensure_value(y_o)
            self._emit_for(out, ir.OpNode(
                ir.OpKind.EW_BINARY, self._op_name(fn), (vx, vy),
                self._fresh_value(), fn=fn))
            return True
        # value (op) param
        if self.valueable(x_o) and self._shape_of(x_o) == out_shape:
            p = self.as_param(y_o)
            if p is not None:
                vx = self.ensure_value(x_o)
                self._emit_for(out, ir.OpNode(
                    ir.OpKind.EW_BINARY, self._op_name(fn), (vx,),
                    self._fresh_value(), fn=fn, params=(p,)))
                return True
        # param (op) value — commutative only (apply_op puts the param second)
        if (fn in _COMMUTATIVE and self.valueable(y_o)
                and self._shape_of(y_o) == out_shape):
            p = self.as_param(x_o)
            if p is not None:
                vy = self.ensure_value(y_o)
                self._emit_for(out, ir.OpNode(
                    ir.OpKind.EW_BINARY, self._op_name(fn), (vy,),
                    self._fresh_value(), fn=fn, params=(p,)))
                return True
        return False

    def _weight_param(self, o) -> str | None:
        """Param name for a weight operand (any shape, unlike as_param)."""
        o = self.resolve(o)
        if isinstance(o, _Const):
            return self._const_param(o.val)
        if isinstance(o, int) and o in self.leaf_index:
            return f"arg{self.leaf_index[o]}"
        if isinstance(o, int) and o in self.alias:
            al = self.alias[o]
            if al.src_shape == al.tgt_shape:
                return al.pname
        return None

    def _try_matmul(self, ri: int) -> bool:
        a = self.atoms[ri]
        x_o, w_o = (self.resolve(o) for o in a.operands)
        if not self.valueable(x_o):
            return False
        x_shape = self._shape_of(x_o)
        dims = a.params.get("dimension_numbers")
        try:
            (lc, rc), (lb, rb) = dims
        except (TypeError, ValueError):
            return False
        if (tuple(lc), tuple(rc)) != ((len(x_shape) - 1,), (0,)):
            return False
        if tuple(lb) or tuple(rb):
            return False
        pe = a.params.get("preferred_element_type")
        if pe is not None and np.dtype(pe) != np.dtype(self._dtype_of(x_o)):
            return False
        w_shape = self._shape_of(w_o)
        if len(w_shape) != 2:
            return False
        wp = self._weight_param(w_o)
        if wp is None:
            return False
        x = self.ensure_value(x_o)
        self._emit_for(a.out_ids[0], ir.OpNode(
            ir.OpKind.MATMUL, self._op_name("matmul"), (x,),
            self._fresh_value(), params=(wp,),
            attrs={"features_out": w_shape[-1]}))
        return True

    _NHWC_SPECS = ((0, 3, 1, 2), (3, 2, 0, 1), (0, 3, 1, 2))

    def _try_conv(self, ri: int) -> bool:
        a = self.atoms[ri]
        x_o, w_o = (self.resolve(o) for o in a.operands)
        if not self.valueable(x_o):
            return False
        p = a.params
        dn = p.get("dimension_numbers")
        specs = (tuple(getattr(dn, "lhs_spec", ())),
                 tuple(getattr(dn, "rhs_spec", ())),
                 tuple(getattr(dn, "out_spec", ())))
        if specs != self._NHWC_SPECS:
            return False
        if (p.get("feature_group_count", 1) != 1
                or p.get("batch_group_count", 1) != 1):
            return False
        if (tuple(p.get("lhs_dilation", (1, 1))) != (1, 1)
                or tuple(p.get("rhs_dilation", (1, 1))) != (1, 1)):
            return False
        pad = tuple(tuple(q) for q in p.get("padding", ()))
        if len(pad) != 2 or pad[0][0] != pad[0][1] or pad[1][0] != pad[1][1]:
            return False
        pe = p.get("preferred_element_type")
        if pe is not None and np.dtype(pe) != np.dtype(self._dtype_of(x_o)):
            return False
        w_shape = self._shape_of(w_o)
        if len(w_shape) != 4:
            return False
        wp = self._weight_param(w_o)
        if wp is None:
            return False
        x = self.ensure_value(x_o)
        self._emit_for(a.out_ids[0], ir.OpNode(
            ir.OpKind.CONV2D, self._op_name("conv"), (x,),
            self._fresh_value(), params=(wp,),
            attrs={"kernel_shape": w_shape,
                   "stride": tuple(p.get("window_strides", (1, 1))),
                   "padding": (pad[0][0], pad[1][0])}))
        return True

    def _try_broadcast(self, ri: int) -> bool:
        a = self.atoms[ri]
        o = self.resolve(a.operands[0])
        out = a.out_ids[0]
        tgt = tuple(self.avals[out].shape)
        bdims = tuple(a.params.get("broadcast_dimensions", ()))
        src_shape = self._shape_of(o)
        trailing = tuple(range(len(tgt) - len(src_shape), len(tgt)))
        scalar = int(np.prod(src_shape or (1,))) == 1
        aligned = (bdims == trailing
                   and all(d == 1 for d in tgt[:len(tgt) - len(src_shape)]))
        if not (scalar or aligned):
            return False                           # fragment fallback
        dtype = self.avals[out].dtype
        if isinstance(o, _Const):
            pname = self._const_param(o.val)
            self.alias[out] = _Alias(pname, src_shape, tgt, dtype)
            return True
        if isinstance(o, int) and o in self.leaf_index:
            self.alias[out] = _Alias(f"arg{self.leaf_index[o]}", src_shape,
                                     tgt, dtype)
            return True
        if isinstance(o, int) and o in self.alias:
            al = self.alias[o]
            if al.src_shape == al.tgt_shape:
                self.alias[out] = _Alias(al.pname, al.src_shape, tgt, dtype)
                return True
        return False                               # value operand: fragment

    def _try_convert(self, ri: int) -> bool:
        a = self.atoms[ri]
        o = self.resolve(a.operands[0])
        out = a.out_ids[0]
        out_aval = self.avals[out]
        new_dtype = out_aval.dtype
        out_weak = bool(getattr(out_aval, "weak_type", False))
        if isinstance(o, _Const):
            if out_weak:       # materialized consts are strong: keep fragment
                return False
            self.redirect[out] = _Const(np.asarray(o.val).astype(new_dtype))
            return True
        # a same-dtype convert can still be a weak_type normalization, which
        # changes promotion of downstream user code — redirect only when the
        # operand's aval is observably identical
        if (self._dtype_of(o) == new_dtype
                and bool(getattr(self.avals[o], "weak_type", False))
                == out_weak):
            self.redirect[out] = o
            return True
        return False                               # real cast: fragment

    # -- OPAQUE fragment fallback ------------------------------------------

    def _emit_opaque(self, ri: int) -> None:
        a = self.atoms[ri]
        slots: list[tuple] = []
        in_names: list[str] = []
        p_names: list[str] = []
        for o in a.operands:
            o = self.resolve(o)
            v = self.as_value(o)
            if v is not None:
                slots.append(("in", len(in_names)))
                in_names.append(v)
                continue
            if isinstance(o, _Const):
                slots.append(("const", jnp.asarray(o.val)))
                continue
            if isinstance(o, int) and o in self.leaf_index:
                slots.append(("p", len(p_names), None))
                p_names.append(f"arg{self.leaf_index[o]}")
                continue
            if isinstance(o, int) and o in self.alias:
                al = self.alias[o]
                slots.append(("p", len(p_names), (al.tgt_shape, al.dtype)))
                p_names.append(al.pname)
                continue
            raise AssertionError(f"unresolvable operand {o!r}")

        prim, params = a.prim, dict(a.params)
        n_in = len(in_names)

        # Registry-facing metadata: the kernel-registry matchers
        # (repro.core.registry) pattern-match OPAQUE clusters by primitive
        # name / params and need each operand's identity back, which the
        # executable closure otherwise hides.
        named_slots: list[tuple] = []
        for slot in slots:
            if slot[0] == "in":
                named_slots.append(("in", in_names[slot[1]]))
            elif slot[0] == "const":
                named_slots.append(("const", slot[1]))
            else:
                named_slots.append(("p", p_names[slot[1]], slot[2]))
        reg_attrs = {"prim": prim.name, "prim_params": params,
                     "operand_slots": tuple(named_slots)}

        def opaque_fn(*args, _prim=prim, _params=params, _slots=tuple(slots),
                      _n_in=n_in):
            ins, ps = args[:_n_in], args[_n_in:]
            subfuns, bind_params = _prim.get_bind_params(dict(_params))
            ordered = []
            for slot in _slots:
                if slot[0] == "in":
                    ordered.append(ins[slot[1]])
                elif slot[0] == "const":
                    ordered.append(slot[1])
                else:
                    v = ps[slot[1]]
                    if slot[2] is not None:
                        shape, dtype = slot[2]
                        v = jnp.broadcast_to(jnp.asarray(v),
                                             shape).astype(dtype)
                    ordered.append(v)
            return _prim.bind(*subfuns, *ordered, **bind_params)

        if not prim.multiple_results:
            out_id = a.out_ids[0]
            self._emit_for(out_id, ir.OpNode(
                ir.OpKind.OPAQUE, self._op_name(prim.name), tuple(in_names),
                self._fresh_value(), params=tuple(p_names),
                attrs={"fn": opaque_fn,
                       "out_shape": tuple(self.avals[out_id].shape),
                       **reg_attrs}))
            return
        # multi-result primitive: one holder op + one projection per result.
        # The holder's runtime value is a *tuple* of all results; its
        # recorded shape only feeds byte accounting (resource traffic
        # models), so charge the summed element count across results.
        holder = self._fresh_value("t")
        holder_shape = (sum(int(math.prod(tuple(self.avals[oid].shape)))
                            for oid in a.out_ids),)
        self._append(ir.OpNode(
            ir.OpKind.OPAQUE, self._op_name(prim.name), tuple(in_names),
            holder, params=tuple(p_names),
            attrs={"fn": opaque_fn, "out_shape": holder_shape}),
            holder, holder_shape)
        for k, oid in enumerate(a.out_ids):
            if not self.consumers.get(oid):
                continue
            self._emit_for(oid, ir.OpNode(
                ir.OpKind.OPAQUE, self._op_name("proj"), (holder,),
                self._fresh_value(),
                attrs={"fn": (lambda t, _k=k: t[_k]),
                       "out_shape": tuple(self.avals[oid].shape),
                       "synthetic": True}))

    # -- main loop ---------------------------------------------------------

    _ROOT_PRIMS = frozenset({"mul", "add", "div"})
    _SCAN_BOUND = 24          # forward-BFS node budget per trigger atom

    def _try_structural(self, ri: int) -> bool:
        """Trigger the backward-rooted pattern matchers *early*.

        Structural idioms (affine / row norms / softmax / avgpool) are
        rooted at their last atom, but by the time the emission loop
        reaches that root its interior atoms would already have been
        emitted individually.  So at every atom we BFS forward through the
        consumer graph (bounded) for candidate roots and run the matchers
        there; a successful match claims the whole idiom — including this
        trigger atom — and emits the fused op at the trigger's position
        (valid: all pattern inputs are defined before the first interior).
        """
        seen = {ri}
        frontier = [ri]
        roots: list[int] = []
        a0 = self.atoms[ri]
        if (a0.virtual is None and len(a0.out_ids) == 1
                and a0.prim.name in self._ROOT_PRIMS):
            roots.append(ri)
        while frontier and len(seen) < self._SCAN_BOUND:
            nxt: list[int] = []
            for i in frontier:
                for o in self.atoms[i].out_ids:
                    for j in self.consumers.get(o, []):
                        if j == -1 or j in seen:
                            continue
                        seen.add(j)
                        nxt.append(j)
                        b = self.atoms[j]
                        if (b.virtual is None and len(b.out_ids) == 1
                                and b.prim.name in self._ROOT_PRIMS):
                            roots.append(j)
            frontier = nxt
        for j in sorted(roots):
            if j in self.claimed:
                continue
            name = self.atoms[j].prim.name
            if ((name == "mul" and self._try_row_norm(j))
                    or (name == "add" and self._try_affine(j))
                    or (name == "div" and (self._try_avgpool(j)
                                           or self._try_softmax(j)))):
                if ri in self.claimed:
                    return True
        return ri in self.claimed

    def build(self) -> None:
        for ri, a in enumerate(self.atoms):
            if ri in self.claimed:
                continue
            if self._try_structural(ri):
                continue
            if self._try_chain_probe(ri):
                continue
            if self._try_single(ri):
                self.emitted.add(ri)
                continue
            self._emit_opaque(ri)
            self.emitted.add(ri)


# ---------------------------------------------------------------------------
# Public entry point.
# ---------------------------------------------------------------------------

def trace(fn: Callable, *example_args) -> TraceResult:
    """Stage ``fn`` on ``example_args`` and lift its jaxpr into a NetGraph.

    ``example_args`` may be any pytree of arrays (as for ``jax.jit``); the
    traced graph is specialized to their shapes/dtypes.  Tracing never
    fails on unrecognized primitives — they become OPAQUE ops.
    """
    leaves, in_tree = jax.tree_util.tree_flatten(example_args)
    if not leaves:
        raise ValueError("trace() needs at least one array argument")
    leaves = [jnp.asarray(leaf) for leaf in leaves]
    store: dict[str, Any] = {}

    def flat_fn(*flat):
        args = jax.tree_util.tree_unflatten(in_tree, flat)
        out = fn(*args)
        out_leaves, out_tree = jax.tree_util.tree_flatten(out)
        store["out_tree"] = out_tree
        return tuple(out_leaves)

    closed = jax.make_jaxpr(flat_fn)(*leaves)
    ctx = _FlattenCtx()
    leaf_ids = [ctx.fresh(v.aval) for v in closed.jaxpr.invars]
    out_operands = _flatten(closed, list(leaf_ids), ctx)

    builder = _Builder(ctx, leaf_ids, out_operands)
    builder.build()

    out_refs: list[tuple] = []
    for o in out_operands:
        o = builder.resolve(o)
        if isinstance(o, _Const):
            out_refs.append(("const", jnp.asarray(o.val)))
        elif o in builder.val_name:
            out_refs.append(("env", builder.val_name[o]))
        elif o in builder.leaf_index:
            out_refs.append(("leaf", builder.leaf_index[o]))
        elif o in builder.alias:
            out_refs.append(("env", builder.ensure_value(o)))
        else:                                     # pragma: no cover
            raise AssertionError(f"unresolved traced output {o!r}")

    out_name = next((ref for kind, ref in out_refs if kind == "env"), "arg0")
    name = getattr(fn, "__name__", None) or "traced"
    # dead-value pruning: fn bodies that compute-and-discard (debug
    # probes, tuple returns partially consumed, speculative matcher
    # residue) leave ops whose outputs nothing consumes.  Iterate to a
    # fixpoint — pruning one op can orphan its producers.
    keep = {ref for kind, ref in out_refs if kind == "env"}
    ops = list(builder.ops)
    while True:
        consumed = {v for op in ops for v in op.inputs}
        live = [op for op in ops
                if op.output in consumed or op.output in keep]
        if len(live) == len(ops):
            break
        ops = live
    graph = ir.NetGraph(name=f"traced_{name}", input="arg0",
                        output=out_name, ops=tuple(ops))
    # drop const params no committed op references — matchers register
    # them speculatively (as_param inside an attempt that then fails), and
    # an orphan would ride the params dict of every optimized call
    used = {p for op in ops for p in op.params}
    const_params = {k: v for k, v in builder.const_params.items()
                    if k in used}
    param_shapes = {k: v for k, v in builder.param_shapes.items()
                    if k not in builder.const_params or k in used}
    return TraceResult(
        graph=graph, shapes=builder.shapes, dtypes=builder.dtypes,
        param_shapes=param_shapes,
        const_params=const_params, n_leaves=len(leaves),
        leaf_avals=tuple((tuple(v.aval.shape), np.dtype(v.aval.dtype))
                         for v in closed.jaxpr.invars),
        in_tree=in_tree, out_tree=store["out_tree"],
        out_refs=tuple(out_refs), input_name="arg0",
        n_atoms=len(ctx.atoms))
