"""Kernel registry — traced backbone regions dispatch to dedicated kernels.

The depth-first stack machinery absorbs elementwise / norm / pool chains,
but the repo also carries hand-tuned pallas kernels for whole backbone
*regions* a stack cannot express: flash attention (``softmax(qk^T·s)·v``),
fused RMSNorm, the SwiGLU gate and the vocab cross-entropy head.  Before
this module those kernels were only reachable from the hand-built
``models/lm.py`` path; the traced frontend replayed the same regions as
OPAQUE ``prim.bind`` soup.

This registry sits between the tracer and codegen: a table of structural
matchers (same dataflow-rule style as ``core/trace.py``) walks the traced
:class:`~repro.core.ir.NetGraph`, recognizes those regions and replaces
each matched cluster with one ``OpKind.KERNEL`` op that codegen dispatches
to the corresponding ``kernels/*/ops.py`` entry point.  Following the
PALLAS/XLA ``KernelType`` idiom, every entry has two backends:

* :attr:`KernelType.PALLAS` — the dedicated pallas kernel (mode
  ``brainslug``; the kernels' existing ``custom_vjp`` keeps
  ``differentiable=True`` intact), and
* :attr:`KernelType.REF` — the ``ref.py`` jnp twin, used automatically
  when pallas constraints are violated (recorded in ``report()`` — a
  fallback must never be invisible) or when the mode is ``xla`` /
  ``barrier``; plain jnp, so ``jax.vjp`` differentiates it natively.

Entries whose cluster the depth-first stacks could absorb instead
(rmsnorm / swiglu) are only claimed when the pallas kernel will actually
run — otherwise the REF "fallback" would *deoptimize* them relative to
the stack capture they had; attention / vocab-CE clusters are OPAQUE
``prim.bind`` soup either way, so their ref twin is never a regression.

Every structural match is additionally **probe-verified**: the claimed
cluster is executed (forward *and* vjp, non-uniform cotangent) on random
inputs of the traced shapes and compared against the entry's ref twin.
A user ``stop_gradient`` / custom-derivative fence anywhere inside the
cluster fails the gradient probe and vetoes the rewrite — the same
fence discipline the tracer's behavioral probes enforce for unary calls.
"""
from __future__ import annotations

import dataclasses
import enum
import math
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ir
from repro.kernels.attention import ops as attn_ops
from repro.kernels.attention import ref as attn_ref
from repro.obs import DispatchStats
from repro.kernels.rmsnorm import ops as rms_ops
from repro.kernels.rmsnorm import ref as rms_ref
from repro.kernels.swiglu import ops as swiglu_ops
from repro.kernels.swiglu import ref as swiglu_ref
from repro.kernels.vocab_ce import ops as ce_ops
from repro.kernels.vocab_ce import ref as ce_ref

__all__ = ["KernelType", "KernelDispatch", "KernelEntry", "KernelMatch",
           "REGISTRY", "STATS", "rewrite", "plan_dispatch"]


class KernelType(enum.Enum):
    """Which backend a KERNEL op runs — the mamba-jax interface idiom."""

    PALLAS = "pallas"
    REF = "ref"


@dataclasses.dataclass(frozen=True)
class KernelDispatch:
    """The compile-time backend decision for one KERNEL op (surfaced by
    ``report()`` so a ref fallback is never silent)."""

    kernel: str
    backend: KernelType
    reason: str | None = None      # why REF ran (constraint / mode), else None


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    """One registry row: the two backends plus dispatch policy.

    ``pallas(args, attrs)`` and ``ref(args, attrs)`` take the
    operand arrays in the slot order the matcher recorded.  ``constraints``
    returns a human-readable reason string when the pallas kernel cannot
    take these shapes (-> REF fallback), or None.  ``vjp`` declares where
    the backward comes from and codegen dispatches on it: ``'custom'``
    means the pallas entry point already carries a ``jax.custom_vjp``
    (all four current entries), ``'ref'`` makes codegen wrap the pallas
    forward with :func:`repro.core.autodiff.with_ref_vjp` so ``jax.grad``
    recomputes through the jnp twin.
    """

    name: str
    pallas: Callable[[list, Mapping], jnp.ndarray]
    ref: Callable[[list, Mapping], jnp.ndarray]
    constraints: Callable[[tuple, Mapping], str | None]
    vjp: str = "custom"
    #: True when the depth-first stack machinery could absorb the cluster
    #: instead (rmsnorm / swiglu are ROW_NORM / EW chains).  Such clusters
    #: are only claimed when the pallas kernel will actually run — a REF
    #: fallback would *deoptimize* them relative to the stack capture they
    #: had before, whereas attention / vocab-CE clusters are OPAQUE soup
    #: either way and the ref twin is never worse.
    stack_absorbable: bool = False


@dataclasses.dataclass(frozen=True)
class KernelMatch:
    """One successful rewrite: which ops were claimed, what replaced them."""

    kernel: str
    root: int
    claimed: tuple[int, ...]
    op: ir.OpNode


# ---------------------------------------------------------------------------
# Registry entries.
# ---------------------------------------------------------------------------

def _as_bhsd(x: jnp.ndarray) -> jnp.ndarray:
    """Lift a (B, S, D) single-head operand to the kernels' (B, H, S, D)."""
    return x if x.ndim == 4 else x[:, None]


def _attention_pallas(args: list, attrs: Mapping):
    q, k, v = args
    out = attn_ops.flash_attention(
        _as_bhsd(q), _as_bhsd(k), _as_bhsd(v), attrs["causal"], 128, 128,
        attrs["scale"])
    return out[:, 0] if q.ndim == 3 else out


def _attention_ref(args: list, attrs: Mapping):
    q, k, v = args
    out = attn_ref.attention_ref(
        _as_bhsd(q), _as_bhsd(k), _as_bhsd(v), causal=attrs["causal"],
        scale=attrs["scale"])
    return out[:, 0] if q.ndim == 3 else out


def _attention_constraints(arg_shapes: tuple, attrs: Mapping) -> str | None:
    d = arg_shapes[0][-1]
    if d < 8 or d % 8:
        return f"head_dim {d} is not a positive multiple of the lane width 8"
    return None


def _rmsnorm_pallas(args: list, attrs: Mapping):
    x, g = args
    return rms_ops.rmsnorm_value(x, jnp.reshape(g, (-1,)), eps=attrs["eps"])


def _rmsnorm_ref(args: list, attrs: Mapping):
    x, g = args
    return rms_ref.rmsnorm_ref(x, jnp.reshape(g, (-1,)), None,
                               eps=attrs["eps"])[0]


def _rmsnorm_constraints(arg_shapes: tuple, attrs: Mapping) -> str | None:
    d = arg_shapes[0][-1]
    if d < 8 or d % 8:
        return f"features {d} is not a positive multiple of the lane width 8"
    return None


def _swiglu_pallas(args: list, attrs: Mapping):
    return swiglu_ops.swiglu(args[0], args[1], attrs["act"], 256)


def _swiglu_ref(args: list, attrs: Mapping):
    return swiglu_ref.swiglu_ref(args[0], args[1], act=attrs["act"])


def _swiglu_constraints(arg_shapes: tuple, attrs: Mapping) -> str | None:
    f = arg_shapes[0][-1]
    if f < 8 or f % 8:
        return f"features {f} is not a positive multiple of the lane width 8"
    return None


def _vocab_ce_pallas(args: list, attrs: Mapping):
    h, w, labels = args
    return ce_ops.fused_gold_logp(h, w, jnp.reshape(labels, (-1,)),
                                  128, 512, 512)


def _vocab_ce_ref(args: list, attrs: Mapping):
    h, w, labels = args
    return ce_ref.gold_logp_ref(h, w, jnp.reshape(labels, (-1,)))


def _vocab_ce_constraints(arg_shapes: tuple, attrs: Mapping) -> str | None:
    return None                    # the CE kernel pads every axis itself


REGISTRY: dict[str, KernelEntry] = {
    "attention": KernelEntry(
        name="attention", pallas=_attention_pallas, ref=_attention_ref,
        constraints=_attention_constraints, vjp="custom"),
    "rmsnorm": KernelEntry(
        name="rmsnorm", pallas=_rmsnorm_pallas, ref=_rmsnorm_ref,
        constraints=_rmsnorm_constraints, vjp="custom",
        stack_absorbable=True),
    "swiglu": KernelEntry(
        name="swiglu", pallas=_swiglu_pallas, ref=_swiglu_ref,
        constraints=_swiglu_constraints, vjp="custom",
        stack_absorbable=True),
    "vocab_ce": KernelEntry(
        name="vocab_ce", pallas=_vocab_ce_pallas, ref=_vocab_ce_ref,
        constraints=_vocab_ce_constraints, vjp="custom"),
}

#: Runtime dispatch counters (same snapshot/delta protocol as the
#: fused-stack STATS; reset together by ``codegen.clear_cache``).
STATS = DispatchStats(keys=tuple(
    f"{name}_{bk.value}" for name in REGISTRY for bk in KernelType))


def expected_out_shape(kernel: str, arg_shapes: tuple) -> tuple | None:
    """Each kernel's output-aval contract, re-derived from its argument
    avals — the static verifier's independent check on a KERNEL op's
    recorded ``out_shape``.  ``None`` means the contract fixes only the
    element count, not the exact shape (vocab_ce emits one loss per
    gathered index; the traced gather decides the layout)."""
    if kernel in ("rmsnorm", "swiglu") and arg_shapes:
        return tuple(arg_shapes[0])
    if kernel == "attention" and len(arg_shapes) == 3:
        # softmax(q·kᵀ)·v: q's leading/sequence dims, v's head dim.
        return tuple(arg_shapes[0][:-1]) + (arg_shapes[2][-1],)
    return None


def get(name: str) -> KernelEntry:
    return REGISTRY[name]


def plan_dispatch(op: ir.OpNode, mode: str) -> KernelDispatch:
    """The compile-time backend decision for one KERNEL op."""
    entry = REGISTRY[op.attrs["kernel"]]
    if mode != "brainslug":
        return KernelDispatch(entry.name, KernelType.REF,
                              f"mode={mode} uses the jnp twin")
    reason = entry.constraints(op.attrs["arg_shapes"], op.attrs)
    if reason is not None:
        return KernelDispatch(entry.name, KernelType.REF, reason)
    return KernelDispatch(entry.name, KernelType.PALLAS, None)


# ---------------------------------------------------------------------------
# Matching context over a traced NetGraph.
# ---------------------------------------------------------------------------

class _Ctx:
    def __init__(self, tr, mode: str = "brainslug") -> None:
        self.tr = tr
        self.mode = mode
        self.ops: list[ir.OpNode] = list(tr.graph.ops)
        self.shapes = tr.shapes
        self.dtypes = tr.dtypes
        self.param_shapes = tr.param_shapes
        self.const_params = tr.const_params
        self.leaf_avals = tr.leaf_avals
        self.claimed: set[int] = set()
        self.producer: dict[str, int] = {}
        self.consumers: dict[str, set[int]] = {}
        for i, op in enumerate(self.ops):
            self.producer[op.output] = i
            for v in op.inputs:
                self.consumers.setdefault(v, set()).add(i)
        #: values that must survive the rewrite (traced outputs)
        self.keep = frozenset(ref for kind, ref in tr.out_refs
                              if kind == "env")

    # -- aval helpers -------------------------------------------------------

    def value_aval(self, name: str) -> tuple[tuple[int, ...], Any]:
        return tuple(self.shapes[name]), self.dtypes.get(name, jnp.float32)

    def param_aval(self, pname: str) -> tuple[tuple[int, ...], Any] | None:
        if pname in self.const_params:
            arr = self.const_params[pname]
            return tuple(arr.shape), arr.dtype
        if pname.startswith("arg"):
            try:
                shape, dtype = self.leaf_avals[int(pname[3:])]
            except (ValueError, IndexError):
                return None
            return tuple(shape), dtype
        return None

    def slot_aval(self, slot: tuple) -> tuple[tuple[int, ...], Any] | None:
        if slot[0] == "in":
            return self.value_aval(slot[1])
        if slot[0] == "p":
            if len(slot) > 2 and slot[2] is not None:
                shape, dtype = slot[2]          # broadcast-alias view spec
                return tuple(shape), dtype
            return self.param_aval(slot[1])
        return None

    # -- dataflow walkers ---------------------------------------------------

    def sole_producer(self, name: str, from_idx: int
                      ) -> tuple[ir.OpNode, int] | None:
        """Producer of ``name`` when it is consumed *only* by ``from_idx``
        and is not a kept traced output (safe to absorb into a cluster)."""
        i = self.producer.get(name)
        if i is None or i in self.claimed:
            return None
        if self.consumers.get(name, set()) != {from_idx}:
            return None
        if name in self.keep:
            return None
        return self.ops[i], i

    def producer_op(self, name: str) -> ir.OpNode | None:
        i = self.producer.get(name)
        return None if i is None else self.ops[i]

    def const_subgraph(self, name: str, budget: int = 24
                       ) -> tuple[Any, set[int]] | None:
        """Evaluate ``name`` when it is a pure function of captured
        constants (e.g. an iota-built causal mask); returns (value, op
        index set) or None."""
        idxs: set[int] = set()
        stack = [name]
        while stack:
            n = stack.pop()
            i = self.producer.get(n)
            if i is None or i in self.claimed:
                return None
            if i in idxs:
                continue
            idxs.add(i)
            if len(idxs) > budget:
                return None
            op = self.ops[i]
            for p in op.params:
                if p not in self.const_params:
                    return None           # leaf-dependent: not a constant
            stack.extend(op.inputs)
        env: dict[str, jnp.ndarray] = {}
        try:
            for i in sorted(idxs):
                op = self.ops[i]
                env[op.output] = ir.apply_op(op, env, self.const_params)
        except Exception:
            return None
        return env[name], idxs

    def cluster_closed(self, claimed: set[int], root: int) -> bool:
        """No interior value of the cluster leaks: every non-root output is
        consumed only inside the cluster and is not a traced output."""
        for i in claimed:
            if i == root:
                continue
            out = self.ops[i].output
            if out in self.keep:
                return False
            if not self.consumers.get(out, set()) <= claimed:
                return False
        return True


def _opaque_prim(op: ir.OpNode) -> str | None:
    return op.attrs.get("prim") if op.kind == ir.OpKind.OPAQUE else None


def _dot_dims(op: ir.OpNode) -> tuple | None:
    try:
        (lc, rc), (lb, rb) = op.attrs["prim_params"]["dimension_numbers"]
        return tuple(lc), tuple(rc), tuple(lb), tuple(rb)
    except Exception:
        return None


def _causal_mask_kind(mask, sq: int, sk: int) -> str | None:
    """'causal' for a lower-triangular 0 / very-negative additive mask,
    'none' for an all-zero mask, None for anything else."""
    m = np.asarray(mask, np.float64)
    while m.ndim > 2 and m.shape[0] == 1:
        m = m[0]
    if m.ndim != 2 or m.shape != (sq, sk) or sq != sk:
        return None
    if np.all(m == 0.0):
        return "none"
    tril = np.tril_indices(sq)
    triu = np.triu_indices(sq, 1)
    if np.all(m[tril] == 0.0) and np.all(m[triu] <= -1e9):
        return "causal"
    return None


# ---------------------------------------------------------------------------
# Probe verification (forward + gradient).
# ---------------------------------------------------------------------------

def _cluster_fn(ctx: _Ctx, claimed: set[int], root_out: str,
                slots: tuple) -> Callable:
    cluster_ops = [ctx.ops[i] for i in sorted(claimed)]

    def f(*arrays):
        env: dict[str, jnp.ndarray] = {}
        params: dict[str, jnp.ndarray] = dict(ctx.const_params)
        for slot, a in zip(slots, arrays):
            if slot[0] == "in":
                env[slot[1]] = a
            else:
                params[slot[1]] = a
        for op in cluster_ops:
            env[op.output] = ir.apply_op(op, env, params)
        return env[root_out]

    return f


def _probe_verify(ctx: _Ctx, claimed: set[int], root_out: str,
                  slots: tuple, entry: KernelEntry, attrs: Mapping,
                  arrays: list[jnp.ndarray]) -> bool:
    """Does the claimed cluster compute (and differentiate) exactly what
    the registry entry's ref twin computes on these probe inputs?  The
    gradient probe uses a non-uniform cotangent so fences that only zero
    part of the backward cannot hide."""
    f = _cluster_fn(ctx, claimed, root_out, slots)
    try:
        got = f(*arrays)
        want = jnp.reshape(entry.ref(list(arrays), attrs), jnp.shape(got))
    except Exception:
        return False
    if not np.allclose(np.asarray(got, np.float64),
                       np.asarray(want, np.float64), rtol=1e-3, atol=1e-3):
        return False

    diff_idx = [i for i, a in enumerate(arrays)
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)]
    if not diff_idx:
        return True

    def fill(fargs):
        full = list(arrays)
        for i, a in zip(diff_idx, fargs):
            full[i] = a
        return full

    def f_d(*fargs):
        return f(*fill(fargs))

    def ref_d(*fargs):
        full = fill(fargs)
        return jnp.reshape(entry.ref(full, attrs), jnp.shape(got))

    fargs = [arrays[i] for i in diff_idx]
    ct = (jnp.linspace(0.5, 1.5, got.size, dtype=jnp.float32)
          .reshape(jnp.shape(got)).astype(got.dtype))
    try:
        _, vjp1 = jax.vjp(f_d, *fargs)
        _, vjp2 = jax.vjp(ref_d, *fargs)
        g1, g2 = vjp1(ct), vjp2(ct)
    except Exception:
        return False
    for a, b in zip(g1, g2):
        if not np.allclose(np.asarray(a, np.float64),
                           np.asarray(b, np.float64), rtol=5e-3, atol=5e-3):
            return False
    return True


def _rand_like(rng: np.random.Generator, aval: tuple) -> jnp.ndarray:
    shape, dtype = aval
    if jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return jnp.asarray(rng.standard_normal(shape), dtype)
    return jnp.asarray(rng.integers(0, 2, shape), dtype)


def _slot_arrays(ctx: _Ctx, rng: np.random.Generator, slots: tuple
                 ) -> list[jnp.ndarray] | None:
    arrays = []
    for slot in slots:
        aval = ctx.slot_aval(slot)
        if aval is None:
            return None
        arrays.append(_rand_like(rng, aval))
    return arrays


# ---------------------------------------------------------------------------
# Matchers.
# ---------------------------------------------------------------------------

def _kernel_op(ctx: _Ctx, kernel: str, root: int, claimed: set[int],
               slots: tuple, extra_attrs: dict) -> KernelMatch | None:
    root_op = ctx.ops[root]
    arg_shapes = []
    arg_dtypes = []
    for slot in slots:
        aval = ctx.slot_aval(slot)
        if aval is None:
            return None
        arg_shapes.append(aval[0])
        arg_dtypes.append(str(np.dtype(aval[1])))
    out_shape, out_dtype = ctx.value_aval(root_op.output)
    op = ir.OpNode(
        ir.OpKind.KERNEL, f"{kernel}[{root_op.name}]",
        tuple(s[1] for s in slots if s[0] == "in"), root_op.output,
        params=tuple(s[1] for s in slots if s[0] == "p"),
        attrs={"kernel": kernel, "slots": tuple(slots),
               "arg_shapes": tuple(arg_shapes),
               "arg_dtypes": tuple(arg_dtypes), "out_shape": out_shape,
               "out_dtype": out_dtype, **extra_attrs})
    return KernelMatch(kernel=kernel, root=root,
                       claimed=tuple(sorted(claimed)), op=op)


def _finish(ctx: _Ctx, kernel: str, root: int, claimed: set[int],
            slots: tuple, extra_attrs: dict) -> KernelMatch | None:
    if not ctx.cluster_closed(claimed, root):
        return None
    match = _kernel_op(ctx, kernel, root, claimed, slots, extra_attrs)
    if match is None:
        return None
    entry = REGISTRY[kernel]
    attrs = match.op.attrs
    if entry.stack_absorbable and (
            ctx.mode != "brainslug"
            or entry.constraints(attrs["arg_shapes"], attrs) is not None):
        # the pallas kernel will not run here; leave the cluster to the
        # depth-first stack machinery rather than deoptimize it to a
        # plain jnp ref call
        return None
    arrays = _slot_arrays(ctx, np.random.default_rng(0), slots)
    if arrays is None:
        return None
    if not _probe_verify(ctx, claimed, ctx.ops[root].output, slots,
                         entry, attrs, arrays):
        return None
    return match


def _match_attention(ctx: _Ctx, ri: int) -> KernelMatch | None:
    """``softmax(q·k^T [* scale] [+ causal mask]) · v`` -> flash attention.

    Rooted at the probabilities@values dot_general; the scale is an
    EW_BINARY mul by a captured scalar, the optional additive mask must be
    a constant subgraph with causal (lower-triangular 0 / -inf) structure.
    """
    root = ctx.ops[ri]
    if _opaque_prim(root) != "dot_general":
        return None
    rslots = root.attrs.get("operand_slots", ())
    if len(rslots) != 2 or rslots[0][0] != "in":
        return None
    out_shape = tuple(ctx.shapes[root.output])
    nd = len(out_shape)
    if nd not in (3, 4):
        return None
    bdims = tuple(range(nd - 2))
    dims = _dot_dims(root)
    if dims != ((nd - 1,), (nd - 2,), bdims, bdims):
        return None
    p_name, v_slot = rslots[0][1], rslots[1]

    got = ctx.sole_producer(p_name, ri)
    if got is None:
        return None
    sm, smi = got
    if sm.kind != ir.OpKind.ROW_SOFTMAX:
        return None
    claimed = {ri, smi}
    s_name = sm.inputs[0]
    from_idx = smi
    causal = False

    # optional additive mask: one side of an OPAQUE add is a constant
    # subgraph with causal structure
    got = ctx.sole_producer(s_name, from_idx)
    if got is not None and _opaque_prim(got[0]) == "add":
        add_op, addi = got
        aslots = add_op.attrs.get("operand_slots", ())
        if len(aslots) == 2:
            for a_slot, m_slot in (aslots, aslots[::-1]):
                if a_slot[0] != "in":
                    continue
                mask_val, midxs = None, set()
                if m_slot[0] == "in":
                    sub = ctx.const_subgraph(m_slot[1])
                    if sub is not None:
                        mask_val, midxs = sub
                elif m_slot[0] == "p" and m_slot[1] in ctx.const_params:
                    mask_val = ctx.const_params[m_slot[1]]
                elif m_slot[0] == "const":
                    mask_val = m_slot[1]
                if mask_val is None:
                    continue
                sq, sk = tuple(ctx.shapes[add_op.output])[-2:]
                kind = _causal_mask_kind(mask_val, sq, sk)
                if kind is None:
                    continue
                causal = kind == "causal"
                claimed |= {addi} | midxs
                s_name, from_idx = a_slot[1], addi
                break

    # optional scalar scale: EW_BINARY mul against a captured scalar const
    scale = 1.0
    got = ctx.sole_producer(s_name, from_idx)
    if (got is not None and got[0].kind == ir.OpKind.EW_BINARY
            and got[0].fn == "mul" and len(got[0].params) == 1
            and got[0].params[0] in ctx.const_params
            and ctx.const_params[got[0].params[0]].size == 1):
        mul_op, muli = got
        scale = float(np.asarray(
            ctx.const_params[mul_op.params[0]]).reshape(()))
        claimed.add(muli)
        s_name, from_idx = mul_op.inputs[0], muli

    got = ctx.sole_producer(s_name, from_idx)
    if got is None:
        return None
    qk, qki = got
    if _opaque_prim(qk) != "dot_general":
        return None
    qk_dims = _dot_dims(qk)
    if qk_dims != ((nd - 1,), (nd - 1,), bdims, bdims):
        return None
    qslots = qk.attrs.get("operand_slots", ())
    if len(qslots) != 2:
        return None
    claimed.add(qki)
    q_slot, k_slot = qslots
    if causal:
        sq, sk = tuple(ctx.shapes[qk.output])[-2:]
        if sq != sk:
            return None

    slots = (q_slot, k_slot, v_slot)
    return _finish(ctx, "attention", ri, claimed, slots,
                   {"causal": causal, "scale": scale})


def _match_vocab_ce(ctx: _Ctx, ri: int) -> KernelMatch | None:
    """``gather(log_softmax(h @ W), idx)`` loss tails -> fused vocab-CE.

    Rooted at the gather; the log-softmax side must be a dataflow-closed
    cluster over exactly one MATMUL(h, W).  The gather *index* value (the
    output of take_along_axis's normalization ops, one vocab index per
    row) becomes a kernel input — whatever transformation the user's code
    applied to the raw labels is preserved exactly.  The (T, V) logits
    never materialize.
    """
    root = ctx.ops[ri]
    if _opaque_prim(root) != "gather":
        return None
    rslots = root.attrs.get("operand_slots", ())
    if len(rslots) != 2 or rslots[0][0] != "in":
        return None
    idx_slot = rslots[1]
    if idx_slot[0] == "const":
        return None
    idx_aval = ctx.slot_aval(idx_slot)
    if idx_aval is None \
            or not jnp.issubdtype(jnp.dtype(idx_aval[1]), jnp.integer):
        return None

    # value side: walk back to exactly one MATMUL through const-only ops
    mm = None
    lse_set: set[int] = set()
    stack = [rslots[0][1]]
    while stack:
        n = stack.pop()
        i = ctx.producer.get(n)
        if i is None or i in ctx.claimed:
            return None
        if i in lse_set or i == mm:
            continue
        op = ctx.ops[i]
        if op.kind == ir.OpKind.MATMUL:
            if mm is not None and mm != i:
                return None
            mm = i
            continue
        lse_set.add(i)
        if len(lse_set) > 24:
            return None
        for p in op.params:
            if p not in ctx.const_params:
                return None
        stack.extend(op.inputs)
    if mm is None:
        return None
    mm_op = ctx.ops[mm]
    if len(mm_op.inputs) != 1 or len(mm_op.params) != 1:
        return None
    h, w = mm_op.inputs[0], mm_op.params[0]
    h_aval = ctx.value_aval(h)
    w_aval = ctx.param_aval(w)
    t = h_aval[0][0] if h_aval[0] else 0
    if (w_aval is None or len(h_aval[0]) != 2 or len(w_aval[0]) != 2
            or math.prod(idx_aval[0]) != t):
        return None

    claimed = lse_set | {mm, ri}
    if not ctx.cluster_closed(claimed, ri):
        return None
    slots = (("in", h), ("p", w, None), idx_slot)
    match = _kernel_op(ctx, "vocab_ce", ri, claimed, slots, {})
    if match is None:
        return None
    # probe with in-range indices (the claimed cluster receives the
    # already-normalized gather index, so [0, V) is its domain)
    rng = np.random.default_rng(0)
    v_dim = w_aval[0][1]
    arrays = [
        _rand_like(rng, h_aval),
        jnp.asarray(rng.standard_normal(w_aval[0]) * 0.3, w_aval[1]),
        jnp.asarray(rng.integers(0, v_dim, idx_aval[0]), idx_aval[1]),
    ]
    if not _probe_verify(ctx, claimed, ctx.ops[ri].output, slots,
                         REGISTRY["vocab_ce"], match.op.attrs, arrays):
        return None
    return match


def _match_swiglu(ctx: _Ctx, ri: int) -> KernelMatch | None:
    """``act(x·W1) * (x·W2)`` (the GLU MLP idiom) -> fused swiglu gate."""
    root = ctx.ops[ri]
    if (root.kind != ir.OpKind.EW_BINARY or root.fn != "mul"
            or root.params or len(root.inputs) != 2):
        return None
    for a, b in ((root.inputs), tuple(root.inputs)[::-1]):
        got = ctx.sole_producer(a, ri)
        if got is None:
            continue
        act, ai = got
        if act.kind != ir.OpKind.EW_UNARY or act.fn not in swiglu_ops.ACTS:
            continue
        gate = act.inputs[0]
        gate_p = ctx.producer_op(gate)
        up_p = ctx.producer_op(b)
        if (gate_p is None or gate_p.kind != ir.OpKind.MATMUL
                or up_p is None or up_p.kind != ir.OpKind.MATMUL):
            continue
        if ctx.shapes[gate] != ctx.shapes[b]:
            continue
        slots = (("in", gate), ("in", b))
        match = _finish(ctx, "swiglu", ri, {ri, ai}, slots, {"act": act.fn})
        if match is not None:
            return match
    return None


def _match_rmsnorm(ctx: _Ctx, ri: int) -> KernelMatch | None:
    """``rmsnorm(x) * g`` feeding a matmul -> fused rmsnorm kernel.

    Standalone norm chains stay in depth-first stacks (they fuse with
    their elementwise neighbors there); the registry only claims the
    norm-then-projection idiom whose downstream is a backbone matmul.
    """
    root = ctx.ops[ri]
    if (root.kind != ir.OpKind.EW_BINARY or root.fn != "mul"
            or len(root.params) != 1 or len(root.inputs) != 1):
        return None
    g = root.params[0]
    out_shape = tuple(ctx.shapes[root.output])
    d = out_shape[-1]
    g_aval = ctx.param_aval(g)
    if g_aval is None or g_aval[0][-1:] != (d,) \
            or math.prod(g_aval[0]) != d:
        return None
    got = ctx.sole_producer(root.inputs[0], ri)
    if got is None:
        return None
    norm, ni = got
    if norm.kind != ir.OpKind.ROW_NORM or norm.attrs.get("norm") != "rms":
        return None
    if not any(ctx.ops[c].kind == ir.OpKind.MATMUL
               for c in ctx.consumers.get(root.output, set())):
        return None
    x = norm.inputs[0]
    if tuple(ctx.shapes[x]) != out_shape:
        return None
    slots = (("in", x), ("p", g, None))
    return _finish(ctx, "rmsnorm", ri, {ri, ni}, slots,
                   {"eps": float(norm.attrs.get("eps", 1e-6))})


_MATCHERS: tuple[tuple[str, Callable], ...] = (
    ("attention", _match_attention),
    ("vocab_ce", _match_vocab_ce),
    ("swiglu", _match_swiglu),
    ("rmsnorm", _match_rmsnorm),
)


# ---------------------------------------------------------------------------
# The rewrite pass.
# ---------------------------------------------------------------------------

def rewrite(tr, *, mode: str = "brainslug"):
    """Replace matched OPAQUE backbone clusters in a
    :class:`~repro.core.trace.TraceResult` with KERNEL ops.

    Returns ``(new_trace_result, matches)``; with no matches the original
    TraceResult is returned unchanged.  Matching is conservative: a
    cluster is only claimed when it is dataflow-closed (no interior value
    escapes), its structural walk succeeds, *and* a forward+gradient probe
    against the entry's ref twin agrees — so a user gradient fence or an
    unexpected primitive convention vetoes the rewrite instead of
    silently changing semantics.  ``mode`` gates the stack-absorbable
    entries (rmsnorm / swiglu): outside ``brainslug`` — or when a pallas
    constraint fails — those clusters stay with the stack machinery.
    """
    ctx = _Ctx(tr, mode)
    matches: list[KernelMatch] = []
    for ri in range(len(ctx.ops)):
        if ri in ctx.claimed:
            continue
        for _, matcher in _MATCHERS:
            got = matcher(ctx, ri)
            if got is not None:
                matches.append(got)
                ctx.claimed |= set(got.claimed)
                break
    if not matches:
        return tr, ()
    root_ops = {m.root: m.op for m in matches}
    drop = set().union(*(set(m.claimed) for m in matches)) - set(root_ops)
    new_ops = []
    for i, op in enumerate(ctx.ops):
        if i in root_ops:
            new_ops.append(root_ops[i])
        elif i not in drop:
            new_ops.append(op)
    graph = ir.NetGraph(name=tr.graph.name, input=tr.graph.input,
                        output=tr.graph.output, ops=tuple(new_ops))
    return dataclasses.replace(tr, graph=graph), tuple(matches)
