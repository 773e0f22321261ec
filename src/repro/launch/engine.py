"""Continuous-batching serve engine: slot-managed KV cache, one jitted
mixed prefill/decode step.

The static driver (``launch/serve.py``) is breadth-first serving: a batch
marches in lock-step, every dispatch sweeps all slots, and finished
requests cycle pad tokens until the longest request stops.  This engine is
the depth-first counterpart at the *scheduler* level — the working set the
engine keeps resident is the set of live requests:

* **Slots.**  The KV/SSM cache has ``slots`` batch rows.  A request is
  admitted into a free slot, generates, and on completion the slot is
  reset (``lm.reset_slots``) and immediately refilled from the queue.
* **One compiled callable.**  Every dispatch runs the same jitted mixed
  step over a ``(slots, chunk)`` token window: a prefilling slot consumes
  up to ``chunk`` prompt tokens, a decoding slot consumes the one token it
  sampled last step, an empty slot rides along inert.  Per-slot ``active``
  masks (threaded through ``lm.decode_step`` down to the per-slot
  ``lengths`` operand of the flash-decode kernel) freeze the cache state
  of lanes that are not consuming a token, so mixed batches never corrupt
  each other — there is no separate prefill executable to compile or to
  serialize the pipeline on.
* **Per-request sampling state.**  Temperature, stop length and the RNG
  lane travel with the request, not the batch: request ``r`` samples its
  ``i``-th token with ``fold_in(fold_in(run_key, r.request_id), i)``, so a
  generation is reproducible regardless of which slot it landed in or what
  traffic it shared the batch with.

KV memory comes in two layouts (``RuntimeConfig.kv_layout``):

* ``"dense"`` — each slot owns a contiguous ``max_len`` reservation.
* ``"paged"`` — attention KV lives in a fixed pool of ``kv_block_size``-
  token physical blocks.  A host-side :class:`BlockAllocator` (free list +
  per-block refcounts) hands blocks out on demand; each slot's logical →
  physical mapping is a row of a block table that rides into the jitted
  step as an operand.  Admission is gated on *blocks*, not slots: a
  request is admitted only when its worst-case block need is covered by
  the free pool minus what live slots may still claim, so the pool can be
  sized well below ``slots * max_len`` and the engine degrades to queueing
  instead of corrupting memory.  Requests with a common token prefix map
  the *same* immutable blocks (:class:`PrefixCache`, content-hash chain);
  a shared block is copy-on-write — the write barrier forks it onto a
  fresh block (``lm.copy_blocks``) before any dispatch may write it.

**Scale-out and streaming.**  ``Engine(..., mesh=...)`` wraps the one
jitted mixed step in a shard_map region planned by
:func:`repro.core.partition.plan_decode_cache`: dense-layout slots shard
over the "data" axis (purely per-slot compute — bitwise identical to the
single-device step), attention heads over "model" (the out-projection
psums; see ``layers.attention``), and the paged pool never data-shards
(its scatter writes are shared across slots).  ``Engine.stream`` /
``Engine.run(on_token=...)`` surface :class:`TokenEvent`\\ s as the
scheduler tick commits tokens, so callers observe generations in commit
order instead of waiting for the run to drain.

Dispatch accounting lives in two places: ``STATS`` (a runtime-keyed
:class:`~repro.obs.DispatchStats`, snapshot/delta protocol) and the
per-run :class:`~repro.core.scheduler.ServeStats` returned via
:attr:`Engine.last_stats`.  The tick's host time is split into
``engine.*`` spans (:func:`repro.obs.span`): admission, then inside
``engine.tick`` the prepare, verify, upload, dispatch, sync and commit
phases.  No span is open across a ``yield``: the time a consumer spends
between events is its own.  :meth:`Engine.report` gives their delta over
the last run under ``"host_spans"``.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import heapq
import time
from typing import Any, Callable, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RuntimeConfig
from repro.core import partition as partition_mod
from repro.core import verify
from repro.core.scheduler import ServeStats
from repro.distributed import collectives
from repro.kernels.attention import ops as attn_ops
from repro.obs import SPANS, DispatchStats, span
from repro.models import lm

STATS = DispatchStats(keys=(
    "mixed_step",          # jitted mixed-step invocations
    "slot_reset",          # jitted slot-reset invocations
    "prefill_tokens",      # prompt tokens ingested by live slots
    "decode_slot_steps",   # slot-units of decode dispatch work
    "idle_slot_steps",     # lane-evaluation units that consumed no token
    "cow_fork",            # copy-on-write block forks (paged layout)
    "paged_blocks_live",   # paged: ceil(length / bs) per token-consuming
                           # lane and model evaluation (blocks attended)
    "paged_blocks_grid",   # paged: table width MB per such lane and
                           # evaluation (blocks the kernel's grid spans)
    "moe_routed_assignments",  # token x top_k expert assignments, over
                               # every MoE layer (host count)
    "moe_held_assignments",    # those that landed on the experts held
                               # here (cfg.expert_share; device count)
    "ssm_state_updates",   # Mamba layer x token-consuming lane evaluation
))


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request.  ``request_id`` seeds the RNG lane (reuse an
    id and you reuse its sample stream); ``max_new_tokens`` is the stop
    length; ``temperature <= 0`` is greedy.  ``deadline_ms`` bounds the
    queue wait: a request still waiting for a slot past its deadline
    completes with status ``'timeout'`` instead of holding its caller
    forever behind a long queue.  ``priority`` orders admission: higher
    pops first, ties fall back to submission order (FIFO).  ``on_token``
    is an optional per-request streaming callback: it fires with each of
    this request's :class:`TokenEvent`\\ s as the scheduler commits them
    (identity-only for hashing/eq — callbacks never change what a request
    *is*)."""
    request_id: int
    prompt: Sequence[int]
    max_new_tokens: int
    temperature: float = 0.0
    deadline_ms: float | None = None
    priority: int = 0
    on_token: Callable[["TokenEvent"], None] | None = dataclasses.field(
        default=None, compare=False, repr=False)


@dataclasses.dataclass(frozen=True)
class Completion:
    """``status`` is ``'ok'`` for a served generation; a request that
    failed validation (``'invalid'``), timed out in the queue
    (``'timeout'``), or hit a per-request error (``'error'``) still gets
    its Completion — one bad request never aborts the other slots'
    work.  ``reason`` carries the failure detail for non-ok statuses."""
    request_id: int
    prompt_len: int
    tokens: np.ndarray          # (max_new_tokens,) int32
    status: str = "ok"          # 'ok' | 'invalid' | 'timeout' | 'error'
    reason: str | None = None


@dataclasses.dataclass(frozen=True)
class TokenEvent:
    """One streamed serving event (``Engine.stream`` / ``run(on_token=)``).

    Token events (``done=False``) carry the ``index``-th generated token
    of their request, in commit order — the order the scheduler tick
    committed them, interleaved across whatever requests shared the
    batch.  The terminal event (``done=True``, ``token=None``) carries
    the request's :class:`Completion`; every request gets exactly one,
    including invalid / timed-out / errored requests (zero token events,
    then the terminal with the failure status)."""
    request_id: int
    token: int | None
    index: int
    done: bool = False
    completion: Completion | None = None


@dataclasses.dataclass
class _Slot:
    """Host-side per-slot request state."""
    idx: int                    # position in the submitted request list
    req: Request
    prompt: np.ndarray          # validated (P,) int32
    pos: int = 0                # prompt tokens consumed so far
    gen: list[int] = dataclasses.field(default_factory=list)
    last: int = 0               # decode input: the token sampled last step
    kv_len: int = 0             # KV positions written (both layouts)
    # paged-layout state
    blocks: list[int] = dataclasses.field(default_factory=list)
    reserve: int = 0            # worst-case blocks still claimable
    chain_key: bytes = b""      # prefix-hash chain after n_reg full blocks
    n_reg: int = 0              # prompt blocks registered with the cache


class BlockAllocator:
    """Host-side physical-block bookkeeping for the paged KV pool.

    A free list hands out block ids; per-block ``refcount`` counts the
    owners (slot tables + the prefix cache), ``filled`` the valid token
    positions (for the utilization metric).  ``release`` returns a block
    to the free list only when its last owner lets go — shared prefix
    blocks survive their writer."""

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.refcount = [0] * num_blocks
        self.filled = [0] * num_blocks
        # pop() hands out ascending ids
        self._free = list(range(num_blocks - 1, -1, -1))
        self.stored = 0             # sum(filled) over in-use blocks
        self.peak_in_use = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.num_blocks - len(self._free)

    def free_blocks(self) -> tuple[int, ...]:
        return tuple(self._free)

    def alloc(self) -> int:
        if not self._free:
            raise RuntimeError(
                "KV block pool exhausted — the admission reservation "
                "should have gated this request; this is an engine bug")
        b = self._free.pop()
        self.refcount[b] = 1
        self.filled[b] = 0
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return b

    def share(self, b: int) -> None:
        self.refcount[b] += 1

    def release(self, b: int) -> None:
        self.refcount[b] -= 1
        assert self.refcount[b] >= 0, f"double release of block {b}"
        if self.refcount[b] == 0:
            self.stored -= self.filled[b]
            self.filled[b] = 0
            self._free.append(b)

    def note_fill(self, b: int, upto: int) -> None:
        """Record that block ``b`` now holds ``upto`` valid tokens."""
        if upto > self.filled[b]:
            self.stored += upto - self.filled[b]
            self.filled[b] = upto

    def note_fork(self, src: int, dst: int) -> None:
        """``dst`` inherited ``src``'s contents via the device copy."""
        self.stored += self.filled[src] - self.filled[dst]
        self.filled[dst] = self.filled[src]


_CHAIN_ROOT = b"\x00" * 16


class PrefixCache:
    """Content-addressed map from token prefixes to immutable KV blocks.

    Keys are a hash chain: block ``i`` of a prompt is keyed by
    ``h(parent_key, tokens_i)``, so two prompts share exactly their common
    block-aligned prefix.  Full blocks are registered as soon as a slot's
    prefill completes them (their contents never change afterwards);
    the sub-block tail of a prompt is registered only when its request
    completes (tagged ``b"P"`` so a partial can never satisfy a full-block
    walk).  The cache holds one allocator reference per registered block;
    ``evict`` drops cache-only blocks (refcount 1) newest-first when
    admission runs short, and ``clear`` releases everything at run end.
    """

    def __init__(self, alloc: BlockAllocator):
        self.alloc = alloc
        self.bs = alloc.block_size
        self._full: dict[bytes, int] = {}
        self._partial: dict[bytes, tuple[int, int]] = {}   # key -> (blk, t)
        self._order: list[tuple[bytes, bool]] = []          # (key, partial)
        self.hits = 0

    @staticmethod
    def _h(parent: bytes, tokens: np.ndarray, tag: bytes = b"F") -> bytes:
        payload = parent + tag + np.asarray(tokens, np.int32).tobytes()
        return hashlib.sha256(payload).digest()[:16]

    def lookup(self, prompt: np.ndarray
               ) -> tuple[list[int], bytes, tuple[int, int] | None]:
        """Longest cached cover of ``prompt``: the full-block chain, the
        chain key after it, and an optional ``(block, t)`` partial tail."""
        key = _CHAIN_ROOT
        blocks: list[int] = []
        pos = 0
        while pos + self.bs <= len(prompt):
            nk = self._h(key, prompt[pos:pos + self.bs])
            blk = self._full.get(nk)
            if blk is None:
                break
            blocks.append(blk)
            key = nk
            pos += self.bs
        rem = len(prompt) - pos
        for t in range(min(rem, self.bs - 1), 0, -1):
            hit = self._partial.get(self._h(key, prompt[pos:pos + t], b"P"))
            if hit is not None:
                return blocks, key, hit
        return blocks, key, None

    def register_full(self, parent: bytes, tokens: np.ndarray,
                      block: int) -> bytes:
        nk = self._h(parent, tokens)
        if nk not in self._full:
            self.alloc.share(block)
            self._full[nk] = block
            self._order.append((nk, False))
        return nk

    def register_partial(self, parent: bytes, tokens: np.ndarray,
                         block: int) -> None:
        if len(tokens) == 0 or len(tokens) >= self.bs:
            return
        pk = self._h(parent, tokens, b"P")
        if pk not in self._partial:
            self.alloc.share(block)
            self._partial[pk] = (block, len(tokens))
            self._order.append((pk, True))

    def cached_blocks(self) -> tuple[int, ...]:
        return tuple([*self._full.values()]
                     + [b for b, _ in self._partial.values()])

    def evict(self, n_needed: int) -> int:
        """Free up to ``n_needed`` cache-only blocks (no live slot maps
        them).  Newest entries go first and partials before fulls — the
        long-lived interior of a popular prefix chain is the last thing
        to drop."""
        freed = 0
        for partial_pass in (True, False):
            for i in range(len(self._order) - 1, -1, -1):
                if freed >= n_needed:
                    return freed
                k, isp = self._order[i]
                if isp != partial_pass:
                    continue
                blk = self._partial[k][0] if isp else self._full[k]
                if self.alloc.refcount[blk] != 1:
                    continue        # a live slot still maps it
                self.alloc.release(blk)
                (self._partial if isp else self._full).pop(k)
                del self._order[i]
                freed += 1
        return freed

    def clear(self) -> None:
        for k, isp in self._order:
            self.alloc.release(self._partial[k][0] if isp
                               else self._full[k])
        self._full.clear()
        self._partial.clear()
        self._order.clear()


def _mixed_step_fn(cfg: ModelConfig, rt: RuntimeConfig,
                   count_experts: bool = False):
    """The raw mixed prefill/decode step for (cfg, rt) — what
    :func:`_jitted_mixed_step` jits directly and what a mesh-backed Engine
    wraps in its shard_map region first (with the head-localized config;
    see ``Engine._build_sharded_step``).  The paged variant takes the
    block tables as an extra operand — host-side mapping state, not cache
    state, so it is never donated.  ``count_experts`` adds a third
    output: the step's expert assignments on the held experts
    (``lm.decode_step(count_experts=True)``), summed over its
    evaluations."""
    vocab = cfg.vocab_size
    paged = rt.kv_layout == "paged"

    def mixed_step(params, cache, tables, tokens, counts, rids, tidx,
                   temps, base_key):
        """tokens (B, C); counts/rids/tidx (B,) i32; temps (B,) f32.

        Slot b consumes tokens[b, :counts[b]] (0 = idle lane); returns
        the token each slot samples from its last consumed position."""
        def body(t, carry):
            logits_last, cache = carry[:2]
            active = t < counts
            tok = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)
            out = lm.decode_step(params, cache, tok, cfg, rt, active,
                                 block_tables=tables,
                                 count_experts=count_experts)
            logits, cache = out[:2]
            logits_last = jnp.where(active[:, None],
                                    logits[:, 0].astype(jnp.float32),
                                    logits_last)
            if count_experts:
                return logits_last, cache, carry[2] + out[2]
            return logits_last, cache

        logits0 = jnp.zeros((tokens.shape[0], vocab), jnp.float32)
        init = (logits0, cache)
        if count_experts:
            init += (jnp.zeros((), jnp.int32),)
        # traced trip count (lowers to a while_loop): in decode-only
        # steady state max(counts) == 1, so the step does one model
        # evaluation, not C — dead all-inactive iterations would multiply
        # every generated token's cost by the window width
        final = jax.lax.fori_loop(0, jnp.max(counts), body, init)
        logits_last, cache = final[:2]

        def sample_row(logits, rid, ti, temp):
            key = jax.random.fold_in(jax.random.fold_in(base_key, rid),
                                     ti)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            samp = jax.random.categorical(
                key, logits / jnp.maximum(temp, 1e-6)).astype(jnp.int32)
            return jnp.where(temp > 0.0, samp, greedy)

        nxt = jax.vmap(sample_row)(logits_last, rids, tidx, temps)
        return (nxt, cache) + final[2:]

    if not paged:
        def dense_step(params, cache, tokens, counts, rids, tidx, temps,
                       base_key):
            return mixed_step(params, cache, None, tokens, counts, rids,
                              tidx, temps, base_key)
        return dense_step
    return mixed_step


@functools.lru_cache(maxsize=None)
def _jitted_mixed_step(cfg: ModelConfig, rt: RuntimeConfig):
    """One jitted mixed prefill/decode step, cached per (cfg, rt) so every
    Engine over the same model shares one trace cache (the step depends on
    the token-window *shape*, not on any per-engine state).  The cache is
    donated: run() rebinds it from the step's return, and in place the
    per-slot where-select KV write stays a masked update instead of a full
    cache copy per token (no-op warning on CPU).  A model with experts
    also returns its held-expert assignment count."""
    return jax.jit(_mixed_step_fn(cfg, rt, count_experts=bool(cfg.n_experts)),
                   donate_argnums=(1,))


# Slot recycling rewrites one batch column of every cache leaf; donating
# the old cache lets XLA do it in place instead of copying the full
# KV/SSM state per admission (donation is a no-op warning on CPU).
_jitted_reset = jax.jit(lm.reset_slots, donate_argnums=0)

# Copy-on-write fork primitive: src/dst are int32 scalars, so one trace
# serves every fork of a run.
_jitted_copy = jax.jit(lm.copy_blocks, donate_argnums=0)


class Engine:
    """Continuous-batching generation over a fixed slot pool.

    ``Engine.run(requests)`` admits the queue into ``slots`` cache rows and
    drives the single jitted mixed step until every request has completed;
    it returns one :class:`Completion` per request, in submission order.

    With ``rt.kv_layout == "paged"`` the attention KV lives in a pool of
    ``kv_num_blocks`` physical blocks (default ``slots * ceil(max_len /
    kv_block_size)``, the dense-equivalent footprint — size it smaller to
    oversubscribe).  ``prefix_sharing`` maps common block-aligned prompt
    prefixes onto shared immutable blocks (automatically disabled for
    model families with recurrent per-slot state, whose SSM carry cannot
    be shared).  ``verify_mode`` runs the ``kv.*`` block-table soundness
    invariants (:func:`repro.core.verify.check_block_tables`) every tick:
    ``"warn"`` (default) emits warnings, ``"strict"`` raises, ``"off"``
    skips the check.

    ``mesh`` plugs the engine into a device mesh: the mixed step runs in
    a shard_map region planned by
    :func:`repro.core.partition.plan_decode_cache` (restrict which axes
    it may use with ``rt.serve_partition``), the plan is checked by the
    ``dist.serve-*`` invariants under the same ``verify_mode``, and
    :meth:`report` records the committed placement.
    """

    def __init__(self, cfg: ModelConfig, params, rt: RuntimeConfig, *,
                 slots: int, max_len: int, prefill_chunk: int = 8,
                 seed: int = 0, kv_num_blocks: int | None = None,
                 prefix_sharing: bool = True, verify_mode: str = "warn",
                 mesh=None):
        if not cfg.supports_decode:
            raise ValueError(f"{cfg.name} is encoder-only; no decode path")
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if rt.kv_layout not in ("dense", "paged"):
            raise ValueError(f"unknown kv_layout {rt.kv_layout!r}; "
                             f"allowed: 'dense' | 'paged'")
        if verify_mode not in verify.VERIFY_MODES:
            raise ValueError(f"unknown verify_mode {verify_mode!r}; "
                             f"allowed: {verify.VERIFY_MODES}")
        self.cfg = cfg
        self.params = params
        self.rt = rt
        self.slots = slots
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        self.seed = seed
        self.kv_layout = rt.kv_layout
        self.block_size = rt.kv_block_size
        self.max_blocks = -(-max_len // self.block_size)
        if self.kv_layout == "paged":
            if kv_num_blocks is None:
                kv_num_blocks = slots * self.max_blocks
            if kv_num_blocks < self.max_blocks:
                raise ValueError(
                    f"kv_num_blocks = {kv_num_blocks} cannot cover even "
                    f"one worst-case request ({self.max_blocks} blocks of "
                    f"{self.block_size} for max_len = {max_len})")
        self.kv_num_blocks = kv_num_blocks or 0
        # recurrent families carry dense SSM state per slot; a prefix hit
        # would skip the recurrence that builds that state, so sharing is
        # attention-family only
        self.prefix_sharing = (prefix_sharing
                               and self.kv_layout == "paged"
                               and cfg.family not in ("ssm", "hybrid"))
        self.verify_mode = verify_mode
        plan = lm.layer_plan(cfg)
        kinds = plan.superblock * plan.n_super + plan.tail
        #: Mamba layers and MoE layers per model evaluation
        self.n_mamba_layers = sum(k in lm.MAMBA_KINDS for k in kinds)
        self.n_moe_layers = sum(k.endswith("_moe") for k in kinds)
        self.last_stats: ServeStats | None = None
        self.last_dispatch: dict[str, int] | None = None
        self.last_allocator: BlockAllocator | None = None
        self.last_prefix_cache: PrefixCache | None = None
        self.last_admission_order: list[int] = []
        self.last_attn_dispatch: dict[str, int] | None = None
        #: ``engine.*`` span deltas of the last run (count, seconds)
        self.last_spans: dict[str, dict] | None = None
        self._n_runs = 0
        self.mesh = mesh
        self.decode_plan: partition_mod.DecodeCachePlan | None = None
        self._model_extent = 1
        if mesh is None:
            self._step = _jitted_mixed_step(cfg, rt)
        else:
            self._step = self._build_sharded_step(mesh)
        self._reset = _jitted_reset
        self._copy = _jitted_copy

    def init_cache(self) -> dict:
        """A fresh decode cache for every slot, in the model's dtype (the
        Mamba SSM state in float32): what a run starts from, and the
        shapes the mesh plan partitions."""
        return lm.init_decode_cache(
            self.cfg, self.slots, self.max_len, kv_layout=self.kv_layout,
            kv_num_blocks=self.kv_num_blocks, kv_block_size=self.block_size)

    def _build_sharded_step(self, mesh):
        """Plan the decode-cache partition, verify it, localize the config
        for tensor-sharded heads, commit the params, and return the jitted
        shard_map-wrapped mixed step.

        ``jit(shard_map(...))`` auto-reshards the per-tick host operands
        (tokens/counts/tables) against the in_specs; the cache stays
        committed to its plan sharding across ticks because the step's
        out_specs (and the GSPMD-propagated reset/copy) reproduce it."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        axes = partition_mod.MeshAxes.from_mesh(mesh)
        cache_shapes = jax.eval_shape(self.init_cache)
        plan = partition_mod.plan_decode_cache(
            cache_shapes, self.rt.serve_partition, axes, slots=self.slots,
            head_extents=(self.cfg.n_heads, self.cfg.n_kv_heads))
        if self.verify_mode != "off":
            verify.enforce(verify.check_decode_plan(plan),
                           self.verify_mode, subject="serve decode plan")
        self.decode_plan = plan
        m = (axes.extent(partition_mod.MODEL_AXIS) if plan.use_model
             else 1)
        self._model_extent = m
        cfg_local = lm.tp_local_config(self.cfg, m)
        rt_local = (dataclasses.replace(self.rt,
                                        tp_axis=partition_mod.MODEL_AXIS)
                    if m > 1 else self.rt)
        pspecs = lm.tp_param_specs(self.params, m)
        # commit the (possibly head-sharded) params once instead of
        # re-sharding them on every dispatch
        self.params = jax.device_put(
            self.params,
            jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), pspecs,
                is_leaf=lambda x: isinstance(x, P)))
        cspecs = plan.spec_tree(cache_shapes)
        vec = plan.operand_spec(1)
        in_specs: list = [pspecs, cspecs]
        if self.kv_layout == "paged":
            in_specs.append(P(None, None))  # host block tables, replicated
        in_specs += [plan.operand_spec(2), vec, vec, vec, vec, P(None)]
        raw = _mixed_step_fn(cfg_local, rt_local)
        return jax.jit(
            collectives.shard_map(raw, mesh, in_specs=tuple(in_specs),
                                  out_specs=(vec, cspecs)),
            donate_argnums=(1,))

    def report(self) -> dict:
        """Serving placement + dispatch summary for the last run: which
        decode path compiled (pallas fast path vs jnp reference, with the
        fallback reason), the mesh placement the plan committed, the
        engine/attention dispatch deltas (paged: ``paged_blocks_live`` over
        ``paged_blocks_grid`` is the share of the paged-decode kernel's
        grid that holds live blocks), and the tick's host time by
        ``engine.*`` span (``host_spans``).  Trace-time counters only move
        when a compilation happens, so a warm trace cache reports the
        mode's static dispatch with a note instead of zeros."""
        attn = dict(self.last_attn_dispatch or {})
        paged = self.kv_layout == "paged"
        pallas_key = "paged_decode_pallas" if paged else "decode_pallas"
        ref_key = "paged_decode_ref" if paged else "decode_ref"
        pallas_path = ("pallas-paged-decode" if paged
                       else "pallas-flash-decode")
        ref_path = "ref-paged-decode" if paged else "ref-decode"
        fallback = None
        if attn.get(pallas_key):
            path = pallas_path
        elif attn.get(ref_key):
            path = ref_path
            fallback = (f"mode={self.rt.mode!r} compiles the jnp "
                        f"reference decode; pallas is the "
                        f"mode='brainslug' fast path")
        elif self.cfg.family == "ssm":
            path = "ssm-recurrent"
            fallback = "no attention layers: nothing to flash-decode"
        elif self.rt.mode == "brainslug":
            path = pallas_path
            fallback = None if self.last_attn_dispatch else \
                "trace cache warm: inferred from mode, not recorded"
        else:
            path = ref_path
            fallback = (f"mode={self.rt.mode!r} compiles the jnp "
                        f"reference decode; pallas is the "
                        f"mode='brainslug' fast path")
        plan = self.decode_plan
        from repro.launch import mesh as mesh_launch
        return {
            "mode": self.rt.mode,
            "kv_layout": self.kv_layout,
            "decode_path": path,
            "decode_fallback": fallback,
            "mesh_axes": mesh_launch.axis_extents(self.mesh),
            "serve_partition": ({"partition": plan.partition,
                                 "data": plan.use_data,
                                 "model": plan.use_model,
                                 "notes": list(plan.notes)}
                                if plan is not None else {}),
            "dispatch": dict(self.last_dispatch or {}),
            "attn_dispatch": attn,
            "host_spans": dict(self.last_spans or {}),
        }

    # -- admission ----------------------------------------------------------

    def _validate(self, r: Request) -> np.ndarray:
        prompt = np.asarray(r.prompt, np.int32)
        if prompt.ndim > 1:
            raise ValueError(
                f"request {r.request_id}: prompt must be a 1-D token "
                f"sequence, got shape {tuple(prompt.shape)} (one Request "
                f"per row — the engine batches across requests itself)")
        prompt = prompt.reshape(-1)
        if r.max_new_tokens < 0:
            raise ValueError(
                f"request {r.request_id}: max_new_tokens must be >= 0")
        total = len(prompt) + r.max_new_tokens
        if total > self.max_len:
            raise ValueError(
                f"request {r.request_id}: prompt_len + max_new_tokens = "
                f"{len(prompt)} + {r.max_new_tokens} = {total} exceeds the "
                f"cache max_len = {self.max_len}; the generation would "
                f"write past the end of its KV-cache slot")
        return prompt

    def _first_token_from_zero_logits(self, req: Request, run_key) -> int:
        """Empty prompt: there is no last-prompt-position logit, so the
        first token is sampled from all-zero logits (greedy decodes the
        pad token 0; temperature samples the uniform distribution) — the
        same convention as the static driver's empty-prompt prefill."""
        if req.temperature <= 0.0:
            return 0
        key = jax.random.fold_in(
            jax.random.fold_in(run_key, req.request_id), 0)
        return int(jax.random.categorical(
            key, jnp.zeros((self.cfg.vocab_size,), jnp.float32)))

    @staticmethod
    def _worst_blocks(prompt_len: int, max_new: int, bs: int) -> int:
        """Total block columns a request can ever touch: the last KV write
        lands at position ``prompt_len + max_new - 2`` (the final sampled
        token is never written back)."""
        return (prompt_len + max_new - 2) // bs + 1

    # -- main loop ----------------------------------------------------------

    def run(self, requests: Sequence[Request],
            key: jnp.ndarray | None = None, *,
            on_token: Callable[[TokenEvent], None] | None = None
            ) -> list[Completion]:
        """Serve every request to completion; returns completions in
        submission order.  ``key`` overrides the per-run RNG key (default:
        ``fold_in(PRNGKey(seed), run_counter)`` so repeated runs with
        temperature sampling draw fresh streams).

        ``on_token`` streams the run: it fires with every
        :class:`TokenEvent` as the scheduler commits it (after any
        per-request ``Request.on_token``), so callers observe tokens in
        commit order while the same completions are still returned in
        submission order at the end.

        Error isolation is per request: a validation failure yields a
        ``status='invalid'`` Completion for that request and the rest of
        the queue is served normally — ``run()`` only raises for engine
        misconfiguration, never for one bad request."""
        it = self._serve(requests, key)
        while True:
            try:
                ev = next(it)
            except StopIteration as stop:
                return stop.value
            if on_token is not None:
                on_token(ev)

    def stream(self, requests: Sequence[Request],
               key: jnp.ndarray | None = None) -> Iterator[TokenEvent]:
        """Generator form of :meth:`run`: yields every :class:`TokenEvent`
        in commit order.  Each request's terminal event carries its
        :class:`Completion`; per-run stats land on :attr:`last_stats` once
        the generator is exhausted."""
        yield from self._serve(requests, key)

    def _serve(self, requests: Sequence[Request],
               key: jnp.ndarray | None) -> Any:
        """The scheduler loop as a generator: yields TokenEvents at every
        commit point, returns the submission-ordered completions (the
        generator's StopIteration value, unwrapped by :meth:`run`)."""
        if key is None:
            key = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                     self._n_runs)
        self._n_runs += 1
        # Module-level STATS is process-cumulative by design; a second
        # run() in the same process must still report only its own work
        # (the static-vs-engine benchmark compares per-run decode
        # slot-steps) — snapshot here, delta at the end.
        stats_before = STATS.snapshot()
        attn_before = attn_ops.STATS.snapshot()
        spans_before = SPANS.snapshot()

        B, C, bs = self.slots, self.prefill_chunk, self.block_size
        paged = self.kv_layout == "paged"
        completions: list[Completion | None] = [None] * len(requests)
        stats = ServeStats(n_requests=len(requests), n_slots=B)
        events: list[TokenEvent] = []

        def emit(req: Request, ev: TokenEvent) -> None:
            # per-request callbacks fire at commit, before the global
            # stream sees the event
            if req.on_token is not None:
                req.on_token(ev)
            events.append(ev)

        # admission order: highest priority first, FIFO within a priority
        # band (the submission index is the tiebreak, so equal-priority
        # entries pop in submission order and Requests never compare)
        heap: list[tuple[int, int, Request, np.ndarray]] = []
        for i, r in enumerate(requests):
            try:
                heapq.heappush(heap, (-r.priority, i, r, self._validate(r)))
            except ValueError as e:
                completions[i] = Completion(
                    request_id=r.request_id,
                    prompt_len=int(np.size(np.asarray(r.prompt))),
                    tokens=np.zeros(0, np.int32), status="invalid",
                    reason=str(e))
                stats.failed += 1
                emit(r, TokenEvent(r.request_id, None, 0, True,
                                   completions[i]))
        for ev in events:
            yield ev
        events.clear()
        slot: list[_Slot | None] = [None] * B
        dirty = [False] * B             # slot held a previous request
        # plain list, not an ndarray: the mask handed to the jitted reset
        # must be a fresh buffer every time (np.asarray(list) copies).
        # jnp.asarray of a live numpy array can alias its memory zero-copy
        # on CPU, and the async reset may read it only after the host loop
        # has moved on — mutating a passed-in mask in place intermittently
        # turned it all-False and left the freed slot's cache stale.
        pending_reset = [False] * B
        pending_len = [0] * B           # paged: restart length (prefix hit)
        alloc = BlockAllocator(self.kv_num_blocks, bs) if paged else None
        prefix = (PrefixCache(alloc)
                  if paged and self.prefix_sharing else None)
        self.last_allocator = alloc
        self.last_prefix_cache = prefix
        self.last_admission_order = []
        tables = np.zeros((B, self.max_blocks), np.int32)
        outstanding = 0         # worst-case blocks live slots may claim
        util_acc, util_n = 0.0, 0
        latencies: list[float] = []
        n_latency_pending = 0   # ok-completions awaiting the next tick's
        # clock read (one timestamp per tick; see `now` below)
        ttfts: list[float] = []
        n_ttft_pending = 0      # first-token commits awaiting that same
        # shared clock read (TTFT = admission wait + prefill)
        cache = self.init_cache()
        t0 = time.perf_counter()

        def complete(s_idx: int, req: Request, prompt, gen) -> None:
            nonlocal n_latency_pending
            completions[s_idx] = Completion(
                request_id=req.request_id, prompt_len=len(prompt),
                tokens=np.asarray(gen, np.int32))
            stats.completed += 1
            n_latency_pending += 1
            emit(req, TokenEvent(req.request_id, None, len(gen), True,
                                 completions[s_idx]))

        def try_map(prompt: np.ndarray, max_new: int):
            """Prefix-map and block-gate one request.  Returns ``(blocks,
            cached_len, chain_key, n_full, reserve)`` after taking the
            reservation, or None when the pool (minus what live slots may
            still claim) cannot cover the worst case — the caller keeps
            the request queued (head-of-line: block order is preserved)."""
            nonlocal outstanding
            worst_total = self._worst_blocks(len(prompt), max_new, bs)
            blocks: list[int] = []
            chain_key = _CHAIN_ROOT
            cached_len = 0
            n_full = 0
            if prefix is not None and len(prompt) > 0:
                fulls, chain_key, partial = prefix.lookup(prompt)
                # take the references immediately: a hit block must not be
                # evicted between lookup and the slot's table pointing at
                # it
                for pb in fulls:
                    alloc.share(pb)
                blocks = list(fulls)
                n_full = len(fulls)
                cached_len = n_full * bs
                if partial is not None:
                    pb, t = partial
                    alloc.share(pb)
                    blocks.append(pb)
                    cached_len += t
                # the last prompt position must be recomputed so the slot
                # has a logit to sample its first token from
                cached_len = min(cached_len, len(prompt) - 1)
            # at most one mapped block is ever written (the boundary
            # column at cached_len // bs) -> at most one COW fork; the
            # rest of the worst case is fresh extension blocks
            reserve = worst_total - len(blocks) + (1 if blocks else 0)
            avail = alloc.n_free - outstanding
            if reserve > avail and prefix is not None:
                prefix.evict(reserve - avail)
                avail = alloc.n_free - outstanding
            if reserve > avail:
                for pb in reversed(blocks):
                    alloc.release(pb)
                return None
            outstanding += reserve
            if prefix is not None:
                prefix.hits += cached_len
            return blocks, cached_len, chain_key, n_full, reserve

        def unmap(mapping) -> None:
            """Roll back a ``try_map`` reservation (admission fast paths
            that never occupy a slot)."""
            nonlocal outstanding
            blocks, _, _, _, reserve = mapping
            for pb in reversed(blocks):
                alloc.release(pb)
            outstanding -= reserve

        def release_slot(b: int, s: _Slot) -> None:
            """Return a completed slot's blocks (registering the prompt's
            sub-block tail with the prefix cache first — it is immutable
            from here on) and its unused reservation."""
            nonlocal outstanding
            plen = len(s.prompt)
            if prefix is not None and plen % bs and s.kv_len >= plen:
                pcol = plen // bs
                prefix.register_partial(s.chain_key, s.prompt[pcol * bs:],
                                        s.blocks[pcol])
            for blk in s.blocks:
                alloc.release(blk)
            s.blocks = []
            outstanding -= s.reserve
            s.reserve = 0
            tables[b, :] = 0

        def admit(now: float) -> None:
            nonlocal n_ttft_pending
            for b in range(B):
                while slot[b] is None and heap:
                    entry = heapq.heappop(heap)
                    _, idx, req, prompt = entry
                    waited_ms = (now - t0) * 1e3
                    if req.deadline_ms is not None \
                            and waited_ms > req.deadline_ms:
                        completions[idx] = Completion(
                            request_id=req.request_id,
                            prompt_len=len(prompt),
                            tokens=np.zeros(0, np.int32),
                            status="timeout",
                            reason=(f"queued {waited_ms:.1f}ms, past the "
                                    f"{req.deadline_ms:.1f}ms deadline"))
                        stats.timed_out += 1
                        emit(req, TokenEvent(req.request_id, None, 0, True,
                                             completions[idx]))
                        continue
                    # max_new == 0 completes at admission without touching
                    # KV; everything else gates on its worst-case blocks
                    mapping = None
                    if paged and req.max_new_tokens > 0 \
                            and self._worst_blocks(
                                len(prompt), req.max_new_tokens, bs) > 0:
                        mapping = try_map(prompt, req.max_new_tokens)
                        if mapping is None:
                            # block admission, not the whole pool: the
                            # request waits for completions to free blocks
                            heapq.heappush(heap, entry)
                            return
                    stats.admitted += 1
                    self.last_admission_order.append(idx)
                    if req.max_new_tokens == 0:
                        complete(idx, req, prompt, [])
                        continue
                    gen: list[int] = []
                    last = 0
                    if len(prompt) == 0:
                        try:
                            tok0 = self._first_token_from_zero_logits(
                                req, key)
                        except Exception as e:   # isolate the one request
                            completions[idx] = Completion(
                                request_id=req.request_id, prompt_len=0,
                                tokens=np.zeros(0, np.int32),
                                status="error",
                                reason=f"{type(e).__name__}: {e}")
                            stats.failed += 1
                            emit(req, TokenEvent(req.request_id, None, 0,
                                                 True, completions[idx]))
                            if mapping is not None:
                                unmap(mapping)
                            continue
                        gen = [tok0]
                        stats.generated_tokens += 1
                        n_ttft_pending += 1
                        emit(req, TokenEvent(req.request_id, tok0, 0))
                        if req.max_new_tokens == 1:
                            complete(idx, req, prompt, gen)
                            continue
                        last = tok0
                    cached_len = 0
                    s = _Slot(idx=idx, req=req, prompt=prompt, gen=gen,
                              last=last)
                    if mapping is not None:
                        blocks, cached_len, chain_key, n_full, rsv = \
                            mapping
                        s.blocks = blocks
                        s.reserve = rsv
                        s.chain_key = chain_key
                        s.n_reg = n_full
                        s.pos = cached_len
                        s.kv_len = cached_len
                        tables[b, :] = 0
                        tables[b, :len(blocks)] = blocks
                        stats.prefix_hit_tokens += cached_len
                    if dirty[b] or cached_len:
                        # freed slots restart at length 0; a prefix hit
                        # restarts mid-prompt at cached_len — the shared
                        # blocks already hold those positions
                        pending_reset[b] = True
                        pending_len[b] = cached_len
                        dirty[b] = False
                    slot[b] = s

        def lanes():
            """The tick's lane arrays, and (paged) its write barrier: every
            block column this dispatch writes must be mapped, and mapped
            privately — extension columns get fresh blocks, shared columns
            are forked copy-on-write before the step runs.  Returns the
            arrays, the prefill flags, the written blocks and the forks."""
            nonlocal outstanding
            tokens = np.zeros((B, C), np.int32)
            counts = np.zeros((B,), np.int32)
            rids = np.zeros((B,), np.int32)
            tidx = np.zeros((B,), np.int32)
            temps = np.zeros((B,), np.float32)
            was_prefill = [False] * B
            copies: list[tuple[int, int]] = []
            writers: set[int] = set()
            for b, s in enumerate(slot):
                if s is None:
                    continue
                rids[b] = s.req.request_id
                temps[b] = s.req.temperature
                tidx[b] = len(s.gen)
                if s.pos < len(s.prompt):
                    n = min(C, len(s.prompt) - s.pos)
                    tokens[b, :n] = s.prompt[s.pos: s.pos + n]
                    counts[b] = n
                    was_prefill[b] = True
                else:
                    tokens[b, 0] = s.last
                    counts[b] = 1
                    n = 1
                if not paged:
                    continue
                lo, hi = s.kv_len, s.kv_len + n
                for col in range(lo // bs, (hi - 1) // bs + 1):
                    if col >= len(s.blocks):
                        s.blocks.append(alloc.alloc())
                        s.reserve -= 1
                        outstanding -= 1
                    elif alloc.refcount[s.blocks[col]] > 1:
                        nb = alloc.alloc()
                        s.reserve -= 1
                        outstanding -= 1
                        copies.append((s.blocks[col], nb))
                        alloc.note_fork(s.blocks[col], nb)
                        alloc.release(s.blocks[col])
                        s.blocks[col] = nb
                        stats.cow_forks += 1
                        STATS.record("cow_fork")
                    tables[b, col] = s.blocks[col]
                    writers.add(s.blocks[col])
            return ((tokens, counts, rids, tidx, temps), was_prefill,
                    writers, copies)

        def check_tables(counts, writers) -> None:
            rows = [(tuple(s.blocks), s.kv_len + int(counts[b]))
                    for b, s in enumerate(slot) if s is not None]
            state = verify.BlockTableState(
                num_blocks=self.kv_num_blocks, block_size=bs,
                refcounts=tuple(alloc.refcount),
                free=alloc.free_blocks(),
                tables=tuple(r[0] for r in rows),
                lengths=tuple(r[1] for r in rows),
                cached=(prefix.cached_blocks() if prefix is not None
                        else ()),
                writers=tuple(sorted(writers)))
            verify.enforce(verify.check_block_tables(state),
                           self.verify_mode, subject="engine tick")

        def commit(nxt: np.ndarray, counts: np.ndarray,
                   was_prefill: list[bool]) -> None:
            """Account the dispatched tick, advance every live slot, and
            emit its tokens and completions."""
            nonlocal n_ttft_pending, util_acc, util_n
            stats.step_dispatches += 1
            STATS.record("mixed_step")

            # idle accounting is in model-evaluation units: the mixed step
            # runs max(counts) sub-steps over every lane, so an empty lane
            # rides the whole window and a live lane rides the sub-steps
            # beyond its own count — both are dispatched-but-useless work
            window = int(counts.max())
            for b in range(B):
                s = slot[b]
                if s is None:
                    stats.idle_slot_steps += window
                    STATS.record("idle_slot_steps", window)
                    continue
                n = int(counts[b])
                if was_prefill[b]:
                    s.pos += n
                    stats.prefill_tokens += n
                    STATS.record("prefill_tokens", n)
                    stats.idle_slot_steps += window - n
                    STATS.record("idle_slot_steps", window - n)
                else:
                    stats.decode_slot_steps += 1
                    STATS.record("decode_slot_steps")
                    stats.idle_slot_steps += window - 1
                    STATS.record("idle_slot_steps", window - 1)
                if self.n_mamba_layers:
                    STATS.record("ssm_state_updates",
                                 n * self.n_mamba_layers)
                if self.n_moe_layers:
                    STATS.record("moe_routed_assignments",
                                 n * self.cfg.top_k * self.n_moe_layers)
                lo = s.kv_len
                s.kv_len = lo + n
                if paged:
                    # the evaluation that consumes token t attends to
                    # lo + t + 1 positions
                    STATS.record("paged_blocks_live", sum(
                        -(-(lo + t + 1) // bs) for t in range(n)))
                    STATS.record("paged_blocks_grid", n * self.max_blocks)
                    for col in range(lo // bs, (s.kv_len - 1) // bs + 1):
                        alloc.note_fill(s.blocks[col],
                                        min(s.kv_len - col * bs, bs))
                    if prefix is not None:
                        # a prompt block is immutable once fully written:
                        # publish it so later prompts can share it
                        n_full_now = min(s.kv_len, len(s.prompt)) // bs
                        for col in range(s.n_reg, n_full_now):
                            s.chain_key = prefix.register_full(
                                s.chain_key,
                                s.prompt[col * bs:(col + 1) * bs],
                                s.blocks[col])
                        s.n_reg = n_full_now
                if was_prefill[b] and s.pos < len(s.prompt):
                    continue        # mid-prefill: sample is discarded
                tok = int(nxt[b])
                s.gen.append(tok)
                s.last = tok
                stats.generated_tokens += 1
                if len(s.gen) == 1:
                    n_ttft_pending += 1
                emit(s.req, TokenEvent(s.req.request_id, tok,
                                       len(s.gen) - 1))
                if len(s.gen) >= s.req.max_new_tokens:
                    complete(s.idx, s.req, s.prompt, s.gen)
                    if paged:
                        release_slot(b, s)
                    slot[b] = None
                    dirty[b] = True

            if paged:
                if alloc.in_use:
                    util_acc += alloc.stored / (alloc.in_use * bs)
                    util_n += 1
            else:
                live = sum(s.kv_len for s in slot if s is not None)
                util_acc += live / (B * self.max_len)
                util_n += 1

        while True:
            # one clock read per scheduler tick: every deadline check this
            # tick and every latency stamped since the last tick sees the
            # same timestamp (per-event reads made admission order change
            # the deadline verdicts of unrelated requests)
            now = time.perf_counter()
            if n_latency_pending:
                latencies.extend([(now - t0) * 1e3] * n_latency_pending)
                n_latency_pending = 0
            if n_ttft_pending:
                ttfts.extend([(now - t0) * 1e3] * n_ttft_pending)
                n_ttft_pending = 0
            with span("engine.admit"):
                admit(now)
            for ev in events:
                yield ev
            events.clear()
            # admission marks a slot for reset only as it fills it
            if all(s is None for s in slot):
                break
            with span("engine.tick"):
                with span("engine.prepare"):
                    if any(pending_reset):
                        # jitted per-slot cache clear: freed slots restart
                        # at length 0 / zero SSM state before their new
                        # request's first prefill chunk
                        mask = jnp.asarray(np.asarray(pending_reset))
                        if paged:
                            cache = self._reset(cache, mask, jnp.asarray(
                                np.asarray(pending_len, np.int32).copy()))
                        else:
                            cache = self._reset(cache, mask)
                        STATS.record("slot_reset")
                        pending_reset = [False] * B
                        pending_len = [0] * B
                    arrays, was_prefill, writers, copies = lanes()
                    for src, dst in copies:
                        cache = self._copy(cache, jnp.asarray(src, jnp.int32),
                                           jnp.asarray(dst, jnp.int32))
                counts = arrays[1]
                if paged and self.verify_mode != "off":
                    with span("engine.verify"):
                        check_tables(counts, writers)
                with span("engine.upload"):
                    step_in = (self.params, cache)
                    if paged:
                        step_in += (jnp.asarray(tables),)
                    step_in += tuple(jnp.asarray(a) for a in arrays)
                with span("engine.dispatch"):
                    nxt, cache, *held = self._step(*step_in, key)
                with span("engine.sync"):
                    if held:
                        nxt, held = jax.device_get((nxt, held[0]))
                        STATS.record("moe_held_assignments", int(held))
                    nxt = np.asarray(nxt)
                with span("engine.commit"):
                    commit(nxt, counts, was_prefill)

            # the tick's commits are final: stream them before the next
            # dispatch so a consumer never waits on future batch-mates
            for ev in events:
                yield ev
            events.clear()

        end = time.perf_counter()
        for ev in events:
            yield ev
        events.clear()
        if n_latency_pending:
            latencies.extend([(end - t0) * 1e3] * n_latency_pending)
        if n_ttft_pending:
            ttfts.extend([(end - t0) * 1e3] * n_ttft_pending)
        stats.wall_s = end - t0
        if latencies:
            stats.p50_latency_ms = float(np.percentile(latencies, 50))
            stats.p99_latency_ms = float(np.percentile(latencies, 99))
        if ttfts:
            stats.ttft_p50_ms = float(np.percentile(ttfts, 50))
            stats.ttft_p99_ms = float(np.percentile(ttfts, 99))
        stats.kv_block_utilization = (util_acc / util_n) if util_n else 0.0
        if paged:
            if prefix is not None:
                # drop the cache's block references: after a run the free
                # list must hold the whole pool again (leak check)
                prefix.clear()
            stats.blocks_in_use = alloc.peak_in_use
        self.last_stats = stats
        self.last_dispatch = STATS.delta(stats_before)
        self.last_attn_dispatch = attn_ops.STATS.delta(attn_before)
        self.last_spans = {k: v for k, v in SPANS.delta(spans_before).items()
                           if k.startswith("engine.")}
        return completions  # type: ignore[return-value]
