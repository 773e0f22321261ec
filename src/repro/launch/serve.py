"""Batched serving drivers: the static two-phase loop and the
continuous-batching engine.

Two drivers share one model (params, jitted ``lm.decode_step`` family):

* ``Server.generate`` — the classic static path: a rectangular batch is
  prefilled, then decoded in lock-step.  Kept as the parity baseline; its
  historical defects are fixed here: the loop stops as soon as every
  request has passed its stop length (and dispatches nothing at all when
  ``stops.max() == 0``), the prompt shape is validated against
  ``ServeConfig`` and against the cache ``max_len``, and each call draws a
  fresh RNG stream (per-call ``fold_in`` on a call counter) instead of
  replaying ``PRNGKey(seed + 1)`` forever.
* ``Server.engine()`` — builds a :class:`repro.launch.engine.Engine` over
  the same params: slot-managed KV cache, queue admission, one jitted
  mixed prefill/decode step.  Use it for ragged traffic.

Dispatch accounting: the static driver records into ``STATS`` (runtime
keys — ``prefill`` / ``decode`` dispatches plus ``decode_slot_steps``, the
slot-units of decode work including the pad cycling of finished requests)
and exposes a per-run :class:`~repro.core.scheduler.ServeStats` via
``Server.last_stats`` for throughput comparisons against the engine.

Usage:
  python -m repro.launch.serve --arch qwen2.5-14b --reduced --new-tokens 16
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import RuntimeConfig
from repro.core.scheduler import ServeStats
from repro.obs import DispatchStats
from repro.launch import compile_cache, engine as engine_mod
from repro.models import lm

STATS = DispatchStats(keys=("prefill", "decode", "decode_slot_steps",
                            "generated_tokens"))


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    arch: str = "qwen2.5-14b"
    reduced: bool = True
    mode: str = "xla"
    batch: int = 4
    prompt_len: int = 16
    new_tokens: int = 16
    max_len: int = 64
    temperature: float = 0.0           # 0 = greedy
    seed: int = 0
    # (field, value) pairs applied to the model config after ``reduced``,
    # e.g. (("n_layers", 8),) serves the published widths cut in depth
    config_overrides: tuple = ()


class Server:
    """Holds jitted prefill/decode callables + the mutable cache."""

    def __init__(self, sc: ServeConfig):
        cfg = get_config(sc.arch)
        if sc.reduced:
            cfg = cfg.reduced()
        if sc.config_overrides:
            cfg = dataclasses.replace(cfg, **dict(sc.config_overrides))
        if not cfg.supports_decode:
            raise ValueError(f"{sc.arch} is encoder-only; no decode path")
        if cfg.frontend == "vision_patches":
            cfg = dataclasses.replace(cfg, frontend=None, n_prefix_tokens=0)
        self.cfg = cfg
        self.sc = sc
        self.rt = RuntimeConfig(mode=sc.mode)
        self.params, _ = lm.init(jax.random.PRNGKey(sc.seed), cfg)
        self.last_stats: ServeStats | None = None
        self.last_dispatch: dict[str, int] | None = None
        self._n_calls = 0

        cfg_, rt_ = self.cfg, self.rt

        @jax.jit
        def decode_fn(params, cache, tok):
            return lm.decode_step(params, cache, tok, cfg_, rt_)

        @jax.jit
        def prefill_fn(params, cache, tokens):
            # One jitted dispatch for the whole prompt: position 0 seeds the
            # carry (logit dtype/shape come from the model, not a guess),
            # the fori_loop rolls the remaining positions inside the jit.
            logits, cache = lm.decode_step(params, cache, tokens[:, :1],
                                           cfg_, rt_)

            def body(t, carry):
                _, cache = carry
                tok = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)
                return lm.decode_step(params, cache, tok, cfg_, rt_)

            return jax.lax.fori_loop(1, tokens.shape[1], body,
                                     (logits, cache))

        self._decode = decode_fn
        self._prefill = prefill_fn

    def engine(self, *, slots: int | None = None, prefill_chunk: int = 8,
               seed: int | None = None, kv_layout: str | None = None,
               kv_block_size: int | None = None,
               kv_num_blocks: int | None = None,
               prefix_sharing: bool = True,
               verify_mode: str = "warn", mesh=None,
               serve_partition: str | None = None) -> engine_mod.Engine:
        """A continuous-batching :class:`~repro.launch.engine.Engine` over
        this server's params/config (``slots`` defaults to the static
        batch width; the cache budget is the same ``max_len``).

        ``kv_layout``/``kv_block_size`` override the runtime config's KV
        cache layout for this engine (``"paged"`` swaps the dense per-slot
        reservation for the block pool; see ``launch/engine.py``).
        ``mesh`` runs the engine's mixed step in a shard_map region over a
        device mesh (``launch.mesh.make_test_mesh`` /
        ``make_production_mesh``); ``serve_partition`` restricts which
        mesh axes the decode-cache plan may use (``'auto'`` | ``'none'`` |
        ``'data'`` | ``'tensor'`` | ``'both'``).  The remaining knobs pass
        through to the Engine."""
        rt = self.rt
        if (kv_layout is not None or kv_block_size is not None
                or serve_partition is not None):
            rt = dataclasses.replace(
                rt,
                kv_layout=rt.kv_layout if kv_layout is None else kv_layout,
                kv_block_size=(rt.kv_block_size if kv_block_size is None
                               else kv_block_size),
                serve_partition=(rt.serve_partition
                                 if serve_partition is None
                                 else serve_partition))
        return engine_mod.Engine(
            self.cfg, self.params, rt,
            slots=self.sc.batch if slots is None else slots,
            max_len=self.sc.max_len, prefill_chunk=prefill_chunk,
            seed=self.sc.seed if seed is None else seed,
            kv_num_blocks=kv_num_blocks, prefix_sharing=prefix_sharing,
            verify_mode=verify_mode, mesh=mesh)

    def prefill(self, tokens: jnp.ndarray) -> tuple[Any, jnp.ndarray]:
        """Ingest the prompt (cache-building prefill) in a single jitted
        dispatch.  Returns (cache, last-token logits)."""
        b, s = tokens.shape
        if s > self.sc.max_len:
            raise ValueError(
                f"prompt length {s} exceeds cache max_len = "
                f"{self.sc.max_len}; the prefill would write past the end "
                f"of the KV cache")
        cache = lm.init_decode_cache(self.cfg, b, self.sc.max_len,
                                     dtype=jnp.float32)
        if s == 0:
            # Zero-length prompts have no last-token logits; generation
            # starts from all-zero logits (greedy decodes the pad token 0)
            # instead of crashing on ``logits[:, 0]`` with logits = None.
            return cache, jnp.zeros((b, self.cfg.vocab_size), jnp.float32)
        STATS.record("prefill")
        logits, cache = self._prefill(self.params, cache,
                                      jnp.asarray(tokens))
        return cache, logits[:, 0]

    def _sample(self, logits: jnp.ndarray, key) -> jnp.ndarray:
        if self.sc.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / self.sc.temperature, axis=-1).astype(jnp.int32)

    def generate(self, prompts: np.ndarray,
                 stop_lengths: np.ndarray | None = None,
                 key: jnp.ndarray | None = None) -> np.ndarray:
        """prompts: (B, P) int32.  Returns (B, new_tokens) generations;
        rows are zero-padded past their stop length.

        ``key`` overrides the sampling key for this call; by default each
        call folds a call counter into ``PRNGKey(seed + 1)``, so repeated
        temperature-sampled calls draw distinct streams (pass an explicit
        key to reproduce a call).
        """
        sc = self.sc
        prompts = np.asarray(prompts)
        if prompts.ndim != 2 or prompts.shape != (sc.batch, sc.prompt_len):
            raise ValueError(
                f"prompts shape {tuple(prompts.shape)} does not match "
                f"ServeConfig(batch={sc.batch}, prompt_len={sc.prompt_len})")
        if sc.prompt_len + sc.new_tokens > sc.max_len:
            raise ValueError(
                f"prompt_len + new_tokens = {sc.prompt_len} + "
                f"{sc.new_tokens} exceeds cache max_len = {sc.max_len}; "
                f"the generation would write past the end of the KV cache")
        b = sc.batch
        stops = (np.full((b,), sc.new_tokens)
                 if stop_lengths is None else np.asarray(stop_lengths))
        if stops.shape != (b,):
            raise ValueError(
                f"stop_lengths shape {tuple(stops.shape)} does not match "
                f"the batch: expected ({b},)")
        stops = np.clip(stops, 0, sc.new_tokens)
        out = np.zeros((b, sc.new_tokens), np.int32)
        stats = ServeStats(n_requests=b, n_slots=b)
        # per-call dispatch delta: STATS is process-cumulative, a second
        # generate() must still report only its own dispatches
        stats_before = STATS.snapshot()
        t0 = time.perf_counter()

        # Every request at stop length 0 => nothing to generate: return the
        # all-pad result without a single dispatch (not even the prefill).
        live_steps = int(stops.max()) if b else 0
        if live_steps == 0:
            self.last_stats = stats
            self.last_dispatch = STATS.delta(stats_before)
            return out

        cache, logits = self.prefill(jnp.asarray(prompts, jnp.int32))
        if sc.prompt_len > 0:           # empty prompts dispatch nothing
            stats.step_dispatches += 1
            stats.prefill_tokens += b * sc.prompt_len
        if key is None:
            key = jax.random.fold_in(jax.random.PRNGKey(sc.seed + 1),
                                     self._n_calls)
        self._n_calls += 1
        for i in range(live_steps):
            key, sub = jax.random.split(key)
            nxt = self._sample(logits, sub)
            done = i >= stops
            nxt = jnp.where(jnp.asarray(done), 0, nxt)      # pad finished
            out[:, i] = np.asarray(nxt)
            n_live = int((~done).sum())
            stats.generated_tokens += n_live
            STATS.record("generated_tokens", n_live)
            # The loop used to march all new_tokens steps, cycling pad
            # tokens through full decode dispatches long after done.all().
            # The last sampled step needs no further logits either: the
            # final decode is skipped too.
            if i + 1 < live_steps:
                STATS.record("decode")
                STATS.record("decode_slot_steps", b)
                stats.step_dispatches += 1
                stats.decode_slot_steps += b
                # slots whose request is already past its stop length only
                # cycle a pad token through this dispatch — the waste the
                # continuous-batching engine exists to remove
                stats.padded_decode_slot_steps += b - int((i + 1 < stops).sum())
                logits_full, cache = self._decode(self.params, cache,
                                                  nxt[:, None])
                logits = logits_full[:, 0]
        stats.completed = b
        stats.admitted = b
        stats.wall_s = time.perf_counter() - t0
        self.last_stats = stats
        self.last_dispatch = STATS.delta(stats_before)
        return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--mode", default="xla",
                    choices=["brainslug", "xla", "barrier"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)
    compile_cache.configure()

    sc = ServeConfig(arch=args.arch, mode=args.mode, batch=args.batch,
                     prompt_len=args.prompt_len, new_tokens=args.new_tokens,
                     max_len=args.prompt_len + args.new_tokens + 1,
                     temperature=args.temperature)
    server = Server(sc)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, server.cfg.vocab_size,
                           (sc.batch, sc.prompt_len)).astype(np.int32)
    t0 = time.time()
    gen = server.generate(prompts)
    dt = time.time() - t0
    tput = sc.batch * sc.new_tokens / dt
    print(f"[serve] {sc.batch} requests x {sc.new_tokens} tokens "
          f"in {dt:.2f}s ({tput_fmt(tput)})")
    print("[serve] first generation:", gen[0].tolist())
    return 0


def tput_fmt(tput: float) -> str:
    return f"{tput:.1f} tok/s"


if __name__ == "__main__":
    sys.exit(main())
