"""A queue of greedy generation requests through the serve engine
(``repro.launch.engine.Engine.stream``), offline batch inference: the
whole queue is handed over at once and never drains inside the window.

The engine is built the way ``Server(...).engine(...)`` builds it, from
the configuration's ``serve`` settings, but over weights the benchmark
makes itself on the device from the seed (``bench/configs/<config>.py``
``init``), so that the plain reference and the program share nothing the
program made.

Set-up ends once the first wave of requests (one per slot) has been
admitted and has streamed its first token: the window measures the batch
job in its steady state, not the prefill of its first wave.  It closes at
the first scheduler tick that commits at or after ``--seconds``.  Every
committed token is stamped by the host clock as it is streamed; the gap
between consecutive tokens of one request is an inter-token latency.

``correct``: after the window the engine serves on, untimed, until its
finished requests hold ``sample_tokens`` served tokens (at most
``FINISH_SECONDS``).  Once the program's state is freed, a seeded sample
of the requests it finished, the longest among them and at least
``sample_tokens`` served tokens in all, goes through the plain float32
reference once per request, over prompt and served tokens.  The number
compared is the widest gap by which a served token's reference logit lies
below the reference's best at that position.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time

import numpy as np

import counts
import generate
import harness
import reduce_trace

TRACE_SECONDS = 4.0
#: how long past the window the engine may serve on to finish requests
FINISH_SECONDS = 120.0


def key_of(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31),
                              seed // 2 ** 31)


#: the program's ModelConfig fields, by the configuration key they come from
SIZES = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
         "d_ff": "intermediate_size", "n_heads": "num_attention_heads",
         "n_kv_heads": "num_key_value_heads", "vocab_size": "vocab_size",
         "rope_theta": "rope_theta", "dtype": "torch_dtype",
         "act": "hidden_act"}


def program_config(cfg: dict):
    """The program's model config: its registered architecture with every
    size taken from the configuration's file."""
    from repro.configs import get_config

    mc = dataclasses.replace(get_config(cfg["serve"]["arch"]),
                             **{k: cfg[v] for k, v in SIZES.items()})
    if mc.tie_embeddings or mc.qkv_bias or mc.norm != "rms":
        raise harness.BenchError(f"{mc.name} is not a LLaMA-style decoder")
    return mc


def program_params(w: dict) -> dict:
    """The benchmark's weights in ``repro.models.lm``'s tree (no copy:
    the same arrays, nested)."""
    return {
        "embed": w["embed"], "out_head": w["head"],
        "final_norm": {"scale": w["final_norm"]},
        "blocks": {"sub0": {
            "norm1": {"scale": w["attn_norm"]},
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "norm2": {"scale": w["mlp_norm"]},
            "mlp": {"wg": w["wg"], "wu": w["wu"], "wd": w["wd"]}}},
    }


def build_engine(cfg: dict, w: dict, seed: int):
    import jax

    from repro.configs.base import RuntimeConfig
    from repro.launch.engine import Engine
    from repro.models import lm

    mc = program_config(cfg)
    params = program_params(w)
    want = jax.tree_util.tree_map(
        lambda s: (s.shape, s.dtype),
        jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), mc)[0]))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    if got != want:
        raise harness.BenchError("weights do not match the program's "
                                 "parameter tree")
    s = cfg["serve"]
    rt = RuntimeConfig(mode=s["mode"], kv_layout=s["kv_layout"],
                       kv_block_size=s["kv_block_size"])
    return Engine(mc, params, rt, slots=s["slots"], max_len=s["max_len"],
                  prefill_chunk=s["prefill_chunk"], seed=seed,
                  verify_mode=s["verify_mode"])


def warm_queue(cfg: dict) -> list:
    """Compiles the engine's programs at the cell's shapes: the mixed step
    at (slots, prefill_chunk), the slot reset, and the copy-on-write block
    copy (the last request repeats the first's prompt, admitted after the
    first has finished and published its blocks)."""
    from repro.launch.engine import Request

    s = cfg["serve"]
    rng = np.random.default_rng(0)
    first = rng.integers(0, cfg["vocab_size"],
                         (s["kv_block_size"] + 3,)).astype(np.int32)
    reqs = [Request(request_id=0, prompt=first, max_new_tokens=1)]
    for i in range(1, s["slots"]):
        reqs.append(Request(
            request_id=i, max_new_tokens=3,
            prompt=rng.integers(0, cfg["vocab_size"],
                                (s["prefill_chunk"] + 1,)).astype(np.int32)))
    reqs.append(Request(request_id=s["slots"], prompt=first,
                        max_new_tokens=2))
    return reqs


@dataclasses.dataclass
class Tok:
    rid: int
    index: int
    t: float        # host clock when streamed
    tick: int       # the engine's mixed-step count when streamed


class Stream:
    """``Engine.stream`` with each event stamped by the host clock and by
    the engine's tick counter (``engine.STATS["mixed_step"]``, a count the
    program keeps)."""

    def __init__(self, engine, requests):
        from repro.launch import engine as engine_mod

        self._stats = engine_mod.STATS
        self._gen = engine.stream(requests)
        self._held = None

    def tick(self) -> int:
        return self._stats.counts["mixed_step"]

    def counters(self) -> dict:
        return self._stats.snapshot()

    def next(self):
        if self._held is not None:
            ev, self._held = self._held, None
            return ev
        ev = next(self._gen)
        return ev, time.perf_counter(), self.tick()

    def push_back(self, item) -> None:
        self._held = item

    def close(self) -> None:
        self._gen.close()


def serve_window(stream: Stream, start: float, seconds: float,
                 log: dict) -> float:
    """Consume ticks until the first one that commits at or after
    ``start + seconds``; every token and completion lands in ``log``.
    Returns the window's length: to that tick's commit."""
    consume(stream, log, lambda: log["tick_start"] >= start + seconds)
    return log["tick_start"] - start


def record_event(log: dict, ev, t: float, tick: int) -> None:
    log["last_t"] = t
    if ev.done:
        log["done"][ev.request_id] = ev.completion
        return
    log["tokens"].append(Tok(ev.request_id, ev.index, t, tick))
    log["last_by_rid"][ev.request_id] = t
    if ev.index == 0:
        log["first_tick"][ev.request_id] = tick


def inter_token_gaps(log: dict) -> list[float]:
    """Every gap that ends with a token of ``log``, also one that began
    before it."""
    last = dict(log["prev_by_rid"])
    gaps = []
    for tok in log["tokens"]:
        if tok.rid in last:
            gaps.append(tok.t - last[tok.rid])
        last[tok.rid] = tok.t
    return gaps


def new_log(before: dict | None = None) -> dict:
    """A fresh window's log; what happened before it (finished requests,
    each request's last token) stays known."""
    prev = dict(before["last_by_rid"]) if before else {}
    return {"tokens": [], "done": dict(before["done"]) if before else {},
            "last_tick": before["last_tick"] if before else -1,
            "tick_start": 0.0, "prev_by_rid": prev,
            "last_by_rid": dict(prev),
            "first_tick": dict(before["first_tick"]) if before else {}}


def consume(stream: Stream, log: dict, until) -> None:
    """Record whole ticks until ``until()`` holds after one; note each
    tick's first commit time and the engine's counters then."""
    while True:
        try:
            item = stream.next()
        except StopIteration:
            raise harness.BenchError("the queue drained")
        ev, t, tick = item
        if tick != log["last_tick"]:
            if log["last_tick"] >= 0 and until():
                stream.push_back(item)
                return
            log["last_tick"], log["tick_start"] = tick, t
            log["counters"] = stream.counters()
        record_event(log, ev, t, tick)


def first_wave(stream: Stream, log: dict, slots: int) -> None:
    """Serve until ``slots`` requests have streamed a first token."""
    firsts = set()

    def done():
        firsts.update(t.rid for t in log["tokens"] if t.index == 0)
        return len(firsts) >= slots
    consume(stream, log, done)


def finish(stream: Stream, log: dict, queue, min_tokens: int) -> None:
    """Serve on, untimed, until the finished requests hold ``min_tokens``
    served tokens or ``FINISH_SECONDS`` have passed."""
    deadline = time.perf_counter() + FINISH_SECONDS

    def done():
        served = sum(queue[r].max_new for r, c in log["done"].items()
                     if c.status == "ok")
        return served >= min_tokens or time.perf_counter() >= deadline
    consume(stream, log, done)


def run(cell: harness.Cell, *, process_start: float) -> harness.Record:
    import jax

    cfg, mix, model = cell.cfg, cell.traffic, cell.model
    rec = harness.Record()
    w = jax.jit(functools.partial(model.init, cfg))(key_of(cell.seed))
    engine = build_engine(cfg, w, cell.seed)
    engine.run(warm_queue(cfg))
    queue = generate.serve_queue(mix, cfg["vocab_size"], cell.seed)
    from repro.launch.engine import Request

    requests = [Request(request_id=q.rid, prompt=q.prompt,
                        max_new_tokens=q.max_new) for q in queue]
    compiles = reduce_trace.CompileCounter()
    stream = Stream(engine, requests)
    log = new_log()
    first_wave(stream, log, cfg["serve"]["slots"])
    before = log["counters"]
    # the tick after set-up's last one began when its events were taken
    window_start = log["last_t"]
    rec.e2e["setup_s"] = window_start - process_start
    log = new_log(log)
    with compiles:
        window_s = serve_window(stream, window_start, cell.seconds, log)
    if len(log["done"]) >= len(queue):
        raise harness.BenchError("the queue drained inside the window")
    if compiles.count:
        raise harness.BenchError(f"{compiles.count} compilations inside "
                                 f"the window: {compiles.names}")
    gaps = inter_token_gaps(log)
    rec.e2e["tok_per_s"] = len(log["tokens"]) / window_s
    rec.e2e["itl_p95_ms"] = 1e3 * percentile(gaps, 95)
    after = log["counters"]
    processed = (after["prefill_tokens"] - before["prefill_tokens"]
                 + after["decode_slot_steps"] - before["decode_slot_steps"])
    rec.facts.update(window_s=window_s, itl_gaps=len(gaps),
                     itl_p50_ms=1e3 * percentile(gaps, 50),
                     processed_tokens=processed,
                     flops_per_token=counts.lm_flops_per_token(cfg),
                     generated_tokens=len(log["tokens"]),
                     finished=len(log["done"]))
    if cell.trace:
        rec.trace, work = traced_window(cell, engine, stream, log, queue)
        rec.facts["kernel_work"] = work
    finish(stream, log, queue, mix["sample_tokens"])
    stream.close()
    rec.memory_peak_bytes = harness.peak_bytes()
    del stream, engine
    # every request finished by the check, inside the window or in the
    # minute or two the engine served on to finish them
    done = list(log["done"].values())
    rec.attempted = len(done)
    rec.failed = sum(c.status != "ok"
                     or len(c.tokens) != queue[c.request_id].max_new
                     for c in done)
    rec.check("failed_requests", float(rec.failed), 0.0)
    gap, n_tok = served_logit_gap(cell, w, queue, log["done"])
    rec.facts["checked_tokens"] = n_tok
    rec.check("served_logit_gap", gap, cell.limits["served_logit_gap"])
    return rec


def percentile(values, q: float) -> float:
    return float(np.percentile(values, q)) if values else float("nan")


def sample_finished(queue, done: dict, seed: int, min_tokens: int) -> list:
    """The longest finished request, then seeded others, until
    ``min_tokens`` served tokens are in the sample."""
    ok = [rid for rid, c in done.items() if c.status == "ok"]
    if not ok:
        return []
    ok.sort(key=lambda rid: (-queue[rid].max_new, rid))
    rng = np.random.default_rng(seed)
    pick = [ok[0]]
    rest = [ok[i] for i in rng.permutation(len(ok) - 1) + 1] if \
        len(ok) > 1 else []
    total = queue[ok[0]].max_new
    for rid in rest:
        if total >= min_tokens:
            break
        pick.append(rid)
        total += queue[rid].max_new
    return pick


def served_logit_gap(cell, w, queue, done, quant=None):
    """(widest gap, tokens compared) over the sample.  With ``quant`` set,
    the gap is that of the token the lower-precision reference puts first
    at each served position (the control)."""
    cfg, model = cell.cfg, cell.model
    width = cfg["serve"]["max_len"]
    worst, n = 0.0, 0
    for rid in sample_finished(queue, done, cell.seed,
                               cell.traffic["sample_tokens"]):
        gap, k = sequence_gap(model, w, cfg, queue[rid].prompt,
                              np.asarray(done[rid].tokens), width, quant)
        worst, n = max(worst, gap), n + k
    return (worst if n else float("nan")), n


def sequence_gap(model, w, cfg, prompt, served, width, quant=None):
    """Run the reference over ``prompt + served`` (padded to ``width``:
    causal, so padding after the end changes nothing before it)."""
    import jax.numpy as jnp

    p = len(prompt)
    seq = np.zeros((width,), np.int32)
    seq[:p] = prompt
    seq[p:p + len(served) - 1] = served[:-1]
    ref = np.asarray(model.logits(w, jnp.asarray(seq), cfg))
    pos = np.arange(p - 1, p - 1 + len(served))
    best = ref[pos].max(axis=-1)
    if quant is None:
        chosen = served
    else:
        lower = np.asarray(model.logits(w, jnp.asarray(seq), cfg, quant))
        chosen = lower[pos].argmax(axis=-1)
    gaps = best - ref[pos, chosen]
    return float(gaps.max()), len(served)


def traced_window(cell, engine, stream: Stream, log: dict, queue):
    """A short traced window right after the measured one, on tick
    boundaries, then enough further ticks to learn when every request live
    in it started; returns the reduced trace and the paged-decode kernel's
    ideal work over the traced ticks.  The engine's step is looked at once,
    on its first traced call, for the shapes it was compiled for."""
    step = engine._step
    seen = []

    def look(*args):
        if not seen:
            seen.append(reduce_trace.abstract(args))
        return step(*args)

    engine._step = look
    try:
        with reduce_trace.traced(cell.out_dir) as tr:
            first = stream.tick() + 1
            serve_window(stream, time.perf_counter(), TRACE_SECONDS, log)
            last = stream.tick()
    finally:
        engine._step = step
    tr.result.attach([reduce_trace.compiled_text(step, *seen[0])])
    s = cell.cfg["serve"]
    horizon = last + math.ceil(s["max_len"] / s["prefill_chunk"]) + 1
    while log["last_tick"] <= horizon:
        try:
            ev, t, tick = stream.next()
        except StopIteration:
            break
        log["last_tick"] = tick
        record_event(log, ev, t, tick)
    evals, work = paged_decode_work(cell.cfg, queue, log["first_tick"],
                                    range(first, last + 1))
    return tr.result, {"paged_decode": work, "paged_decode_evals": evals}


def paged_decode_work(cfg: dict, queue, first_tick: dict, ticks):
    """Rebuild each traced tick's lanes from the streamed tokens and the
    engine's schedule (a request is admitted ``ceil(P / chunk)`` ticks
    before its first token, prefills a chunk a tick, then decodes one
    token a tick), and count the paged-decode kernel's ideal work over
    those ticks: each model evaluation calls it once per layer, over the
    lanes that consume a token at that evaluation."""
    s = cfg["serve"]
    chunk = s["prefill_chunk"]
    lanes_by_tick: dict[int, list[tuple[int, int]]] = {t: [] for t in ticks}
    for rid, t1 in first_tick.items():
        p, new = len(queue[rid].prompt), queue[rid].max_new
        n_pre = math.ceil(p / chunk)
        for k in range(n_pre):                  # prefill chunks
            t = t1 - n_pre + 1 + k
            if t in lanes_by_tick:
                lanes_by_tick[t].append((k * chunk, min(chunk, p - k * chunk)))
        for j in range(1, new):                 # decode steps
            t = t1 + j
            if t in lanes_by_tick:
                lanes_by_tick[t].append((p + j - 1, 1))
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    work, evals = counts.ZERO, 0
    for lanes in lanes_by_tick.values():
        if not lanes:
            continue
        n_max = max(n for _, n in lanes)
        evals += n_max
        for e in range(1, n_max + 1):
            live = [l0 + e for l0, n in lanes if e <= n]
            work = work + counts.paged_decode_call(
                live, cfg["num_attention_heads"], cfg["num_key_value_heads"],
                hd)
    layers = cfg["num_hidden_layers"]
    return evals * layers, counts.KernelWork(work.flops * layers,
                                             work.bytes * layers)
