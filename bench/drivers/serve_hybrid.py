"""A queue of greedy generation requests through the serve engine, for a
model whose layers mix Mamba-2 and attention mixers with a share of
sparse experts (``granitemoehybrid``): the same offline batch as
``serve.py``, whose window, stream, inter-token gaps and sample of
finished requests this driver uses as they are.  What differs is the
model: its weights map into the program's tree of one scanned period of
distinct layers, and the engine holds two kinds of cache (paged keys and
values for the attention layer, dense per-slot state for the Mamba
layers).

Set-up, the window and ``correct`` are as in ``serve.py``: the gap by
which each served token's reference logit lies below the reference's
best, over the longest finished request and seeded others (at least
``sample_tokens`` served tokens), finished inside the window or while
the engine serves on after it.  Both the widest gap and the mean gap are
compared: the mean sees a fault in one layer of ten (RoPE turned on in
the attention layer), which moves many tokens a little, where the
widest gap lies within the tail of bfloat16's own.

Per-layer facts are ``serve.py``'s, for its readers: the median
inter-token gap, the FLOPs per processed token (every mixer, the router,
the shared expert and the head, plus one expert's FLOPs for each
assignment that landed on a held expert, counted on the device, over the
tokens processed), and in a traced run the paged-decode kernel's ideal
work, which the attention layers alone call.  A traced run also keeps
the compiled step's HLO and the model evaluations of the traced window,
for device time by scope (``scopes.py``).
"""
from __future__ import annotations

import dataclasses
import functools
import pathlib

import numpy as np

import generate
import harness
import reduce_trace

serve = harness.load_module(pathlib.Path(__file__).resolve().parent
                            / "serve.py")

#: the program's ModelConfig fields, by the configuration key they come from
SIZES = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
         "d_ff": "intermediate_size", "n_heads": "num_attention_heads",
         "n_kv_heads": "num_key_value_heads", "vocab_size": "vocab_size",
         "n_experts": "router_experts", "top_k": "num_experts_per_tok",
         "shared_expert_ff": "shared_intermediate_size",
         "ssm_state": "mamba_d_state", "ssm_head_dim": "mamba_d_head",
         "ssm_expand": "mamba_expand", "ssm_conv_width": "mamba_d_conv",
         "ssm_conv_bias": "mamba_conv_bias",
         "embedding_multiplier": "embedding_multiplier",
         "residual_multiplier": "residual_multiplier",
         "attention_multiplier": "attention_multiplier",
         "logits_scaling": "logits_scaling", "norm_eps": "rms_norm_eps",
         "dtype": "torch_dtype", "act": "hidden_act",
         "tie_embeddings": "tie_word_embeddings"}
#: the program's layer kinds, by ``layer_types`` entry
KINDS = {"mamba": "mamba_moe", "attention": "attn_moe"}


def program_config(cfg: dict):
    """The program's model config: its registered architecture with every
    size taken from the configuration's file.  Raises ``BenchError``
    before any device work when the program does not know the
    architecture."""
    try:
        from repro.configs import get_config

        base = get_config(cfg["serve"]["arch"])
    except (ImportError, KeyError) as e:
        raise harness.BenchError(f"the program cannot serve "
                                 f"{cfg['serve']['arch']!r}: {e}")
    n = cfg["num_hidden_layers"]
    period = cfg["layer_types"][:n]
    try:
        mc = dataclasses.replace(
            base, **{k: cfg[v] for k, v in SIZES.items()},
            d_head=cfg["hidden_size"] // cfg["num_attention_heads"],
            layer_pattern=tuple(KINDS[k] for k in period),
            expert_share=(cfg["first_held_expert"],
                          cfg["num_local_experts"]),
            use_rope=cfg["position_embedding_type"] != "nope")
    except TypeError as e:
        raise harness.BenchError(f"the program's config has no field: {e}")
    if mc.d_inner != cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
        raise harness.BenchError("mamba_expand * hidden_size is not "
                                 "mamba_n_heads * mamba_d_head")
    return mc


def program_params(w: dict, cfg: dict) -> dict:
    """The benchmark's weights in ``repro.models.lm``'s tree (no copy:
    the same arrays, nested).  Position ``j`` of the reference's layer
    period is the program's sub-layer ``j`` of its scanned period."""
    blocks = {}
    for j, lw in enumerate(w["layers"]):
        sub = {"norm1": {"scale": lw["input_norm"]},
               "norm2": {"scale": lw["post_norm"]},
               "moe": {"router": lw["router"], "wg": lw["wg"],
                       "wu": lw["wu"], "wd": lw["wd"],
                       "shared": {"wg": lw["shared_wg"],
                                  "wu": lw["shared_wu"],
                                  "wd": lw["shared_wd"]}}}
        if cfg["layer_types"][j] == "attention":
            sub["attn"] = {k: lw[k] for k in ("wq", "wk", "wv", "wo")}
        else:
            sub["mixer"] = {k: lw[k] for k in (
                "wz", "wx", "wB", "wC", "wdt", "dt_bias", "conv_x",
                "conv_B", "conv_C", "conv_bias", "A_log", "D")}
            sub["mixer"]["norm_scale"] = lw["gate_norm"]
            sub["mixer"]["wo"] = lw["w_out"]
        blocks[f"sub{j}"] = sub
    return {"embed": w["embed"], "final_norm": {"scale": w["final_norm"]},
            "blocks": blocks}


def build_engine(mc, cfg: dict, params, seed: int):
    import jax

    from repro.configs.base import RuntimeConfig
    from repro.launch.engine import Engine
    from repro.models import lm

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


# -- counts -------------------------------------------------------------------

def mixer_params(cfg: dict, kind: str) -> int:
    """Weights one token multiplies in a mixer of ``kind``."""
    d = cfg["hidden_size"]
    if kind == "attention":
        hd = d // cfg["num_attention_heads"]
        h = cfg["num_attention_heads"] * hd
        g = cfg["num_key_value_heads"] * hd
        return d * h + 2 * d * g + h * d
    di = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    return d * (2 * di + 2 * cfg["mamba_d_state"] + cfg["mamba_n_heads"]) \
        + di * d


def dense_flops_per_token(cfg: dict) -> int:
    """2 FLOPs per weight that every token multiplies: each layer's mixer,
    router and shared expert, and the tied head.  The Mamba recurrence
    (about 6 FLOPs per state element, 3% of a Mamba layer) and
    attention's score and value products are left out, so a utilization
    built on this count errs low, never high."""
    d = cfg["hidden_size"]
    per_layer = d * cfg["router_experts"] \
        + 3 * d * cfg["shared_intermediate_size"]
    total = sum(mixer_params(cfg, k) + per_layer
                for k in cfg["layer_types"][:cfg["num_hidden_layers"]])
    return 2 * (total + d * cfg["vocab_size"])


def expert_flops_per_assignment(cfg: dict) -> int:
    """2 FLOPs per weight of one expert, for one token routed to it."""
    return 2 * 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def flops_per_token(cfg: dict, processed: int, held: int) -> float:
    """FLOPs per processed token on this chip's share: every token's
    dense work, plus one expert's for each of the ``held`` assignments
    that landed on an expert held here."""
    return dense_flops_per_token(cfg) + held / max(processed, 1) \
        * expert_flops_per_assignment(cfg)


# -- the run ------------------------------------------------------------------

def warm(engine, cfg: dict):
    """Runs ``serve.py``'s warm queue; returns the abstract arguments of
    the engine's first step call there, the shapes every later tick runs
    at (nothing compiles in the window), for the step's HLO text."""
    step, seen = engine._step, []

    def look(*args):
        if not seen:
            seen.append(reduce_trace.abstract(args))
        return step(*args)

    engine._step = look
    try:
        engine.run(serve.warm_queue(cfg))
    finally:
        engine._step = step
    return seen[0]


def traced_window(cell, engine, stream, log: dict, queue):
    """``serve.traced_window`` with the paged-decode work counted over
    the attention layers alone (the Mamba layers call no kernel);
    returns it with the model evaluations of the traced ticks."""
    cfg = cell.cfg
    n_attn = cfg["layer_types"][:cfg["num_hidden_layers"]].count(
        "attention")
    attn = dataclasses.replace(cell, cfg={**cfg, "num_hidden_layers": n_attn})
    trace, work = serve.traced_window(attn, engine, stream, log, queue)
    return trace, work, work["paged_decode_evals"] // n_attn


def run(cell: harness.Cell, *, process_start: float) -> harness.Record:
    cfg, mix, model = cell.cfg, cell.traffic, cell.model
    mc = program_config(cfg)
    import jax

    rec = harness.Record()
    w = jax.jit(functools.partial(model.init, cfg))(serve.key_of(cell.seed))
    engine = build_engine(mc, cfg, program_params(w, cfg), cell.seed)
    shapes = warm(engine, cfg)
    queue = generate.serve_queue(mix, cfg["vocab_size"], cell.seed)
    from repro.launch.engine import Request

    requests = [Request(request_id=q.rid, prompt=q.prompt,
                        max_new_tokens=q.max_new) for q in queue]
    compiles = reduce_trace.CompileCounter()
    stream = serve.Stream(engine, requests)
    log = serve.new_log()
    serve.first_wave(stream, log, cfg["serve"]["slots"])
    before = log["counters"]
    window_start = log["last_t"]
    rec.e2e["setup_s"] = window_start - process_start
    log = serve.new_log(log)
    with compiles:
        window_s = serve.serve_window(stream, window_start, cell.seconds,
                                      log)
    if len(log["done"]) >= len(queue):
        raise harness.BenchError("the queue drained inside the window")
    if compiles.count:
        raise harness.BenchError(f"{compiles.count} compilations inside "
                                 f"the window: {compiles.names}")
    gaps = serve.inter_token_gaps(log)
    rec.e2e["tok_per_s"] = len(log["tokens"]) / window_s
    rec.e2e["itl_p95_ms"] = 1e3 * serve.percentile(gaps, 95)
    after = log["counters"]

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    processed = delta("prefill_tokens") + delta("decode_slot_steps")
    held = delta("moe_held_assignments")
    rec.facts.update(
        window_s=window_s, itl_gaps=len(gaps),
        itl_p50_ms=1e3 * serve.percentile(gaps, 50),
        processed_tokens=processed, held_assignments=held,
        routed_assignments=delta("moe_routed_assignments"),
        ssm_state_updates=delta("ssm_state_updates"),
        flops_per_token=flops_per_token(cfg, processed, held),
        generated_tokens=len(log["tokens"]), finished=len(log["done"]))
    if cell.trace:
        rec.trace, work, evals = traced_window(cell, engine, stream, log,
                                               queue)
        rec.facts.update(
            kernel_work=work, traced_evaluations=evals,
            step_hlo=reduce_trace.compiled_text(engine._step, *shapes))
    serve.finish(stream, log, queue, mix["sample_tokens"])
    stream.close()
    rec.memory_peak_bytes = harness.peak_bytes()
    del stream, engine
    done = list(log["done"].values())
    rec.attempted = len(done)
    rec.failed = sum(c.status != "ok"
                     or len(c.tokens) != queue[c.request_id].max_new
                     for c in done)
    rec.check("failed_requests", float(rec.failed), 0.0)
    gaps = served_gaps(cell, w, queue, log["done"])
    rec.facts["checked_tokens"] = len(gaps)
    for name, reduce in (("served_logit_gap", np.max),
                         ("served_logit_gap_mean", np.mean)):
        value = float(reduce(gaps)) if len(gaps) else float("nan")
        rec.check(name, value, cell.limits[name])
    return rec


def served_gaps(cell, w, queue, done, quant=None) -> np.ndarray:
    """Each served token's gap over ``serve.py``'s sample (its
    ``served_logit_gap`` is their widest): how far the token's reference
    logit lies below the reference's best at its position.  With
    ``quant``, the gap of the token that the lower-precision reference
    puts first there (the control)."""
    import jax.numpy as jnp

    cfg, model = cell.cfg, cell.model
    width = cfg["serve"]["max_len"]
    out = []
    for rid in serve.sample_finished(queue, done, cell.seed,
                                     cell.traffic["sample_tokens"]):
        prompt, served = queue[rid].prompt, np.asarray(done[rid].tokens)
        p = len(prompt)
        seq = np.zeros((width,), np.int32)
        seq[:p] = prompt
        seq[p:p + len(served) - 1] = served[:-1]
        pos = np.arange(p - 1, p - 1 + len(served))
        ref = np.asarray(model.logits(w, jnp.asarray(seq), cfg))[pos]
        chosen = served if quant is None else np.asarray(
            model.logits(w, jnp.asarray(seq), cfg, quant))[pos].argmax(-1)
        out.append(ref.max(axis=-1) - ref[np.arange(len(pos)), chosen])
    return np.concatenate(out) if out else np.zeros(0)
