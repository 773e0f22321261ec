"""Model FLOP utilization of the served model over the measured window
(%): prompt and generated tokens the engine processed (its
``prefill_tokens`` and ``decode_slot_steps`` counters) times the model's
FLOPs per token, over the window, over the chip's bf16 peak.  Moves
``tok_per_s``."""


def read(rec, peaks):
    f = rec.facts
    if not f.get("window_s") or "processed_tokens" not in f:
        return None
    rate = f["processed_tokens"] * f["flops_per_token"] / f["window_s"]
    return 100.0 * rate / peaks["bf16_flops_per_s"]
