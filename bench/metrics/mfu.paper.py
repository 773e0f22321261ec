"""Model FLOP utilization of the optimized image-model step over the
measured window (%): images times the model's FLOPs per image (forward,
or three times it for a training step), over the window, over the chip's
bf16 peak.  Moves ``img_per_s``."""


def read(rec, peaks):
    f = rec.facts
    if not f.get("window_s") or "steps" not in f:
        return None
    rate = f["steps"] * f["batch"] * f["flops_per_image"] / f["window_s"]
    return 100.0 * rate / peaks["bf16_flops_per_s"]
