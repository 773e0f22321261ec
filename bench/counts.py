"""Operations and bytes, counted from shapes.

Each function counts what the algorithm needs, not what a given kernel
happens to move: a share of the roofline built on these counts can reach
100% only when a kernel touches each needed byte once and wastes no work.
"""
from __future__ import annotations

import dataclasses


# -- a dense LLaMA-style decoder -------------------------------------------

def lm_matmul_params(cfg: dict) -> int:
    """Weights that every token multiplies: the attention projections, the
    gated MLP and the head (the embedding is a gather, not a product)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    h = cfg["num_attention_heads"] * hd
    g = cfg["num_key_value_heads"] * hd
    per_layer = d * h + 2 * d * g + h * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def lm_flops_per_token(cfg: dict) -> int:
    """2 FLOPs per weight per token.  Attention's score and value products
    (4 x d per cached position per layer) are left out: under 2% of a
    token at the 1024 positions these cells reach, so a utilization built
    on this count errs low, never high."""
    return 2 * lm_matmul_params(cfg)


@dataclasses.dataclass(frozen=True)
class KernelWork:
    flops: float
    bytes: float

    def __add__(self, other: "KernelWork") -> "KernelWork":
        return KernelWork(self.flops + other.flops, self.bytes + other.bytes)

    def least_seconds(self, peak_flops: float, peak_bytes: float) -> float:
        """The larger of the compute and the memory bound."""
        return max(self.flops / peak_flops, self.bytes / peak_bytes)


ZERO = KernelWork(0.0, 0.0)


def paged_decode_call(lengths, heads: int, kv_heads: int, head_dim: int,
                      itemsize: int = 2) -> KernelWork:
    """One paged-decode call over the lanes that consume a token, each
    attending to its ``lengths[i]`` cached positions: the query and the
    output once, the keys and values of live positions once (GQA: the
    ``kv_heads`` heads, not one copy per query head), and 2 FLOPs for each
    of the score and the value product per position and head channel."""
    positions = float(sum(lengths))
    lanes = len(lengths)
    q_and_out = 2 * lanes * heads * head_dim * itemsize
    kv = 2 * positions * kv_heads * head_dim * itemsize
    flops = 4 * positions * heads * head_dim
    return KernelWork(flops, q_and_out + kv)


# -- VGG ---------------------------------------------------------------------

def vgg_flops_per_image(cfg: dict) -> int:
    """Multiply-adds x 2 of every convolution and linear layer; the
    elementwise stages (bias, BN, ReLU, pooling) are under 1% and left
    out."""
    side, cin, total = cfg["image_size"], cfg["in_channels"], 0
    for stage in cfg["conv_widths"]:
        for cout in stage:
            total += 2 * side * side * 9 * cin * cout
            cin = cout
        side //= 2
    fan_in = side * side * cin
    for width in cfg["classifier_widths"] + [cfg["num_classes"]]:
        total += 2 * fan_in * width
        fan_in = width
    return total


def vgg_pool_stacks(cfg: dict) -> list[tuple[int, int, int]]:
    """``(H, W, C)`` at the input of each stage's last BN+ReLU+MaxPool
    stack, the stacks the NHWC kernels run."""
    side, out = cfg["image_size"], []
    for stage in cfg["conv_widths"]:
        out.append((side, side, stage[-1]))
        side //= 2
    return out


def nhwc_pool_stack_fwd(batch: int, h: int, w: int, c: int, n_params: int,
                        itemsize: int = 4) -> KernelWork:
    """Bias, BN, ReLU and a 2x2 max-pool in one pass: read the input and
    the ``n_params`` per-channel vectors once, write the pooled output
    once, at the true channel count; about 4 operations per input
    element."""
    x = batch * h * w * c
    y = batch * (h // 2) * (w // 2) * c
    return KernelWork(4.0 * x, (x + y + n_params * c) * itemsize)


def nhwc_pool_stack_bwd(batch: int, h: int, w: int, c: int, n_params: int,
                        itemsize: int = 4) -> KernelWork:
    """The generated backward of that stack: read the stack's input, the
    output cotangent and the parameters once, write the input cotangent
    and the parameter gradients once; about 8 operations per input
    element (recompute the chain, route the pool, two products)."""
    x = batch * h * w * c
    y = batch * (h // 2) * (w // 2) * c
    return KernelWork(8.0 * x, (2 * x + y + 2 * n_params * c) * itemsize)
