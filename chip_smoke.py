#!/usr/bin/env python3
"""Bring-up smoke: the main paths, compiled, on one TPU.

    python chip_smoke.py            # one chip: serve, kernels, paper path
    python chip_smoke.py --chips 4  # four chips: the mesh-sharded engine only

Phases, in one process, in order (each prints what it checked on its own
line; the first failure exits non-zero and nothing else is printed):

* serve   — ``Server(...).engine(...)`` on deepseek-7b at its published
  widths (d_model 4096, 32 heads of 128, d_ff 11008, vocab 102400, bf16
  weights from a seed), cut to 8 of 30 layers; ``mode="brainslug"``,
  ``kv_layout="paged"``, ``verify_mode="strict"``.  A ragged queue of
  prompts a few hundred tokens long must complete through the Pallas
  paged-decode path, and one ``lm.decode_step`` in ``brainslug`` and one
  in ``xla`` from the same cache must agree on logits.
* kernels — each main-path kernel at those widths against its ``ref.py``
  twin: paged decode, rmsnorm, swiglu, the rows fused stack (forward and
  generated backward), flash attention, vocab-CE.
* paper   — ``api.optimize(cnn.vgg_fn, ...)`` with
  ``OptimizeConfig(mode="brainslug", differentiable=True,
  verify="strict")`` on 224x224 ImageNet-sized images; forward and
  ``jax.grad`` must match the raw function, and ``explain()`` must show
  the nhwc stacks running as generated kernels.
* mesh (``--chips 4`` only) — the engine over the four devices in the
  ``data`` and ``tensor`` placements against the same engine on one
  device, same process, same queue: greedy tokens must be identical.

The script refuses to run anywhere but on a TPU, and never falls back to
the CPU.  The last line of its output is one JSON object naming the device.
Autotuning stays off, so a kernel that fails to compile cannot be floored
away.  Times are printed for orientation only.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ARCH = "deepseek-7b"
SLOTS = 8


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def rel_err(got, want) -> float:
    """||got - want|| / ||want|| in float64 (0 when both are zero)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), "non-finite values")
    den = np.linalg.norm(want)
    return float(np.linalg.norm(got - want) / den) if den else 0.0


def close(name: str, got, want, tol: float) -> float:
    err = rel_err(got, want)
    check(err <= tol, f"{name}: relative error {err:.3e} > {tol:g}")
    return err


# Tolerances, each with its reason.
#: bf16 kernel outputs against a jnp twin that accumulates in float32 too:
#: the two round the same float32 values to bf16 at different points (the
#: kernel keeps its chain in VMEM in float32), one bf16 rounding is 2^-9
#: relative, and a few of them stack up — 1e-2 is about 5 bf16 ulps.
TOL_BF16 = 1e-2
#: float32 outputs of bf16 operands (vocab-CE log-probs): both sides form
#: the same bf16 x bf16 products in float32 and differ only in the order of
#: the 4096-term sums and of the online log-sum-exp.
TOL_F32_OF_BF16 = 1e-3
#: logits of the 8-layer bf16 model, brainslug against xla: every layer
#: rounds its activations to bf16 at different points on the two paths
#: (fused kernels vs XLA fusions) and the differences compound through 8
#: residual blocks and the 102400-wide head.
TOL_LOGITS = 3e-2
#: the float32 paper path under float32 matmul precision: convolutions and
#: the head are the same XLA ops on both sides; the generated stacks reorder
#: no arithmetic, so only float32 reassociation across fusions remains.
TOL_F32 = 1e-4
#: gradients of that path: ReLU and max-pool routing are discontinuous, so
#: a pre-activation within float32 rounding of zero (the forward passes
#: differ by ~1e-7 relative) can take the other branch on the other path and
#: move one element's whole gradient; among the 8x224x224x64 first-stage
#: activations a few dozen such flips give ~1e-3 relative L2 in d/dx.
TOL_GRAD = 5e-3


def tree_bytes(tree) -> int:
    import jax
    return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
               for x in jax.tree_util.tree_leaves(tree))


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def reg_refs() -> dict[str, int]:
    from repro.core import registry
    return {k: v for k, v in registry.STATS.counts.items()
            if k.endswith("_ref")}


def serve_phase(*, arch: str = ARCH, n_layers: int = 8, max_len: int = 1024,
                prompt_lens=(448, 384, 320, 256, 224, 192),
                new_tokens=(48, 16, 32, 24, 40, 20), prefill_chunk: int = 64,
                seed: int = 0, reduced: bool = False) -> str:
    """Engine on the published widths cut in depth, then decode-step
    parity between the brainslug and the xla path from one cache."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import RuntimeConfig
    from repro.kernels.fused_stack import ops as fused_ops
    from repro.launch.engine import Request
    from repro.launch.serve import ServeConfig, Server
    from repro.models import lm

    sc = ServeConfig(arch=arch, reduced=reduced, mode="brainslug",
                     batch=SLOTS, max_len=max_len, seed=seed,
                     config_overrides=(("n_layers", n_layers),))
    server = Server(sc)
    cfg = server.cfg
    eng = server.engine(slots=SLOTS, prefill_chunk=prefill_chunk,
                        kv_layout="paged", kv_block_size=16,
                        verify_mode="strict")
    rng = np.random.default_rng(seed)
    reqs = [Request(request_id=i,
                    prompt=rng.integers(0, cfg.vocab_size, (p,)).astype(
                        np.int32),
                    max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompt_lens, new_tokens))]
    fused_before = fused_ops.STATS.snapshot()
    refs_before = reg_refs()
    t0 = time.perf_counter()
    comps = eng.run(reqs)
    run_s = time.perf_counter() - t0
    bad = [(c.request_id, c.status, c.reason) for c in comps
           if c.status != "ok"]
    check(not bad, f"requests failed: {bad}")
    for r, c in zip(reqs, comps):
        check(len(c.tokens) == r.max_new_tokens,
              f"request {r.request_id}: {len(c.tokens)} tokens, "
              f"wanted {r.max_new_tokens}")
    rep = eng.report()
    check(rep["decode_path"] == "pallas-paged-decode",
          f"decode path {rep['decode_path']} ({rep['decode_fallback']})")
    attn = rep["attn_dispatch"]
    check(attn.get("paged_decode_pallas", 0) > 0
          and attn.get("paged_decode_ref", 0) == 0,
          f"attention dispatch {attn}")
    fused = fused_ops.STATS.delta(fused_before)
    check(fused["fwd_reference"] == 0 and fused["bwd_reference"] == 0,
          f"fused-stack reference dispatch {fused}")
    check(reg_refs() == refs_before, f"registry ref dispatch {reg_refs()}")
    cache_bytes = tree_bytes(jax.eval_shape(
        lambda: lm.init_decode_cache(
            cfg, SLOTS, max_len, dtype=jnp.float32, kv_layout="paged",
            kv_num_blocks=eng.kv_num_blocks, kv_block_size=16)))
    weight_bytes = tree_bytes(server.params)
    peak = peak_bytes()

    # decode-step parity: fill a paged cache through the xla path, then one
    # step in each mode from that same cache
    bs, n_pos = 16, 128
    mb = n_pos // bs
    rt_b = RuntimeConfig(mode="brainslug", kv_layout="paged",
                         kv_block_size=bs)
    rt_x = dataclasses.replace(rt_b, mode="xla")
    table = jnp.arange(SLOTS * mb, dtype=jnp.int32).reshape(SLOTS, mb)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (SLOTS, 65)),
                       jnp.int32)
    cache = lm.init_decode_cache(cfg, SLOTS, n_pos, dtype=jnp.float32,
                                 kv_layout="paged",
                                 kv_num_blocks=SLOTS * mb, kv_block_size=bs)

    @jax.jit
    def fill(params, cache, toks):
        def body(t, c):
            tok = jax.lax.dynamic_slice_in_dim(toks, t, 1, axis=1)
            return lm.decode_step(params, c, tok, cfg, rt_x,
                                  block_tables=table)[1]
        return jax.lax.fori_loop(0, toks.shape[1] - 1, body, cache)

    cache = fill(server.params, cache, toks)

    def step(rt):
        fn = jax.jit(lambda p, c, t: lm.decode_step(
            p, c, t, cfg, rt, block_tables=table)[0])
        return np.asarray(fn(server.params, cache, toks[:, -1:]),
                          np.float32)

    logit_err = close("decode_step logits brainslug vs xla", step(rt_b),
                      step(rt_x), TOL_LOGITS)
    n_tok = sum(len(c.tokens) for c in comps)
    return (f"[serve] PASS {arch} d_model={cfg.d_model} heads={cfg.n_heads}"
            f"x{cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
            f"layers={cfg.n_layers}/{server_layers(arch)} "
            f"dtype={cfg.dtype}; {len(comps)} requests ok, prompts "
            f"{min(prompt_lens)}-{max(prompt_lens)} tok, {n_tok} generated; "
            f"decode_path={rep['decode_path']}; verify=strict; "
            f"decode_step rel_err brainslug/xla={logit_err:.3e} "
            f"(tol {TOL_LOGITS:g}); weights={weight_bytes} B "
            f"kv_cache={cache_bytes} B peak_bytes_in_use={peak}; "
            f"run {run_s:.1f}s incl. compile")


def server_layers(arch: str) -> int:
    from repro.configs import get_config
    return get_config(arch).n_layers


def kernel_phase(*, d_model: int = 4096, heads: int = 32,
                 head_dim: int = 128, d_ff: int = 11008,
                 vocab: int = 102400, rows: int = 256, seq: int = 1024,
                 n_pos: int = 1024, seed: int = 0) -> str:
    """Each main-path kernel against its jnp twin at the serve widths."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.attention import ops as attn_ops
    from repro.kernels.attention import ref as attn_ref
    from repro.kernels.fused_stack import ops as fused_ops
    from repro.kernels.fused_stack import ref as fs_ref
    from repro.kernels.rmsnorm import ops as rms_ops
    from repro.kernels.rmsnorm import ref as rms_ref
    from repro.kernels.swiglu import ops as sw_ops
    from repro.kernels.swiglu import ref as sw_ref
    from repro.kernels.vocab_ce import ops as ce_ops
    from repro.kernels.vocab_ce import ref as ce_ref
    from repro.layers import stacks

    bf = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))

    def normal(shape, dtype=bf, scale=1.0):
        return (scale * jax.random.normal(next(keys), shape,
                                          jnp.float32)).astype(dtype)

    errs: dict[str, float] = {}
    fused_before = fused_ops.STATS.snapshot()

    # paged decode: a shuffled block table over the pool, ragged lengths
    bs = 16
    mb = n_pos // bs
    q = normal((SLOTS, heads, 1, head_dim))
    kp = normal((SLOTS * mb, heads, bs, head_dim))
    vp = normal((SLOTS * mb, heads, bs, head_dim))
    table = jax.random.permutation(next(keys), SLOTS * mb).astype(
        jnp.int32).reshape(SLOTS, mb)
    lengths = jax.random.randint(next(keys), (SLOTS,), 1, n_pos + 1,
                                 jnp.int32)
    errs["paged_decode"] = close(
        "paged_decode",
        jax.jit(attn_ops.paged_flash_decode)(q, kp, vp, table, lengths),
        attn_ref.paged_decode_ref(q, kp, vp, table, lengths), TOL_BF16)

    # residual add + rmsnorm
    x, res = normal((rows, d_model)), normal((rows, d_model))
    scale = normal((d_model,), scale=0.1) + 1.0
    y, h = jax.jit(rms_ops.rmsnorm)(x, scale, res)
    yr, hr = rms_ref.rmsnorm_ref(x, scale, res)
    errs["rmsnorm"] = max(close("rmsnorm y", y, yr, TOL_BF16),
                          close("rmsnorm h", h, hr, TOL_BF16))

    # swiglu, the dedicated kernel
    g, u = normal((rows, d_ff)), normal((rows, d_ff))
    errs["swiglu"] = close("swiglu", jax.jit(sw_ops.swiglu)(g, u),
                           sw_ref.swiglu_ref(g, u), TOL_BF16)

    # the generic rows stack (same GLU chain): forward and the generated
    # backward against jax.vjp of the reference interpreter
    prog = stacks.glu_program("silu")

    def fused(g_, u_):
        return fused_ops.fused_stack_apply(
            prog, {"gate": g_, "up": u_}, {}, mode="brainslug")["y"]

    def reference(g_, u_):
        return fs_ref.fused_stack_ref(prog, {"gate": g_, "up": u_}, {})["y"]

    cot = normal((rows, d_ff))
    yk, vjp_k = jax.vjp(fused, g, u)
    yr, vjp_r = jax.vjp(reference, g, u)
    errs["rows_stack"] = close("rows stack fwd", jax.jit(fused)(g, u), yr,
                               TOL_BF16)
    del yk
    dk = jax.jit(lambda c: vjp_k(c))(cot)
    dr = vjp_r(cot)
    errs["rows_stack_bwd"] = max(
        close("rows stack d_gate", dk[0], dr[0], TOL_BF16),
        close("rows stack d_up", dk[1], dr[1], TOL_BF16))
    fused_delta = fused_ops.STATS.delta(fused_before)
    check(fused_delta["bwd_generated"] > 0
          and fused_delta["bwd_reference"] == 0
          and fused_delta["fwd_reference"] == 0,
          f"rows stack dispatch {fused_delta}")

    # flash attention (causal, the training/prefill forward)
    qa = normal((1, heads, seq, head_dim))
    ka = normal((1, heads, seq, head_dim))
    va = normal((1, heads, seq, head_dim))
    errs["flash_attention"] = close(
        "flash_attention",
        jax.jit(attn_ops.flash_attention)(qa, ka, va),
        attn_ref.attention_ref(qa, ka, va, causal=True), TOL_BF16)

    # vocab cross-entropy over the full 102400 vocabulary
    hh = normal((rows, d_model))
    w = normal((d_model, vocab), scale=d_model ** -0.5)
    labels = jax.random.randint(next(keys), (rows,), 0, vocab, jnp.int32)
    errs["vocab_ce"] = close(
        "vocab_ce", jax.jit(ce_ops.fused_gold_logp)(hh, w, labels),
        ce_ref.gold_logp_ref(hh, w, labels), TOL_F32_OF_BF16)

    listed = " ".join(f"{k}={v:.2e}" for k, v in errs.items())
    return (f"[kernels] PASS compiled kernels vs ref.py twins at "
            f"d_model={d_model} heads={heads}x{head_dim} d_ff={d_ff} "
            f"vocab={vocab}, bf16: rel_err {listed} (tol bf16 "
            f"{TOL_BF16:g}, f32 {TOL_F32_OF_BF16:g}); rows stack "
            f"bwd_generated={fused_delta['bwd_generated']} "
            f"bwd_reference=0")


def paper_phase(*, batch: int = 8, image: int = 224,
                stages=(64, 128, 256, 512, 512), seed: int = 0) -> str:
    """The paper's own path: optimize() on a VGG, forward and gradient
    against the raw function."""
    import jax
    import jax.numpy as jnp

    from repro import api
    from repro.kernels.fused_stack import ops as fused_ops
    from repro.models import cnn

    _, params = cnn.vgg_net(stages=stages, n_classes=1000,
                            key=jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (batch, image, image, 3), jnp.float32)
    fused_before = fused_ops.STATS.snapshot()
    refs_before = reg_refs()
    with jax.default_matmul_precision("float32"):
        net = api.optimize(cnn.vgg_fn, x, params,
                           config=api.OptimizeConfig(
                               mode="brainslug", differentiable=True,
                               verify="strict"))
        y = jax.jit(net)(x, params)
        y_ref = jax.jit(cnn.vgg_fn)(x, params)
        fwd_err = close("vgg forward", y, y_ref, TOL_F32)

        def loss(f):
            return lambda x_, p_: jnp.sum(jnp.square(f(x_, p_)))

        g = jax.jit(jax.grad(loss(net), argnums=(0, 1)))(x, params)
        g_ref = jax.jit(jax.grad(loss(cnn.vgg_fn), argnums=(0, 1)))(x,
                                                                   params)
    grad_err = max(
        close(f"vgg grad {jax.tree_util.keystr(path)}", leaf, want,
              TOL_GRAD)
        for (path, leaf), want in zip(
            jax.tree_util.tree_leaves_with_path(g),
            jax.tree_util.tree_leaves(g_ref)))
    rep = net.report()
    text = net.explain()
    nhwc = [s for s in rep.stacks if s.layout == "nhwc"]
    check(len(nhwc) == len(stages),
          f"{len(nhwc)} nhwc stacks, wanted {len(stages)}:\n{text}")
    check(all(s.execution == "generated" for s in nhwc),
          f"nhwc stacks not generated:\n{text}")
    check(not rep.verify, f"verify findings: {rep.verify}")
    check(not rep.kernel_fallbacks, f"kernel fallbacks "
                                    f"{rep.kernel_fallbacks}")
    check(reg_refs() == refs_before, f"registry ref dispatch {reg_refs()}")
    fused = fused_ops.STATS.delta(fused_before)
    check(fused["fwd_generated"] > 0 and fused["bwd_generated"] > 0
          and fused["fwd_reference"] == 0 and fused["bwd_reference"] == 0,
          f"fused-stack dispatch {fused}")
    for line in text.splitlines():
        print(f"[paper]   {line}")
    return (f"[paper] PASS optimize(vgg_fn) {batch}x{image}x{image}x3 "
            f"stages={list(stages)}: {len(nhwc)} nhwc stacks generated, "
            f"forward rel_err={fwd_err:.2e} (tol {TOL_F32:g}), grad "
            f"rel_err={grad_err:.2e} (tol {TOL_GRAD:g}), float32 matmul "
            f"precision; "
            f"fused dispatch {fused}")


def mesh_phase(*, arch: str = ARCH, n_layers: int = 2, max_len: int = 128,
               n_devices: int = 4, seed: int = 0,
               reduced: bool = False) -> str:
    """The mesh-sharded engine over ``n_devices`` against the same engine
    on one device: slots over "data" (dense cache) and heads over "model"
    (paged cache), greedy tokens identical."""
    import jax

    from repro.launch.engine import Request
    from repro.launch.mesh import make_test_mesh
    from repro.launch.serve import ServeConfig, Server

    # float32 weights at float32 matmul precision: the placements may only
    # reorder float32 sums (the tensor split's output-projection psum), so
    # exact greedy-token parity is the bar, as in the CPU mesh suite
    overrides = (("n_layers", n_layers), ("dtype", "float32"))
    sc = ServeConfig(arch=arch, reduced=reduced, mode="brainslug",
                     batch=SLOTS, max_len=max_len, seed=seed,
                     config_overrides=overrides)
    check(len(jax.devices()) >= n_devices,
          f"{len(jax.devices())} devices, wanted {n_devices}")
    lines = []
    with jax.default_matmul_precision("float32"):
        server = Server(sc)
        rng = np.random.default_rng(seed)
        reqs = [Request(request_id=i,
                        prompt=rng.integers(0, server.cfg.vocab_size,
                                            (int(rng.integers(8, 64)),)
                                            ).astype(np.int32),
                        max_new_tokens=int(rng.integers(4, 12)))
                for i in range(12)]
        for placement, layout, mesh in (
                ("data", "dense", make_test_mesh(n_devices)),
                ("tensor", "paged",
                 make_test_mesh(n_devices, model_parallel=n_devices))):
            kw = dict(slots=SLOTS, prefill_chunk=16, kv_layout=layout,
                      verify_mode="strict")
            base = server.engine(**kw).run(reqs)
            eng = server.engine(mesh=mesh, serve_partition=placement, **kw)
            out = eng.run(reqs)
            for a, b in zip(base, out):
                check(a.status == "ok" and b.status == "ok",
                      f"{placement}: request {a.request_id} "
                      f"{a.status}/{b.status} {b.reason}")
                check(a.tokens.tolist() == b.tokens.tolist(),
                      f"{placement}: request {a.request_id} tokens "
                      f"{b.tokens.tolist()} != {a.tokens.tolist()}")
            rep = eng.report()
            part = rep["serve_partition"]
            want = {"data": (True, False), "tensor": (False, True)}
            check((part["data"], part["model"]) == want[placement],
                  f"{placement}: plan {part}")
            check(rep["decode_path"].startswith("pallas"),
                  f"{placement}: decode path {rep['decode_path']}")
            lines.append(f"{placement}({layout}, mesh {rep['mesh_axes']}, "
                         f"{rep['decode_path']})")
    n_tok = sum(len(c.tokens) for c in base)
    return (f"[mesh] PASS {arch} widths, {n_layers} layers, float32: "
            f"{len(reqs)} requests ({n_tok} tokens) token-identical to the "
            f"one-device engine in " + ", ".join(lines))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded engine phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax

    from repro.launch import compile_cache

    compile_cache.configure()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"this smoke runs on the chip only", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: {len(devices)} TPU device(s), --chips "
              f"{args.chips} needs {args.chips}", file=sys.stderr)
        return 1
    print(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache "
          f"{jax.config.jax_compilation_cache_dir}", flush=True)

    phases = ([("mesh", mesh_phase)] if args.chips == 4 else
              [("serve", serve_phase), ("kernels", kernel_phase),
               ("paper", paper_phase)])
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            line = phase(seed=args.seed)
        except Exception as e:  # report which phase failed, then stop
            import traceback
            traceback.print_exc()
            print(f"[{name}] FAIL {type(e).__name__}: {e}", file=sys.stderr)
            return 1
        print(f"{line} [{time.perf_counter() - t0:.1f}s]", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
