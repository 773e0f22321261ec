"""Closed loop of image-model steps through ``repro.api.optimize``.

The model is the configuration's plain function (``bench/configs/<config>.py``
``make_forward``), handed to ``optimize()`` as a user would.  A ``train``
mix runs forward, backward and an SGD-momentum update per step; an
``infer`` mix runs the forward alone.  Steps are dispatched back to back
with one in flight behind the one the host waits for, as a training loop
does; the window closes on ``block_until_ready`` of its last step.

``correct``:

* train — set-up drives the compiled step, with its state, through its
  first three steps on distinct batches, and hands that same step and
  state to the window.  The plain function, jitted by XLA alone at
  ``"highest"`` precision, follows the same three steps from the same
  weights.  Compared: each step's loss (relative gap), the first gradient
  as the optimizer holds it (the momentum buffer after step 1) and the
  parameters' change after step 3, each by its worst leaf: the gap
  between the program's norm and the reference's over the larger of the
  reference leaf's norm and the median leaf's.  Leaves whose reference
  gradient is under a thousandth of the median leaf's are left out.
* infer — the logits of a seeded sample of the window's steps against the
  reference forward on the same batches: the worst row's relative L2 gap.
"""
from __future__ import annotations

import functools
import time

import numpy as np

import harness
import generate
import reduce_trace

TRACE_SECONDS = 3.0
SETUP_STEPS = 3
#: a leaf whose reference gradient norm is under this share of the median
#: leaf's moves by round-off alone and is left out of the gradient and
#: change comparison
GRAD_FLOOR = 1e-3


def key_of(seed: int):
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31),
                              seed // 2 ** 31)


def make_train_step(loss_of, opt: dict):
    """SGD with momentum and weight decay, as torch.optim.SGD applies it:
    g = grad + wd * p;  m = mu * m + g;  p = p - lr * m."""
    import jax

    lr, mu, wd = opt["lr"], opt["momentum"], opt["weight_decay"]

    def step(p, m, x, y):
        loss, g = jax.value_and_grad(loss_of)(p, x, y)
        g = jax.tree_util.tree_map(lambda g_, p_: g_ + wd * p_, g, p)
        m = jax.tree_util.tree_map(lambda m_, g_: mu * m_ + g_, m, g)
        p = jax.tree_util.tree_map(lambda p_, m_: p_ - lr * m_, p, m)
        return p, m, loss

    return step


def leaf_norms(tree: dict) -> dict:
    import jax.numpy as jnp

    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def worst_leaf_gap(prog: dict, ref: dict, keep) -> float:
    """max over kept leaves of |prog - ref| / max(ref, median ref)."""
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def first_steps(step, params, batches) -> tuple:
    """Drive ``step`` (donating its state) from ``params`` through the
    first ``SETUP_STEPS`` steps on distinct batches.  Returns the state
    after them and the numbers compared: each step's loss, the momentum
    buffer's leaf norms after step 1 and the parameters' change norms
    after the last step (``params`` is donated: the change is taken
    against ``init``, called again)."""
    import jax
    import jax.numpy as jnp

    state = (params, jax.tree_util.tree_map(jnp.zeros_like, params))
    losses, mom1 = [], None
    for i in range(SETUP_STEPS):
        p, m, loss = step(*state, *batches[i])
        state = (p, m)
        losses.append(loss)
        if i == 0:
            mom1 = jax.jit(leaf_norms)(m)
    return state, {"losses": losses, "mom1": mom1}


def change_norms(p, p0) -> dict:
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))(p, p0)


def to_host(numbers: dict) -> dict:
    import jax

    out = jax.device_get(numbers)
    return {"losses": [float(v) for v in out["losses"]],
            "mom1": {k: float(v) for k, v in out["mom1"].items()},
            "change": {k: float(v) for k, v in out["change"].items()}}


def compare_train(prog: dict, ref: dict) -> dict:
    """The three compared numbers of a train cell, and the leaves left
    out of the last two."""
    med = float(np.median(list(ref["mom1"].values())))
    keep = [k for k, v in ref["mom1"].items() if v >= GRAD_FLOOR * med]
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(prog["losses"], ref["losses"])),
        "grad_norm_gap": worst_leaf_gap(prog["mom1"], ref["mom1"], keep),
        "change_norm_gap": worst_leaf_gap(prog["change"], ref["change"],
                                          keep),
    }, sorted(set(ref["mom1"]) - set(keep))


def reference_numbers(cell, init_params, batches,
                      precision: str = "highest") -> dict:
    """The plain function, jitted by XLA alone at ``precision``, through
    the same first steps from the same weights."""
    import jax

    model, cfg = cell.model, cell.cfg
    forward = model.make_forward(cfg)
    with jax.default_matmul_precision(precision):
        step = jax.jit(make_train_step(
            lambda p, x, y: model.cross_entropy(forward(x, p), y),
            cfg["optimizer"]), donate_argnums=(0, 1))
        state, numbers = first_steps(step, init_params(), batches)
        numbers["change"] = change_norms(state[0], init_params())
    return to_host(numbers)


def reference_logits(cell, params, images, precision: str = "highest"):
    import jax

    forward = jax.jit(cell.model.make_forward(cell.cfg))
    with jax.default_matmul_precision(precision):
        return np.asarray(jax.device_get(forward(images, params)),
                          np.float64)


def worst_row_gap(got, want) -> float:
    rows = np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
    return float(np.max(rows))


def program_step(net, cell: harness.Cell):
    """The timed step around the optimized forward ``net``: for a train
    mix, loss, gradient and update; for an infer mix, the forward."""
    if cell.traffic["mode"] != "train":
        return net
    return make_train_step(
        lambda p, x, y: cell.model.cross_entropy(net(x, p), y),
        cell.cfg["optimizer"])


def build(cell: harness.Cell, params, example):
    """``optimize()`` the configuration's forward and jit the cell's step
    around it; returns (step, seconds optimize() took)."""
    import jax

    from repro import api

    train = cell.traffic["mode"] == "train"
    t = time.perf_counter()
    net = api.optimize(cell.model.make_forward(cell.cfg), example, params,
                       config=api.OptimizeConfig(
                           differentiable=train,
                           **cell.cfg["optimize_config"]))
    seconds = time.perf_counter() - t
    step = program_step(net, cell)
    return jax.jit(step, donate_argnums=(0, 1) if train else ()), seconds


def inputs(cell: harness.Cell, seed: int):
    """``(init_params, batches)``: a function that makes the weights on
    the device (call it again for a fresh copy), and the batch pool."""
    import jax

    kw, kd = jax.random.split(key_of(seed))
    init = jax.jit(functools.partial(cell.model.init, cell.cfg))
    images, labels = jax.jit(functools.partial(
        generate.image_pool, cell.traffic, cell.cfg))(kd)
    return (lambda: init(kw)), [(images[i], labels[i])
                                for i in range(cell.traffic["pool"])]


def run(cell: harness.Cell, *, process_start: float) -> harness.Record:
    import jax

    cfg, mix = cell.cfg, cell.traffic
    train = mix["mode"] == "train"
    rec = harness.Record()
    init_params, batches = inputs(cell, cell.seed)
    precision = cfg["matmul_precision"]
    with jax.default_matmul_precision(precision):
        params = init_params()
        step, rec.facts["optimize_s"] = build(cell, params, batches[0][0])
        if train:
            state, prog = first_steps(step, params, batches)
            del params
            prog["change"] = change_norms(state[0], init_params())
            prog = to_host(prog)

            def call(i):
                nonlocal state
                p, m, loss = step(*state, *batches[i % len(batches)])
                state = (p, m)
                return loss
        else:
            jax.block_until_ready(step(batches[0][0], params))

            def call(i):
                return step(batches[i % len(batches)][0], params)

        window_start = time.perf_counter()
        rec.e2e["setup_s"] = window_start - process_start
        n_steps, window_s, kept = closed_loop(
            call, cell.seconds, first=SETUP_STEPS if train else 0,
            keep=sample_steps(cell, mix))
        batch = mix["batch"]
        rec.e2e["img_per_s"] = n_steps * batch / window_s
        rec.facts.update(window_s=window_s, steps=n_steps, batch=batch,
                         flops_per_image=_flops_per_image(cfg, train))
        if cell.trace:
            rec.trace, rec.facts["traced_steps"] = traced_loop(cell, call)
            args = ((*state, *batches[0]) if train
                    else (batches[0][0], params))
            rec.trace.attach([reduce_trace.compiled_text(step, *args)])
            rec.facts["kernel_work"], rec.facts["kernel_calls"] = \
                kernel_work(cfg, batch, train)
    rec.memory_peak_bytes = harness.peak_bytes()
    rec.attempted = n_steps

    # the program's state is done with: free it before the reference runs
    outputs = {i: np.asarray(jax.device_get(v), np.float64)
               for i, v in kept.items()}
    del kept, call, step
    if train:
        del state
        readings, rec.facts["leaves_left_out"] = compare_train(
            prog, reference_numbers(cell, init_params, batches))
        rec.failed = sum(not np.isfinite(v) for v in prog["losses"])
    else:
        params = init_params()
        readings = {"logit_row_gap": max(
            (worst_row_gap(y, reference_logits(
                cell, params, batches[i % len(batches)][0]))
             for i, y in outputs.items()), default=float("nan"))}
        rec.failed = sum(not np.all(np.isfinite(y))
                         for y in outputs.values())
    for name, value in readings.items():
        rec.check(name, value, cell.limits[name])
    return rec


def sample_steps(cell: harness.Cell, mix: dict) -> set[int] | None:
    if mix["mode"] == "train":
        return None
    rng = np.random.default_rng(cell.seed)
    # steps surely inside any window: the first few dozen
    return set(int(i) for i in rng.choice(32, mix["sample_steps"],
                                          replace=False))


def _flops_per_image(cfg: dict, train: bool) -> float:
    import counts

    f = counts.vgg_flops_per_image(cfg)
    return 3.0 * f if train else float(f)


def closed_loop(call, seconds: float, *, first: int = 0,
                keep: set[int] | None = None):
    """Dispatch ``call(i)`` back to back for ``seconds``; returns (steps
    completed, window seconds, {i: output} for i in ``keep``).  One step
    stays in flight behind the one the host waits for; the window closes
    when the last dispatched step is ready."""
    import jax

    kept = {}
    start = time.perf_counter()
    deadline = start + seconds
    pending = None
    i = first
    n = 0
    while True:
        out = call(i)
        if keep is not None and n in keep:
            kept[n] = out
        if pending is not None:
            jax.block_until_ready(pending)
        pending = out
        i += 1
        n += 1
        if time.perf_counter() >= deadline:
            break
    jax.block_until_ready(pending)
    return n, time.perf_counter() - start, kept


def traced_loop(cell: harness.Cell, call):
    with reduce_trace.traced(cell.out_dir) as tr:
        n, _, _ = closed_loop(call, TRACE_SECONDS)
    return tr.result, n


def kernel_work(cfg: dict, batch: int, train: bool):
    """Ideal work and calls per step of each NHWC kernel family, for the
    roofline readers: one call per pooling stack (its bias, BN and ReLU
    fused in front of the pool: 3 per-channel vectors)."""
    import counts

    stacks = counts.vgg_pool_stacks(cfg)
    fwd = bwd = counts.ZERO
    for h, w, c in stacks:
        fwd = fwd + counts.nhwc_pool_stack_fwd(batch, h, w, c, 3)
        bwd = bwd + counts.nhwc_pool_stack_bwd(batch, h, w, c, 3)
    work, calls = {"nhwc_fwd": fwd}, {"nhwc_fwd": len(stacks)}
    if train:
        work["nhwc_bwd"], calls["nhwc_bwd"] = bwd, len(stacks)
    return work, calls
