"""GPipe-style pipeline parallelism over a mesh axis (opt-in).

Not load-bearing for the assigned shape cells (they all fit DP x TP), but
required posture at 1000+ nodes for deeper-than-memory models.  The
implementation is the classic collective-permute schedule under
``shard_map``: the layer stack is split into ``n_stages`` groups along the
scan axis, microbatches stream through stages, and activations hop stages
via ``ppermute``.  Bubble fraction is (S-1)/(M+S-1) — reported by
:func:`bubble_fraction` so configs can size microbatches.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed import collectives


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def pipeline_apply(block_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   stage_params: Any, x: jnp.ndarray, *, mesh: Mesh,
                   axis: str = "stage", n_microbatches: int = 4
                   ) -> jnp.ndarray:
    """Run ``x`` through ``n_stages`` pipeline stages.

    ``stage_params`` leaves have leading dim = n_stages (one slice per
    stage, sharded over ``axis``); ``block_fn(params_slice, x)`` applies one
    stage's layers.  ``x``: (B, ...) with B divisible by n_microbatches.
    """
    n_stages = mesh.shape[axis]
    b = x.shape[0]
    if b % n_microbatches:
        raise ValueError(f"batch {b} % microbatches {n_microbatches} != 0")
    mb = b // n_microbatches
    micro = x.reshape(n_microbatches, mb, *x.shape[1:])

    def stage_body(params, micro_in):
        """Runs on one device (= one stage) under shard_map."""
        stage = jax.lax.axis_index(axis)
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        n_ticks = n_microbatches + n_stages - 1
        # carries become stage-varying inside the loop; mark them as such
        buf = jax.lax.pcast(jnp.zeros_like(micro_in[0]), (axis,),
                            to="varying")
        outputs = jax.lax.pcast(jnp.zeros_like(micro_in), (axis,),
                                to="varying")

        def tick(carry, t):
            buf, outputs = carry
            # stage 0 ingests microbatch t (when in range)
            feed = jnp.where(t < n_microbatches,
                             micro_in[jnp.minimum(t, n_microbatches - 1)], 0.0)
            inp = jnp.where(stage == 0, feed, buf)
            out = block_fn(params, inp)
            # last stage banks its result for microbatch t-(S-1).  A masked
            # at[].set (not lax.cond): the predicate varies across the
            # shard_map axis, and cond branches must agree on varying-axis
            # types.
            out_idx = t - (n_stages - 1)
            write = (stage == n_stages - 1) & (out_idx >= 0)
            idx = jnp.maximum(out_idx, 0)
            banked = jnp.where(write, out, outputs[idx])
            outputs = outputs.at[idx].set(banked)
            # hop activations to the next stage
            buf = jax.lax.ppermute(
                out, axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (buf, outputs), None

        (_, outputs), _ = jax.lax.scan(tick, (buf, outputs),
                                       jnp.arange(n_ticks))
        # broadcast final outputs from the last stage to all stages
        # (masked psum — multicast ppermute is not universally supported)
        outputs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outputs, 0.0), axis)
        return outputs

    sharded = collectives.shard_map(stage_body, mesh,
                                    in_specs=(P(axis), P()), out_specs=P())
    out = sharded(stage_params, micro)
    return out.reshape(b, *x.shape[1:])
