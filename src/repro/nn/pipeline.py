"""GPipe-style pipeline parallelism over a mesh axis (the "pod" axis).

``pipeline_apply`` runs `stage_fn` per pipeline stage with microbatch
rotation via ``jax.lax.ppermute`` inside a fully-manual ``shard_map``:
stage s holds layers [s·L/S, (s+1)·L/S); microbatches stream through the
classic GPipe schedule (S + M − 1 ticks, bubble fraction (S−1)/(S+M−1)).

Provided as a composable runner (mesh-axis-agnostic) + tests; the default
multi-pod dry-run keeps pod-as-DP (DESIGN.md §5 gives the bubble/link-speed
rationale), so this is the opt-in building block for deeper meshes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _pcast_varying(x, axis: str):
    """Mark a value as device-varying along ``axis`` for shard_map's
    varying-axes checker."""
    return jax.lax.pcast(x, (axis,), to="varying")


def pipeline_apply(mesh, axis: str, stage_fn, stage_params, x_microbatches):
    """Run a pipeline over mesh axis `axis`.

    stage_fn(params_slice, x) -> x     (one stage's computation)
    stage_params: pytree whose leaves have a leading dim == n_stages
    x_microbatches: (M, mb, ...) microbatched input, replicated over `axis`

    Returns (M, mb, ...) outputs (each microbatch has passed through all
    stages, in order).
    """
    n_stages = mesh.shape[axis]
    m = x_microbatches.shape[0]
    ticks = n_stages + m - 1

    def inner(params, xs):
        # each shard holds a (1, ...) slice of the stacked stage params
        params = jax.tree.map(lambda a: a[0], params)
        sid = jax.lax.axis_index(axis)
        # state: the activation currently held by this stage (pcast to
        # device-varying: the loop makes them differ per stage)
        buf = _pcast_varying(jnp.zeros_like(xs[0]), axis)
        outs = _pcast_varying(jnp.zeros_like(xs), axis)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (when valid)
            feed = xs[jnp.clip(t, 0, m - 1)]
            cur = jnp.where(sid == 0, feed, buf)
            # compute this stage on its current microbatch
            y = stage_fn(params, cur)
            # pass to the next stage (ring; the wrap-around result is the
            # pipeline output, collected by the last stage)
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            # the last stage's output for microbatch (t - n_stages + 1)
            out_idx = t - (n_stages - 1)
            done = y  # value produced by the LAST stage this tick
            outs = jnp.where(
                (sid == n_stages - 1) & (out_idx >= 0) & (out_idx < m),
                outs.at[jnp.clip(out_idx, 0, m - 1)].set(done),
                outs,
            )
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # only the last stage collected outputs; psum replicates them
        return jax.lax.psum(outs, axis)

    return jax.shard_map(
        inner, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(),
        axis_names={axis},
    )(stage_params, x_microbatches)
