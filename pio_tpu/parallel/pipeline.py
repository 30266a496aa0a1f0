"""GPipe-style pipeline parallelism (pp) over a mesh axis.

The framework's pipeline-parallel building block: layer stages live one per
device on the `model` axis; microbatches stream through the stages with a
`jax.lax.ppermute` hand-off per tick. Net-new beyond the reference's
capability set (Spark has no model partitioning at all — SURVEY.md §2
"Parallelism & distributed-communication components": TP/PP/SP/EP absent),
built for TPU: the schedule is a `lax.scan` over ticks (static trip count,
reverse-differentiable, one compiled program), the hand-off is a
neighbor-only ppermute that rides ICI, and every device runs the same SPMD
code — bubbles compute masked garbage that never lands in the output.

Schedule (classic GPipe): with S stages and M microbatches the scan runs
S + M - 1 ticks; at tick t device d works on microbatch t - d (when in
range). Forward-only cost: bubble fraction = (S-1)/(S+M-1).
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pio_tpu.parallel.mesh import MODEL_AXIS


def pipeline_apply(
    stage_params,
    x_micro: jax.Array,
    stage_fn: Callable,
    mesh: Mesh,
    axis: str = MODEL_AXIS,
):
    """Run microbatches through per-device stages.

    stage_params: pytree whose leaves have leading axis n_stages ==
    mesh.shape[axis] (stage s's slice lives on device s).
    x_micro: (n_micro, mb, d) microbatches (replicated input).
    stage_fn(stage_param_slice, x) -> y with y.shape == x.shape (the
    inter-stage activation contract; widths may differ INSIDE a stage).

    Returns (n_micro, mb, d) outputs, replicated. Differentiable (the
    schedule is a lax.scan).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    n_ticks = n_stages + n_micro - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    spec_stage = P(axis)
    spec_rep = P()

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: spec_stage, stage_params),
                  spec_rep),
        out_specs=spec_rep,
        check_vma=False,
    )
    def run(p_local, xs):
        d = jax.lax.axis_index(axis)
        p_stage = jax.tree_util.tree_map(lambda a: a[0], p_local)
        mb_shape = xs.shape[1:]

        def tick(carry, t):
            left_in, out = carry
            # stage 0 consumes microbatch t; during drain ticks
            # (t >= n_micro) the clip re-feeds the LAST microbatch — its
            # results are garbage that the validity mask below never
            # lands, but drain-tick inputs are NOT zeros: do not rely on
            # them (e.g. for activation statistics)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            fresh = jax.lax.dynamic_index_in_dim(
                xs, mb_idx, axis=0, keepdims=False
            )
            x_in = jnp.where(d == 0, fresh, left_in)
            y = stage_fn(p_stage, x_in)
            # the LAST stage's result at tick t is microbatch t-(S-1);
            # write it when valid (only the last device holds real data —
            # everyone else writes garbage that the psum mask below drops)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            valid = (d == n_stages - 1) & (t >= n_stages - 1)
            upd = jnp.where(valid, y, 0.0)
            prev = jax.lax.dynamic_index_in_dim(
                out, out_idx, axis=0, keepdims=False
            )
            out = jax.lax.dynamic_update_index_in_dim(
                out, prev + upd, out_idx, axis=0
            )
            # hand activations to the right neighbor for the next tick
            left_in = jax.lax.ppermute(y, axis, perm)
            return (left_in, out), None

        init = (
            jnp.zeros(mb_shape, x_micro.dtype),
            jnp.zeros((n_micro,) + mb_shape, x_micro.dtype),
        )
        (_, out), _ = jax.lax.scan(
            tick, init, jnp.arange(n_ticks, dtype=jnp.int32)
        )
        # out is fully non-zero only on the last device; psum replicates it
        # (every other device contributed zeros)
        return jax.lax.psum(out, axis)

    shard = lambda s: NamedSharding(mesh, s)  # noqa: E731
    p_sharded = jax.tree_util.tree_map(
        lambda a: jax.device_put(a, shard(spec_stage)), stage_params
    )
    xs = jax.device_put(x_micro, shard(spec_rep))
    return run(p_sharded, xs)


def split_microbatches(x: jax.Array, n_micro: int) -> jax.Array:
    """(B, ...) -> (n_micro, B/n_micro, ...)."""
    if x.shape[0] % n_micro != 0:
        raise ValueError(
            f"batch {x.shape[0]} not divisible into {n_micro} microbatches"
        )
    return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
