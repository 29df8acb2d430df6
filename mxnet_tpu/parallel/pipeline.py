"""Pipeline parallelism: GPipe-style microbatch schedule over a 'pp' axis.

No reference counterpart (SURVEY.md §2.3: model parallelism in the
reference is manual group2ctx placement) — this is the TPU-native design
slot filled first-class: each device on the ``pp`` mesh axis owns ONE
stage's parameters; activations flow stage-to-stage over ICI via
``ppermute`` while microbatches fill and drain the pipe (fill-drain /
GPipe schedule: T = n_micro + n_stages - 1 ticks, bubble fraction
(n_stages-1)/T shrinks as microbatches grow).

Constraints (standard for this schedule): every stage maps activations of
one fixed shape to the same shape (transformer-block shaped), and the
stage function is shared code with per-stage parameters (the leading
parameter axis is sharded over ``pp``).  The whole schedule is one
``lax.fori_loop`` inside ``shard_map`` — differentiable end to end, so a
training step wraps it in ``jax.value_and_grad`` unchanged.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply", "pipeline_parallel"]


def pipeline_apply(stage_params, xs, *, stage_fn: Callable,
                   axis_name: str = "pp"):
    """Run the fill-drain schedule.  Call INSIDE shard_map.

    stage_params: this device's stage parameters (leading stage axis
        already split away by shard_map: each device sees its own slice).
    xs: (n_micro, micro_batch, ...) microbatched input, replicated.
    stage_fn(params, x) -> y with y.shape == x.shape.

    Returns (n_micro, micro_batch, ...) outputs — valid on the LAST stage
    (other stages hold zeros; combine with a psum/gather or read on the
    last stage only, as the loss usually lives there).
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    n_micro = xs.shape[0]
    ticks = n_micro + n - 1
    perm = [(j, (j + 1) % n) for j in range(n)]

    def tick(t, carry):
        state, outputs = carry
        # stage 0 injects microbatch t (clamped; beyond n_micro it keeps
        # injecting the last one — its results never reach outputs)
        inject = xs[jnp.clip(t, 0, n_micro - 1)]
        x_in = jnp.where(idx == 0, inject, state)
        y = stage_fn(stage_params, x_in)
        # the LAST stage finishes microbatch t-(n-1) at tick t
        out_t = t - (n - 1)
        slot = jnp.clip(out_t, 0, n_micro - 1)
        write = jnp.logical_and(idx == n - 1, out_t >= 0)
        outputs = outputs.at[slot].set(
            jnp.where(write, y, outputs[slot]))
        # hand activations to the next stage (the wrap-around n-1 -> 0
        # link carries garbage that stage 0 overwrites with its inject)
        state = lax.ppermute(y, axis_name, perm)
        return state, outputs

    state0 = jnp.zeros(xs.shape[1:], xs.dtype)
    out0 = jnp.zeros_like(xs)
    if hasattr(lax, "pcast"):
        state0 = lax.pcast(state0, (axis_name,), to="varying")
        out0 = lax.pcast(out0, (axis_name,), to="varying")
    _, outputs = lax.fori_loop(0, ticks, tick, (state0, out0))
    return outputs


def pipeline_parallel(stage_fn: Callable, mesh: Mesh, *,
                      pp_axis: str = "pp", n_microbatches: int = None):
    """User-facing wrapper (reference role: the group2ctx placement UX).

    stage_fn(params, x) -> y; returns apply(stacked_params, x) where
    stacked_params has a leading stage axis of size mesh.shape[pp_axis]
    and x is (batch, ...).  The batch splits into microbatches, runs the
    schedule, and returns (batch, ...) outputs gathered from the last
    stage.
    """
    n_stages = mesh.shape[pp_axis]
    n_micro = n_microbatches or n_stages

    def inner(stacked_params, xs):
        params = jax.tree_util.tree_map(lambda p: p[0], stacked_params)
        out = pipeline_apply(params, xs, stage_fn=stage_fn,
                             axis_name=pp_axis)
        # only the last stage holds real outputs: broadcast them to all
        # stages so the result is replicated over pp
        return lax.psum(jnp.where(lax.axis_index(pp_axis) ==
                                  lax.psum(1, pp_axis) - 1, out,
                                  jnp.zeros_like(out)), pp_axis)

    def apply(stacked_params, x):
        n_given = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
        if n_given != n_stages:
            raise ValueError(
                "pipeline_parallel: %d stacked stages but the %r mesh axis "
                "has %d devices (one stage per device)"
                % (n_given, pp_axis, n_stages))
        batch = x.shape[0]
        if batch % n_micro != 0:
            raise ValueError("batch (%d) must divide into %d microbatches"
                             % (batch, n_micro))
        xs = x.reshape((n_micro, batch // n_micro) + x.shape[1:])
        specs_in = (jax.tree_util.tree_map(lambda _: P(pp_axis),
                                           stacked_params),
                    P())
        mapped = shard_map(inner, mesh=mesh, in_specs=specs_in,
                           out_specs=P())
        out = mapped(stacked_params, xs)
        return out.reshape((batch,) + out.shape[2:])

    return apply
