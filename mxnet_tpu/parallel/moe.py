"""Expert parallelism: gated mixture-of-experts over an 'ep' mesh axis.

No reference counterpart (SURVEY.md §2.3 design slot) — TPU-native MoE:
experts live sharded across the ``ep`` axis (``e_local`` per device);
tokens are top-1 routed, packed to a fixed per-expert capacity (static
shapes — XLA requirement), exchanged with TWO ``all_to_all`` collectives
(dispatch, return), and combined scaled by the gate probability.  Dropped
tokens (over capacity) contribute zeros, the standard GShard/Switch
behavior; gradients flow through the gate via the combine weights.

Everything is jittable and differentiable; correctness is pinned against
a per-token dense reference on the 8-device CPU mesh.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["moe_apply", "moe_parallel", "top1_dispatch"]


def top1_dispatch(gate_logits, n_experts: int, capacity: int):
    """Build dispatch/combine tensors for top-1 routing.

    gate_logits: (T, E).  Returns (dispatch (T,E,C) one-hot placement,
    combine (T,E,C) = dispatch * gate_prob, aux_loss scalar — the Switch
    load-balancing loss).
    """
    probs = jax.nn.softmax(gate_logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)                  # (T,)
    onehot = jax.nn.one_hot(expert, n_experts, dtype=probs.dtype)
    gate = jnp.sum(probs * onehot, axis=-1)              # (T,)
    # position of each token within its expert's queue (1-based at the
    # selected expert, 0 elsewhere; summing over E extracts it)
    pos = jnp.cumsum(onehot, axis=0) * onehot
    keep = (pos <= capacity) & (onehot > 0)
    position = pos.sum(axis=-1).astype(jnp.int32) - 1    # (T,), 0-based
    loc = jax.nn.one_hot(position, capacity, dtype=probs.dtype)  # (T, C)
    dispatch = loc[:, None, :] * keep.astype(probs.dtype)[:, :, None]
    combine = dispatch * gate[:, None, None]
    # Switch aux loss: E * sum_e (fraction tokens to e) * (mean prob to e)
    frac = onehot.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = n_experts * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def moe_apply(x, gate_w, expert_params, *, expert_fn: Callable,
              axis_name: str = "ep", capacity_factor: float = 2.0):
    """Call INSIDE shard_map.  x: (T_local, d) tokens on this device;
    gate_w: (d, E) replicated; expert_params: this device's experts with
    leading axis e_local.  Returns (y (T_local, d), aux_loss)."""
    n = lax.psum(1, axis_name)
    e_local = jax.tree_util.tree_leaves(expert_params)[0].shape[0]
    n_experts = n * e_local
    if gate_w.shape[-1] != n_experts:
        raise ValueError(
            "moe: gate_w routes to %d experts but %d are stacked "
            "(%d devices x %d local)" % (gate_w.shape[-1], n_experts, n,
                                         e_local))
    t_local = x.shape[0]
    capacity = max(1, int(capacity_factor * t_local / n_experts))

    logits = x @ gate_w                                  # (T, E)
    dispatch, combine, aux = top1_dispatch(logits, n_experts, capacity)
    # pack: (E, C, d) expert-major token buffers
    xin = jnp.einsum("tec,td->ecd", dispatch, x)
    # dispatch all_to_all: every device keeps its e_local experts' buffers
    # from ALL devices -> (e_local, n*C, d)
    xin = lax.all_to_all(xin, axis_name, split_axis=0, concat_axis=1,
                         tiled=True)
    yout = jax.vmap(expert_fn)(expert_params, xin)       # (e_local, n*C, d)
    # return all_to_all: back to (E, C, d) token-origin layout
    yout = lax.all_to_all(yout, axis_name, split_axis=1, concat_axis=0,
                          tiled=True)
    y = jnp.einsum("tec,ecd->td", combine, yout)
    return y, lax.pmean(aux, axis_name)


def moe_parallel(expert_fn: Callable, mesh: Mesh, *, ep_axis: str = "ep",
                 capacity_factor: float = 2.0):
    """User-facing wrapper: apply(x, gate_w, stacked_expert_params) with
    x (tokens, d) sharded over ``ep_axis``, experts stacked on a leading
    axis of size n_devices*e_local and sharded over ``ep_axis``.
    Returns (y, aux_loss)."""

    def inner(x, gate_w, expert_params):
        return moe_apply(x, gate_w, expert_params, expert_fn=expert_fn,
                         axis_name=ep_axis,
                         capacity_factor=capacity_factor)

    def apply(x, gate_w, stacked_expert_params):
        espec = jax.tree_util.tree_map(lambda _: P(ep_axis),
                                       stacked_expert_params)
        mapped = shard_map(inner, mesh=mesh,
                           in_specs=(P(ep_axis), P(), espec),
                           out_specs=(P(ep_axis), P()))
        return mapped(x, gate_w, stacked_expert_params)

    return apply
