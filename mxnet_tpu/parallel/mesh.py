"""Mesh construction + sharded training steps.

Reference: the data-parallel machinery of src/kvstore/ (CommDevice reduce,
KVStoreNCCL allreduce, kvstore_dist PS) and gluon Trainer's step — here ONE
jitted function over a `jax.sharding.Mesh`: the forward, loss, backward,
gradient allreduce and optimizer update compile into a single XLA program
whose collectives XLA schedules to overlap with the backward pass (the
per-key engine-op overlap property of SURVEY.md §3.5, now in the compiler).

Tensor parallelism (absent in the reference, SURVEY.md §2.3 design slot):
Megatron-style column/row sharding of Dense weights via NamedSharding —
XLA inserts the psum at the row-sharded matmul.

Multi-host: `init_process_group` wraps jax.distributed.initialize (the
`tools/launch.py` / DMLC_ROLE env role).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Sequence

import numpy as _np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..gluon.block import functionalize

__all__ = ["make_mesh", "replicated", "batch_sharded", "shard_params_tp",
           "TrainStep", "init_process_group"]


def init_process_group(coordinator_address: Optional[str] = None,
                       num_processes: Optional[int] = None,
                       process_id: Optional[int] = None,
                       initialization_timeout: Optional[int] = None):
    """Multi-host process group over DCN (reference role: ps-lite
    Postoffice::Start + DMLC_* env; here jax.distributed.initialize).

    Arguments default from the env contract tools/launch.py sets
    (MX_COORDINATOR / MX_NUM_PROCESSES / MX_PROCESS_ID), the way the
    reference workers read DMLC_PS_ROOT_URI & co from their tracker.
    ``initialization_timeout`` (seconds, also env MX_INIT_TIMEOUT) bounds
    the coordinator handshake so a failed pairing surfaces as an error the
    launcher can retry with a fresh port instead of a 5-minute hang.
    """
    from ..base import get_env
    if coordinator_address is None:
        coordinator_address = get_env("MX_COORDINATOR") or None
    if num_processes is None and get_env("MX_NUM_PROCESSES"):
        num_processes = int(get_env("MX_NUM_PROCESSES"))
    if process_id is None and get_env("MX_PROCESS_ID"):
        process_id = int(get_env("MX_PROCESS_ID"))
    if initialization_timeout is None and get_env("MX_INIT_TIMEOUT"):
        initialization_timeout = int(get_env("MX_INIT_TIMEOUT"))
    kwargs = {}
    if initialization_timeout is not None:
        import inspect
        import warnings
        sig = inspect.signature(jax.distributed.initialize)
        if "initialization_timeout" in sig.parameters:
            kwargs["initialization_timeout"] = initialization_timeout
        else:
            warnings.warn("this jax has no initialization_timeout kwarg; "
                          "the requested %ss handshake bound is ignored"
                          % initialization_timeout)
    jax.distributed.initialize(coordinator_address, num_processes,
                               process_id, **kwargs)


def make_mesh(axes: Sequence[str] = ("dp",),
              shape: Optional[Sequence[int]] = None,
              devices=None) -> Mesh:
    """Build a Mesh over the visible devices.

    Default: all devices on one 'dp' axis.  shape=(dp, tp) splits them 2-D;
    -1 infers one dimension.  On a real pod, jax's device order keeps ICI
    neighbours adjacent, so the innermost axis gets the fastest links.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if shape is None:
        shape = [n] + [1] * (len(axes) - 1)
    shape = list(shape)
    if -1 in shape:
        known = 1
        for s in shape:
            if s != -1:
                known *= s
        shape[shape.index(-1)] = n // known
    arr = _np.asarray(devices[:int(_np.prod(shape))]).reshape(shape)
    return Mesh(arr, tuple(axes))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharded(mesh: Mesh, axis: str = "dp") -> NamedSharding:
    """Shard axis 0 (batch) over the data-parallel mesh axis."""
    return NamedSharding(mesh, P(axis))


def shard_params_tp(param_values: Dict[str, jax.Array], mesh: Mesh,
                    tp_axis: str = "tp",
                    rules: Optional[Dict[str, Any]] = None):
    """Deprecated thin alias: Megatron-style TP placement for Dense
    weights, now owned by :mod:`mxnet_tpu.parallel.speclayout` (the one
    source of truth for parameter shardings — ISSUE 14).  Same
    semantics as ever: explicit ``rules`` ({name-substring:
    PartitionSpec}; unmatched params replicate), else column/row
    alternation for consecutive 2-D '.weight' params.  New code should
    build a :class:`~mxnet_tpu.parallel.speclayout.SpecLayout` and call
    :func:`~mxnet_tpu.parallel.speclayout.shard_params` (which adds the
    fsdp/ZeRO sheet-sharding this TP-only surface never had).

    NOTE: sharding choices here NEVER change results — XLA inserts the
    collectives that preserve the math; a suboptimal layout only costs
    communication.
    """
    from .speclayout import shard_params_tp as _impl
    return _impl(param_values, mesh, tp_axis=tp_axis, rules=rules)


class TrainStep:
    """One jitted data-parallel (+optional TP) training step.

    Built from a Gluon block via functionalize(); the returned callable has
    signature step(params, opt_state, *batch) -> (params, opt_state, loss).
    SGD+momentum by default (enough for the dry-run and the bench; the full
    optimizer set runs through gluon.Trainer's eager path).
    """

    def __init__(self, block, loss_fn: Callable, mesh: Mesh,
                 learning_rate: float = 0.01, momentum: float = 0.9,
                 dp_axis: str = "dp", tp_axis: str = "tp",
                 tp_rules: Optional[Dict[str, Any]] = None,
                 donate: bool = True):
        pure_fn, param_values = functionalize(block)
        self.mesh = mesh
        self.params = shard_params_tp(param_values, mesh, tp_axis, tp_rules)
        self.opt_state = jax.tree_util.tree_map(jnp.zeros_like, self.params)
        self._batch_sharding = batch_sharded(mesh, dp_axis)
        lr, mom = learning_rate, momentum
        from ..ops.attention import attention_partition_scope
        from .speclayout import SpecLayout
        layout = SpecLayout(mesh, data_axis=dp_axis, tp_axis=tp_axis)

        def step(params, opt_state, *batch):
            def loss_of(p):
                # flash kernels run per shard of this mesh (a Mosaic
                # kernel cannot be partitioned by GSPMD)
                with attention_partition_scope(layout):
                    out = pure_fn(p, *batch[:-1], training=True)
                return loss_fn(out, batch[-1])

            loss, grads = jax.value_and_grad(loss_of)(params)
            # batch is dp-sharded: jax.grad's sum over examples makes XLA
            # emit the gradient all-reduce (psum over 'dp') automatically,
            # overlapped with backward by the latency-hiding scheduler
            new_opt = jax.tree_util.tree_map(
                lambda m, g: mom * m - lr * g, opt_state, grads)
            new_params = jax.tree_util.tree_map(
                lambda p, m: p + m, params, new_opt)
            return new_params, new_opt, loss

        self._step_fn = step
        self._donate = donate
        from ..programs import register_program
        self._step = register_program(
            "mesh.train_step", step, mode="light",
            donate_argnums=(0, 1) if donate else ())
        self._multi = {}

    def shard_batch(self, *arrays):
        """Place host batches onto the dp-sharded layout.  Multi-host: each
        process passes its LOCAL shard (the data-loader's part_index slice)
        and the pieces assemble into one global array — the reference's
        dist-training contract where every worker feeds its own partition."""
        if jax.process_count() > 1:
            return tuple(
                jax.make_array_from_process_local_data(
                    self._batch_sharding, _np.asarray(a))
                for a in arrays)
        return tuple(jax.device_put(a, self._batch_sharding) for a in arrays)

    def run_steps(self, k: int, *batch):
        """Run k steps under ONE jit dispatch (lax.fori_loop over the step
        body, same batch each iteration).  Perf diagnostic: comparing
        k-step against k x one-step isolates per-step dispatch/transfer
        overhead (host work) from device compute — the
        reference's benchmark_score.py plays the same trick with its
        wait_to_read-once loop."""
        batch = self.shard_batch(*batch)
        if k not in self._multi:
            step_fn = self._step_fn

            def multi(params, opt_state, *b):
                def body(_, carry):
                    p, o, _loss = carry
                    p, o, loss = step_fn(p, o, *b)
                    return p, o, loss.astype(jnp.float32)
                return lax.fori_loop(
                    0, k, body,
                    (params, opt_state, jnp.zeros((), jnp.float32)))
            from ..programs import register_program
            self._multi[k] = register_program(
                "mesh.train_window", multi, mode="light",
                donate_argnums=(0, 1) if self._donate else ())
        self.params, self.opt_state, loss = self._multi[k](
            self.params, self.opt_state, *batch)
        return loss

    def __call__(self, *batch):
        batch = self.shard_batch(*batch)
        self.params, self.opt_state, loss = self._step(
            self.params, self.opt_state, *batch)
        return loss

    def save(self, path: str) -> None:
        """Sharded checkpoint of params+opt_state (mxnet_tpu.checkpoint)."""
        from ..checkpoint import save_sharded
        save_sharded(path, {"params": self.params,
                            "opt_state": self.opt_state})

    def restore(self, path: str) -> None:
        """Restore in place, re-laying-out onto THIS step's shardings
        (elastic: the saving mesh may have differed)."""
        from ..checkpoint import restore_sharded
        state = restore_sharded(
            path,
            template={"params": self.params, "opt_state": self.opt_state})
        self.params = state["params"]
        self.opt_state = state["opt_state"]

    def write_back(self, block):
        """Copy trained params back into the Block's Parameters.

        Step params are GLOBAL (mesh-sharded/replicated) arrays; the
        block's NDArrays are single-device — materialize the local copy
        (replicated: shard 0 IS the value; sharded: gather first) and
        re-home it, or later eager ops mix single- and multi-device
        operands and fail."""
        params = block.collect_params()
        for name, v in self.params.items():
            arr = params[name].data()
            if hasattr(v, "sharding") and not isinstance(
                    v.sharding, jax.sharding.SingleDeviceSharding):
                if getattr(v.sharding, "is_fully_replicated", False):
                    local = v.addressable_data(0)
                elif jax.process_count() > 1:
                    # spans non-addressable devices: multihost gather
                    from jax.experimental import multihost_utils
                    local = multihost_utils.process_allgather(
                        v, tiled=True)
                else:
                    local = jax.device_get(v)      # gather sharded param
            else:
                local = v
            arr._set_jax(jax.device_put(jnp.asarray(local),
                                        arr.context.jax_device)
                         .astype(arr.dtype))
