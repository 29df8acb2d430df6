"""Device contexts (reference: python/mxnet/context.py `class Context`,
include/mxnet/base.h `Context::GPU/CPU`).

TPU-native mapping: a Context names a jax.Device. `mx.tpu(i)` is the
first-class accelerator context (the reference's `mx.gpu(i)` role); `mx.gpu(i)`
is kept as a compatibility alias for the accelerator so reference scripts run
unmodified. `mx.cpu()` maps to the host XLA:CPU backend. Only under the test
harness's explicit `MX_FORCE_CPU=1` does `tpu(i)` resolve to the i-th CPU
device, so the test suite exercises multi-device logic on a forced 8-device
host platform (SURVEY.md §4.5); anywhere else a missing chip is an error.
"""
from __future__ import annotations

import os
import threading
from typing import List, Optional

import jax

from .base import MXNetError, force_cpu as _force_cpu

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "num_gpus", "num_tpus",
           "tpu_memory_info", "gpu_memory_info",
           "current_context", "current_device", "Device"]

_ACCEL_PLATFORM = "tpu"


def _accel_devices() -> List[jax.Device]:
    """Device ids are PROCESS-LOCAL, like the reference's per-worker gpu(i):
    under jax.distributed, rank r's cpu(0)/tpu(0) must resolve to one of
    r's own (addressable) devices, never another process's — hence
    jax.local_devices, not jax.devices."""
    if _force_cpu():    # the harness's pin: no chip is looked for
        return []
    try:
        return list(jax.local_devices(backend=_ACCEL_PLATFORM))
    except RuntimeError:
        return []


def _cpu_devices() -> List[jax.Device]:
    try:
        return jax.local_devices(backend="cpu")
    except RuntimeError:
        # No cpu backend registered (rare); fall back to default platform.
        return jax.local_devices()


class Context:
    """A device context. devtype in {'cpu', 'tpu', 'gpu', 'cpu_pinned'}.

    'gpu' is an alias for the accelerator (tpu); 'cpu_pinned' aliases cpu
    (PJRT manages pinned staging buffers itself).
    """

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 5}
    _default_ctx = threading.local()

    __slots__ = ("device_typeid", "device_id", "_old_ctx")

    def __init__(self, device_type, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        elif isinstance(device_type, str):
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        else:
            self.device_typeid = int(device_type)
            self.device_id = device_id
        self._old_ctx: Optional[Context] = None

    # -- identity ----------------------------------------------------------
    @property
    def device_type(self) -> str:
        return Context.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.canonical_type, self.device_id))

    @property
    def canonical_type(self) -> str:
        """'gpu' and 'tpu' are the same physical accelerator here."""
        t = self.device_type
        if t == "gpu":
            return "tpu"
        if t == "cpu_pinned":
            return "cpu"
        return t

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.canonical_type == other.canonical_type
                and self.device_id == other.device_id)

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    # -- jax mapping -------------------------------------------------------
    @property
    def jax_device(self) -> jax.Device:
        if self.canonical_type != "tpu" or _force_cpu():
            devs = _cpu_devices()
        else:
            devs = _accel_devices()
            if not devs:
                raise MXNetError(
                    "%s: no %r device found (jax default backend is %r, "
                    "JAX_PLATFORMS=%r); set MX_FORCE_CPU=1 only in the test "
                    "harness, where tpu(i) means the i-th host device"
                    % (self, _ACCEL_PLATFORM, jax.default_backend(),
                       os.environ.get("JAX_PLATFORMS", "")))
        if self.device_id >= len(devs):
            raise ValueError(
                "%s: device_id %d out of range (%d %s device(s) visible)"
                % (self, self.device_id, len(devs), self.canonical_type))
        return devs[self.device_id]

    # -- default-context stack (reference: with mx.Context(...)) -----------
    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, *exc):
        Context._default_ctx.value = self._old_ctx
        return False

    def empty_cache(self):
        """Reference: Context.empty_cache. PJRT owns pooling; best-effort."""
        # jax has no public per-device cache drop; live buffers stay valid.
        return None


# Device is the 2.x-era name for Context.
Device = Context


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Compatibility alias: accelerator context (maps to the TPU chip)."""
    return Context("gpu", device_id)


def num_tpus() -> int:
    """Devices tpu(i) can name: the chips, or under MX_FORCE_CPU=1 the
    forced host devices (mirrors tpu()'s resolution)."""
    if _force_cpu():
        return len(_cpu_devices())
    return len(_accel_devices())


def num_gpus() -> int:
    """Reference: mx.context.num_gpus — here the accelerator count."""
    return len(_accel_devices())


def current_context() -> Context:
    if not hasattr(Context._default_ctx, "value"):
        Context._default_ctx.value = Context("cpu", 0)
    return Context._default_ctx.value


current_device = current_context


def tpu_memory_info(device_id: int = 0):
    """(free, total) bytes on the accelerator (reference:
    mx.context.gpu_memory_info → MXGetGPUMemoryInformation64).

    Backed by the PJRT allocator's memory_stats; backends that expose no
    stats (CPU) report (0, 0) — the reference raises there, but a soft
    zero keeps monitoring loops portable across the fake-mesh tests.
    """
    ctx = Context("tpu", device_id)
    stats = ctx.jax_device.memory_stats() or {}
    total = stats.get("bytes_limit", 0)
    used = stats.get("bytes_in_use", 0)
    return (total - used, total)


def gpu_memory_info(device_id: int = 0):
    """Compatibility alias (reference name) for tpu_memory_info."""
    return tpu_memory_info(device_id)
