"""mx.runtime — build/runtime feature discovery.

Reference: ``python/mxnet/runtime.py`` (class Feature, feature_list,
Features.is_enabled — backed by libinfo.cc's compile-time flag table).

The rebuild's "build flags" are runtime properties of the JAX/XLA stack:
which PJRT backends are reachable, which dtypes the compiler supports,
and which subsystems this package ships.  Names keep the reference's
spelling where a meaningful mapping exists (CUDA→TPU, MKLDNN→XLA CPU,
OPENCV→PIL, ...) so scripts probing `is_enabled('...')` keep working.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List

__all__ = ["Feature", "Features", "feature_list", "features"]


class Feature:
    def __init__(self, name: str, enabled: bool):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return "[%s %s]" % ("✔" if self.enabled else "✖", self.name)


def _accelerator_reachable() -> bool:
    """True if this process can address a TPU chip.  Asked in process: a
    child probing for the chip would fail to claim what its parent
    already holds."""
    from .device import _accel_devices
    return bool(_accel_devices())


def _have(mod: str) -> bool:
    import importlib.util
    return importlib.util.find_spec(mod) is not None


def feature_list() -> List[Feature]:
    """Check the run-time features (reference: runtime.feature_list)."""
    import jax
    feats = OrderedDict()
    feats["TPU"] = _accelerator_reachable()
    feats["CUDA"] = False           # this build targets TPU via XLA
    feats["CUDNN"] = False
    feats["XLA"] = True
    feats["PALLAS"] = _have("jax.experimental.pallas")
    feats["BLAS_OPEN"] = True       # XLA:CPU's dot lowering
    feats["MKLDNN"] = True          # role: XLA:CPU fused kernels
    feats["OPENCV"] = _have("PIL")  # PIL fills the codec role
    feats["F16C"] = True
    feats["BF16"] = True            # MXU-native
    feats["INT64_TENSOR_SIZE"] = jax.config.jax_enable_x64
    feats["SIGNAL_HANDLER"] = False
    feats["PROFILER"] = True        # mx.profiler over jax.profiler
    feats["DIST_KVSTORE"] = True    # jax.distributed collectives
    feats["SSE"] = True
    feats["LAPACK"] = _have("scipy")
    feats["RECORDIO"] = True
    try:
        from . import _native
        _native.load("recordio")
        feats["NATIVE_RECORDIO"] = True
    except OSError:
        feats["NATIVE_RECORDIO"] = False
    return [Feature(k, v) for k, v in feats.items()]


class Features(Dict[str, Feature]):
    """Dict-like view with is_enabled (reference: runtime.Features)."""

    instance = None

    def __init__(self):
        super().__init__([(f.name, f) for f in feature_list()])

    def __repr__(self):
        return str(list(self.values()))

    def is_enabled(self, feature_name: str) -> bool:
        feature_name = feature_name.upper()
        if feature_name not in self:
            raise RuntimeError("Feature %r does not exist" % feature_name)
        return self[feature_name].enabled


def features() -> Features:
    if Features.instance is None:
        Features.instance = Features()
    return Features.instance
