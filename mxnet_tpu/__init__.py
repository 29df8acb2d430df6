"""mxnet_tpu: a TPU-native deep-learning framework with MXNet's capabilities.

Import as ``import mxnet_tpu as mx`` — the public surface mirrors the
reference (`python/mxnet/__init__.py`): mx.nd (+sparse), mx.np/mx.npx,
mx.sym, mx.mod, mx.autograd, mx.gluon, mx.optimizer, mx.kvstore, mx.io,
mx.image, mx.recordio, mx.metric, mx.amp, mx.profiler, mx.runtime,
mx.callback, mx.monitor, mx.model, mx.init, mx.random, device helpers —
rebuilt on JAX/XLA/PJRT (see SURVEY.md; README "Status" lists the scope
cuts).
"""
from __future__ import annotations

__version__ = "0.1.0"

from . import telemetry as _telemetry
# the start-up timeline's first span: this file's first line to its last
_import_span = _telemetry.phase("import")
_import_span.__enter__()

from .base import MXNetError, get_env, set_env, environment, force_cpu

# Honor the MX_FORCE_CPU=1 pin at import, before anything initializes a
# backend: subprocesses (im2rec, launchers, data workers) inherit only
# the environment, and the variable alone does not keep jax off the chip.
if force_cpu():
    from .base import pin_cpu as _pin_cpu
    _pin_cpu()
del force_cpu
from .device import (Context, Device, cpu, gpu, tpu, cpu_pinned, num_gpus,
                     num_tpus, current_context, current_device,
                     tpu_memory_info, gpu_memory_info)
from . import runtime
from . import engine
from . import programs
from . import ops
from . import ndarray
from . import ndarray as nd
from . import autograd
from . import random
from .ndarray import NDArray, waitall

from . import amp
from . import profiler
from . import visualization
from . import visualization as viz
from . import onnx
from . import numpy as np
from . import npx
from . import recordio
from . import io
from . import image
from . import symbol
from . import name
from . import symbol as sym
from .symbol import AttrScope
from . import contrib
from . import subgraph
from . import initializer
from . import initializer as init
from . import metric
from . import lr_scheduler
from . import optimizer
from . import kvstore
from . import kvstore as kv
from . import gluon
from . import parallel
from . import callback
from . import checkpoint
from . import fault
from . import health
from . import model
from . import monitor
from . import module
from . import module as mod
from . import rnn
from . import util
from . import device as context

# compat: the reference's context.py is a REAL module — register the alias
# so `import mxnet_tpu.context` / `from mxnet_tpu.context import Context`
# work like they do upstream
import sys as _sys
_sys.modules[__name__ + ".context"] = context
from . import operator
from . import attribute
from . import npx as numpy_extension    # 2.x alias: mx.numpy_extension IS npx
_sys.modules[__name__ + ".numpy_extension"] = numpy_extension
from . import tpu_kernel

# Subsystems land milestone-by-milestone (SURVEY.md §7.1); this list grows
# until it covers the reference's full `python/mxnet/__init__.py` surface.
from . import test_utils

_import_span.__exit__(None, None, None)
del _import_span, _telemetry
