"""Operator registry + implementations.

Importing this package registers all ops (the role of C++ static-init
registration at dlopen in the reference — SURVEY.md §3.1).
"""
from . import registry
from .registry import register, get_op, list_ops, cached_jit, OpDef

from . import elemwise    # noqa: F401
from . import reduce      # noqa: F401
from . import matrix      # noqa: F401
from . import nn          # noqa: F401
from . import random     # noqa: F401
from . import optimizer  # noqa: F401
from . import rnn       # noqa: F401
from . import attention  # noqa: F401
from . import ssm        # noqa: F401
from . import ssm        # noqa: F401
from . import linalg     # noqa: F401
from . import extra      # noqa: F401
from . import detection  # noqa: F401
from . import spatial    # noqa: F401
from . import control_flow  # noqa: F401
from . import quantization  # noqa: F401
from . import scalar     # noqa: F401
from . import creation   # noqa: F401
from . import misc       # noqa: F401
from . import image      # noqa: F401
from . import nn_extra   # noqa: F401
from . import numpy_ops  # noqa: F401
from . import graph      # noqa: F401
